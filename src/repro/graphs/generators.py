"""Graph generators: Kronecker (Graph500), Erdős–Rényi, and structural
analogues of the paper's Table-1 SNAP families (offline container — see
DESIGN.md §7: degree-distribution + diameter-regime matched synthetics).
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from repro.graphs.csr import Graph, from_edges


KRONECKER_CHUNK = 1 << 20      # edges per independent random stream


def _kronecker_chunk(src, dst, scale, rng, a, ab, abc):
    """Fill one chunk of edge endpoints: per level, one uniform draw picks
    the quadrant (a, b, c, d) of the recursive adjacency matrix."""
    for _ in range(scale):
        r = rng.random(src.shape[0], dtype=np.float32)
        bottom = r >= ab
        right = (r >= abc) | ((r >= a) & ~bottom)
        src <<= 1
        src |= bottom
        dst <<= 1
        dst |= right


def kronecker(scale: int, edge_factor: int = 16, seed: int = 0,
              a=0.57, b=0.19, c=0.19) -> Graph:
    """Graph500 Kronecker generator (power-law degree distribution).

    Edges are drawn in chunks of :data:`KRONECKER_CHUNK`, each from its
    own stream seeded by ``(seed, chunk)``, on the host's cores; the
    graph depends on ``seed`` only, not on the number of threads."""
    n = 1 << scale
    m = edge_factor * n
    itype = np.int32 if scale < 31 else np.int64
    src = np.zeros(m, itype)
    dst = np.zeros(m, itype)
    starts = range(0, m, KRONECKER_CHUNK)

    def fill(start):
        sl = slice(start, start + KRONECKER_CHUNK)
        rng = np.random.default_rng([seed, start // KRONECKER_CHUNK])
        _kronecker_chunk(src[sl], dst[sl], scale, rng, a, a + b, a + b + c)

    with ThreadPoolExecutor(max_workers=os.cpu_count() or 1) as pool:
        list(pool.map(fill, starts))
    perm = np.random.default_rng(seed).permutation(n).astype(itype)
    return from_edges(perm[src], perm[dst], n, symmetrize=True)


def erdos_renyi(n: int, avg_degree: float = 8.0, seed: int = 0) -> Graph:
    rng = np.random.default_rng(seed)
    m = int(n * avg_degree / 2)
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n, m)
    return from_edges(src, dst, n, symmetrize=True)


def grid2d(side: int) -> Graph:
    """Road-network analogue: 2-D grid (large diameter, degree <= 4)."""
    idx = np.arange(side * side).reshape(side, side)
    s1, d1 = idx[:, :-1].ravel(), idx[:, 1:].ravel()
    s2, d2 = idx[:-1, :].ravel(), idx[1:, :].ravel()
    src = np.concatenate([s1, s2])
    dst = np.concatenate([d1, d2])
    return from_edges(src, dst, side * side, symmetrize=True)


def preferential(n: int, m_per: int = 4, seed: int = 0) -> Graph:
    """Social-network analogue: Barabási–Albert preferential attachment."""
    rng = np.random.default_rng(seed)
    targets = list(range(m_per))
    repeated: list[int] = []
    src_l, dst_l = [], []
    for v in range(m_per, n):
        ts = rng.choice(targets if len(repeated) == 0 else repeated,
                        size=m_per)
        for t in ts:
            src_l.append(v)
            dst_l.append(int(t))
        repeated.extend(ts.tolist())
        repeated.extend([v] * m_per)
        targets.append(v)
    return from_edges(np.array(src_l), np.array(dst_l), n, symmetrize=True)


def bipartite_web(n: int, hubs: int = 32, avg_degree: float = 6.0,
                  seed: int = 0) -> Graph:
    """Web-graph analogue: hub-dominated structure (few very high degree
    vertices + sparse tail)."""
    rng = np.random.default_rng(seed)
    m = int(n * avg_degree)
    hub_ids = rng.integers(0, hubs, m)
    src = rng.integers(0, n, m)
    dst = np.where(rng.random(m) < 0.7, hub_ids, rng.integers(0, n, m))
    return from_edges(src, dst, n, symmetrize=True)


def random_weights(g: Graph, seed: int = 0, low=0.1, high=10.0) -> Graph:
    """Attach symmetric random weights (for SSSP / Boruvka)."""
    import dataclasses as dc
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    src = np.asarray(g.src)
    dst = np.asarray(g.dst)
    lo = np.minimum(src, dst).astype(np.int64)
    hi = np.maximum(src, dst).astype(np.int64)
    key = lo * g.num_vertices + hi
    # same weight for both directions of an undirected edge
    h = (np.abs(np.sin(key * 12.9898 + seed)) * (high - low) + low)
    return dc.replace(g, weights=jnp.asarray(h.astype(np.float32)))


# Table-1 family registry (paper §6.1.2): structurally-matched synthetics.
TABLE1_FAMILIES = {
    "cWT-comm": lambda n, seed=0: bipartite_web(n, hubs=max(8, n // 1000),
                                                avg_degree=4, seed=seed),
    "sLV-social": lambda n, seed=0: kronecker(
        max(int(np.log2(max(n, 2))), 4), 14, seed=seed),
    "sYT-social": lambda n, seed=0: preferential(n, 3, seed=seed),
    "pAM-purchase": lambda n, seed=0: preferential(n, 8, seed=seed),
    "rCA-road": lambda n, seed=0: grid2d(int(np.sqrt(n))),
    "ciP-citation": lambda n, seed=0: erdos_renyi(n, 8.0, seed=seed),
    "wGL-web": lambda n, seed=0: bipartite_web(n, hubs=max(8, n // 500),
                                               avg_degree=12, seed=seed),
}
