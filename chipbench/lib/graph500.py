"""Graph500 Kronecker edge lists and their CSR, independent of the program.

``kronecker_edges`` copies the edge-list part of the program's
``repro.graphs.generators.kronecker`` (initiator A/B/C/D, one float32
draw per level, chunks of ``KRONECKER_CHUNK`` edges each seeded by
``(seed, chunk)``, then a seeded vertex permutation), so that a later
change to the program cannot change the benchmark's data.  The program
ingests the list through its own ``from_edges``; the reference builds
its CSR with ``csr`` below.  A configuration draws its edges once, from
its structure seed; a run's seed only relabels the vertices
(``label_seed``), so every run holds the same graph and the same work.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

KRONECKER_CHUNK = 1 << 20      # edges per independent random stream


def _chunk(src, dst, scale, rng, a, ab, abc):
    """Fill one chunk of endpoints: per level, one uniform draw picks the
    quadrant (a, b, c, d) of the recursive adjacency matrix."""
    for _ in range(scale):
        r = rng.random(src.shape[0], dtype=np.float32)
        bottom = r >= ab
        right = (r >= abc) | ((r >= a) & ~bottom)
        src <<= 1
        src |= bottom
        dst <<= 1
        dst |= right


def kronecker_edges(scale: int, edge_factor: int, seed: int, *,
                    a: float, b: float, c: float,
                    label_seed: int | None = None):
    """``(src, dst)`` int32 arrays of ``edge_factor * 2**scale`` directed
    Kronecker edges with permuted vertex labels (self-loops and
    duplicates included, as generated).  ``seed`` draws the edges;
    ``label_seed`` (``seed`` unless given) draws the permutation of the
    labels, so two label seeds give the same graph under two labellings."""
    n = 1 << scale
    m = edge_factor * n
    src = np.zeros(m, np.int32)
    dst = np.zeros(m, np.int32)

    def fill(start):
        sl = slice(start, start + KRONECKER_CHUNK)
        rng = np.random.default_rng([seed, start // KRONECKER_CHUNK])
        _chunk(src[sl], dst[sl], scale, rng, a, a + b, a + b + c)

    with ThreadPoolExecutor(max_workers=os.cpu_count() or 1) as pool:
        list(pool.map(fill, range(0, m, KRONECKER_CHUNK)))
    perm = label_permutation(n, seed if label_seed is None else label_seed)
    return perm[src], perm[dst]


def label_permutation(n: int, seed: int) -> np.ndarray:
    """The vertex labels ``kronecker_edges`` gives under label seed
    ``seed``: vertex ``v`` as drawn is labelled ``perm[v]``."""
    return np.random.default_rng(seed).permutation(n).astype(np.int32)


def relabelling(n: int, from_seed: int, to_seed: int) -> np.ndarray:
    """``map`` with ``map[v]`` the label under ``to_seed`` of the vertex
    labelled ``v`` under ``from_seed``."""
    return label_permutation(n, to_seed)[
        np.argsort(label_permutation(n, from_seed))]


def csr(src, dst, n: int):
    """Undirected simple graph of an edge list: self-loops dropped, both
    directions kept once.  Returns ``(indptr int64 [n+1], adj int32)``
    with each row's neighbours sorted."""
    keep = src != dst
    s, d = src[keep].astype(np.int64), dst[keep].astype(np.int64)
    key = np.unique(np.concatenate([s * n + d, d * n + s]))
    rows, adj = np.divmod(key, n)
    indptr = np.zeros(n + 1, np.int64)
    indptr[1:] = np.cumsum(np.bincount(rows, minlength=n))
    return indptr, adj.astype(np.int32)


def degree(src, dst, n: int) -> np.ndarray:
    """Undirected degree of each vertex in the edge list as generated
    (self-loops do not count; duplicates do, which leaves degree >= 1
    unchanged)."""
    keep = src != dst
    return (np.bincount(src[keep], minlength=n)
            + np.bincount(dst[keep], minlength=n))


def search_keys(deg, count: int, seed: int) -> np.ndarray:
    """``count`` distinct Graph500 search keys drawn from the seed among
    the vertices of degree >= 1 (``deg`` from :func:`degree`)."""
    rng = np.random.default_rng([seed, 1])
    return rng.permutation(np.flatnonzero(deg > 0))[:count]


def depth_stratified(levels, seed: int) -> list:
    """An order of the keys ``0 .. len(levels)-1``, drawn from the seed,
    in which every prefix holds each number of BFS levels in its share of
    the whole set, to within one key.  The sequence of depths is the same
    for every seed; the seed orders the keys of one depth."""
    rng = np.random.default_rng([seed, 2])
    depths = sorted(set(levels))
    pools = {lv: rng.permutation([i for i, x in enumerate(levels)
                                  if x == lv]).tolist() for lv in depths}
    share = {lv: len(pools[lv]) / len(levels) for lv in depths}
    taken = dict.fromkeys(depths, 0)
    out = []
    for j in range(1, len(levels) + 1):
        lv = max((lv for lv in depths if taken[lv] < len(pools[lv])),
                 key=lambda lv: (share[lv] * j - taken[lv], -lv))
        out.append(pools[lv][taken[lv]])
        taken[lv] += 1
    return out
