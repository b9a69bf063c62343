"""Plain NumPy references (copied from the program's ``chip_smoke.py``).

Both run on the CSR of :func:`chipbench.lib.graph500.csr`, in float64
for PageRank, and share nothing with the program under test."""
from __future__ import annotations

import numpy as np

BFS_INF = 2 ** 30       # the program's "unreached" distance


def bfs_reference(indptr, adj, root: int) -> np.ndarray:
    """Level-synchronous BFS: each level expands the frontier's CSR ranges
    in one vectorized gather and marks the unseen neighbours."""
    dist = np.full(indptr.size - 1, BFS_INF, np.int32)
    dist[root] = 0
    frontier = np.asarray([root], np.int64)
    level = 0
    while frontier.size:
        starts = indptr[frontier]
        counts = indptr[frontier + 1] - starts
        total = int(counts.sum())
        if total == 0:
            break
        offs = (np.repeat(starts - np.cumsum(counts) + counts, counts)
                + np.arange(total))
        nbrs = adj[offs]
        level += 1
        dist[nbrs[dist[nbrs] == BFS_INF]] = level
        frontier = np.flatnonzero(dist == level)
    return dist


def levels(dist) -> int:
    """BFS levels of a search: its key's eccentricity plus one, the
    rounds a level-synchronous search runs."""
    return int(dist[dist < BFS_INF].max()) + 1


def reached_edges(indptr, dist) -> int:
    """Undirected edges of the component a search reached: the reached
    vertices' degrees summed and halved (Graph500's count of input
    edges traversed)."""
    deg = np.diff(indptr)
    return int(deg[dist < BFS_INF].sum()) // 2


def pagerank_reference(indptr, adj, *, d: float, iters: int,
                       pool=None) -> np.ndarray:
    """Graphalytics PageRank in float64: ``iters`` synchronous iterations
    from 1/V, damping ``d``, the rank of dangling vertices spread over
    all vertices.  The graph is symmetric, so the in-edges of ``v`` are
    its CSR row: one gather and one ``np.add.reduceat`` per block of
    rows, the blocks spread over ``pool``'s threads."""
    v = indptr.size - 1
    deg = np.diff(indptr)
    rank = np.full(v, 1.0 / v)
    inv = d / np.maximum(deg, 1)
    rows = np.flatnonzero(deg)                     # reduceat needs runs > 0
    blocks = [b for b in np.array_split(rows, max(1, min(rows.size, 16)))
              if b.size]

    def pull(y, acc, blk):
        e0, e1 = indptr[blk[0]], indptr[blk[-1] + 1]
        acc[blk] = np.add.reduceat(y[adj[e0:e1]], indptr[blk] - e0)

    for _ in range(iters):
        y = rank * inv
        acc = np.zeros(v)
        list((pool.map if pool else map)(lambda b: pull(y, acc, b), blocks))
        dangling = d * rank[deg == 0].sum()
        rank = (1 - d) / v + acc + dangling / v
    return rank


def rank_error(got, want) -> float:
    """Largest relative gap of a rank vector from the reference (every
    reference rank is at least (1-d)/V > 0)."""
    got = np.asarray(got, np.float64)
    return float(np.max(np.abs(got - want) / want))
