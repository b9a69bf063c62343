"""Controls: the plain algorithm put in the program's place, one guarantee
weaker than the configuration states.  A sound comparison has to find
each of them incorrect.

* ``bfs_bounded`` — BFS whose per-round message buffer holds an eighth of
  the edges and drops the rest, as a frontier compacted into a buffer
  sized for the average frontier would: it breaks the guarantee that
  every message is delivered exactly once (distances come out too long,
  or unreached).  The configuration states no precision below int32, so
  this, and not a narrower integer, is the BFS control.
* ``pagerank_bf16`` — Graphalytics PageRank computed in bfloat16, the
  precision one step below the configuration's float32.

Both take the arrays of the program's graph (``src``, ``dst``) and
return what the program's entry returns, so a driver cannot tell them
from the program.
"""
from __future__ import annotations

import types
from functools import partial

import jax
import jax.numpy as jnp

BFS_INF = 2 ** 30
BUFFER_SHARE = 8          # the buffer holds edges // BUFFER_SHARE messages


@partial(jax.jit, static_argnames=("n", "cap"))
def _bfs_bounded(src, dst, source, *, n: int, cap: int):
    e = src.shape[0]
    dist = jnp.full((n,), BFS_INF, jnp.int32).at[source].set(0)
    frontier = jnp.zeros((n,), bool).at[source].set(True)

    def body(c):
        dist, frontier, level, msgs = c
        active = frontier[src]
        idx = jnp.nonzero(active, size=cap, fill_value=e)[0]
        tgt = jnp.where(idx < e, dst[jnp.minimum(idx, e - 1)], n)
        new = dist.at[tgt].min(level + 1, mode="drop")
        return (new, new != dist, level + 1,
                msgs + jnp.sum(active.astype(jnp.int32)))

    z = jnp.zeros((), jnp.int32)
    dist, _, rounds, msgs = jax.lax.while_loop(
        lambda c: jnp.any(c[1]), body, (dist, frontier, z, z))
    return dist, rounds, msgs


def bfs_bounded(g, source, **_):
    """Stands in for ``repro.graphs.algorithms.bfs.bfs``."""
    cap = max(1, g.num_edges // BUFFER_SHARE)
    dist, rounds, msgs = _bfs_bounded(g.src, g.dst, source,
                                      n=g.num_vertices, cap=cap)
    return types.SimpleNamespace(dist=dist, rounds=rounds, messages=msgs)


@partial(jax.jit, static_argnames=("n", "iters"))
def _pagerank_bf16(src, dst, d, *, n: int, iters: int):
    bf = jnp.bfloat16
    deg = jnp.zeros((n,), jnp.int32).at[src].add(1)
    inv = (d / jnp.maximum(deg, 1)).astype(bf)
    dangling = deg == 0
    rank = jnp.full((n,), 1.0 / n, bf)
    for _ in range(iters):
        contrib = rank[src] * inv[src]
        acc = jnp.zeros((n,), bf).at[dst].add(contrib)
        dm = (d * jnp.sum(jnp.where(dangling, rank, 0).astype(bf))).astype(bf)
        rank = (bf((1.0 - d) / n) + acc + dm / bf(n)).astype(bf)
    return rank.astype(jnp.float32)


def pagerank_bf16(g, *, d, iters, **_):
    """Stands in for ``repro.graphs.algorithms.pagerank.pagerank``."""
    return _pagerank_bf16(g.src, g.dst, d, n=g.num_vertices,
                          iters=iters), None
