"""Distributed multi-vertex transactions — the ownership protocol (§4.3).

The paper's protocol: a transaction touching remote vertices CAS-marks each
element's *ownership marker*, migrates marked elements, retries on conflict
with random backoff (livelock possible — §5.7).

TPU adaptation (DESIGN.md §7): synchronous *bidding rounds*.  Every pending
transaction bids for ALL its vertices with a min-commit of its rotating
priority key (the CAS analogue — lowest bid wins the marker); a transaction
that wins every bid applies atomically this round, everyone else retries
next round.  Rotating priorities make the protocol deterministic and
livelock-free (the globally-minimal pending transaction always wins all its
bids), replacing random backoff.

Used by ``benchmarks/fig5_coalescing.py`` scenarios O-1..O-4.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as Ps

from repro.core import commit as C
from repro.core.engine import EngineConfig, wave_until_delivered
from repro.core.messages import make_messages


@dataclasses.dataclass
class TxnStats:
    rounds: jax.Array           # rounds until all transactions committed
    retries: jax.Array          # total (txn, round) retry events
    bids: jax.Array             # total bid messages sent


def run_transactions(mesh, txns, num_vertices: int, *, axis: str = "data",
                     capacity: int = 2048, max_rounds: int = 1024):
    """txns: int32 [P, X, K] global vertex ids per shard-local transaction.
    Applies visited |= 1 to every vertex of every transaction, atomically
    per transaction.  Returns (visited [V], TxnStats)."""
    P = mesh.shape[axis]
    X, K = txns.shape[1], txns.shape[2]
    block = -(-num_vertices // P)
    vpad = P * block
    total = P * X
    ecfg_bid = EngineConfig(P, block, capacity, axis=axis, op="min")
    ecfg_apply = EngineConfig(P, block, capacity, axis=axis, op="or")

    def shard_fn(txn):
        txn = txn[0]                                    # [X, K]
        shard = jax.lax.axis_index(axis)
        gid = shard * X + jnp.arange(X, dtype=jnp.int32)
        # duplicate vertices inside one transaction bid once (the dup lanes
        # auto-succeed — a transaction cannot conflict with itself)
        dup = jnp.zeros((X, K), bool)
        for k in range(1, K):
            dup = dup.at[:, k].set(
                jnp.any(txn[:, :k] == txn[:, k:k + 1], axis=1))

        def cond(c):
            done, visited, it, *_ = c
            n = jax.lax.psum(jnp.sum((~done).astype(jnp.int32)), axis)
            return (n > 0) & (it < max_rounds)

        def body(c):
            done, visited, it, retries, bids = c
            prio = (gid + it * jnp.int32(1000003)) % total
            key = prio * total + gid   # unique, rotating; needs total^2 < 2^31
            markers = jnp.full((block,), jnp.int32(2 ** 30), jnp.int32)
            targets = txn.reshape(X * K)
            payload = jnp.repeat(key, K)
            valid = jnp.repeat(~done, K) & ~dup.reshape(X * K)
            markers, success, _, _, _ = wave_until_delivered(
                ecfg_bid, markers, targets, payload, valid)
            granted = success.reshape(X, K) | dup
            win = jnp.all(granted, axis=1) & ~done
            # winners apply atomically (visited-mark wave)
            visited, _, _, _, _ = wave_until_delivered(
                ecfg_apply, visited, targets,
                jnp.ones((X * K,), bool), jnp.repeat(win, K))
            retries = retries + jnp.sum((~done & ~win).astype(jnp.int32))
            bids = bids + jnp.sum(valid.astype(jnp.int32))
            return done | win, visited, it + 1, retries, bids

        done0 = jnp.zeros((X,), bool)
        vis0 = jnp.zeros((block,), bool)
        z = jnp.zeros((), jnp.int32)
        done, visited, rounds, retries, bids = jax.lax.while_loop(
            cond, body, (done0, vis0, z, z, z))
        all_done = jax.lax.psum(jnp.sum(done.astype(jnp.int32)), axis)
        return visited, rounds, retries, bids, all_done

    fn = jax.shard_map(shard_fn, mesh=mesh, in_specs=(Ps(axis),),
                    out_specs=(Ps(axis), Ps(), Ps(), Ps(), Ps()),
                    check_vma=False)
    visited, rounds, retries, bids, all_done = jax.jit(fn)(txns)
    assert int(all_done) == total, (int(all_done), total)
    return (visited[:num_vertices],
            TxnStats(rounds=rounds, retries=retries, bids=bids))
