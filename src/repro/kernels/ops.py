"""Public jit'd wrappers for the Pallas kernels.

``use_pallas`` in RunConfig routes the framework's hot-spots through these;
on CPU they run in interpret mode, on TPU compiled
(:func:`repro.kernels.interpret_default`).
Every wrapper has a pure-jnp oracle in :mod:`repro.kernels.ref` and a
shape/dtype sweep in ``tests/test_kernels.py``.
"""
from __future__ import annotations

from repro.kernels import interpret_default
from repro.kernels.coalesce import bucket_count_pallas
from repro.kernels.coarse_commit import coarse_commit_pallas
from repro.kernels.ssd_chunk import ssd_chunk_pallas
from repro.kernels import ref


def coarse_commit(state, idx, val, *, op="min", tile_m=256, block_v=512):
    return coarse_commit_pallas(state, idx, val, op=op, tile_m=tile_m,
                                block_v=block_v, interpret=interpret_default())


def bucket_count(owner, *, num_buckets, tile_m=512):
    return bucket_count_pallas(owner, num_buckets=num_buckets, tile_m=tile_m,
                               interpret=interpret_default())


def ssd_chunk(C, B, x, a):
    return ssd_chunk_pallas(C, B, x, a, interpret=interpret_default())


__all__ = ["coarse_commit", "bucket_count", "ssd_chunk", "ref"]
