"""Pallas kernel sweeps: shapes x dtypes x ops vs the pure-jnp oracles
(interpret mode on CPU; the same kernels compile on TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.kernels import ops
from repro.kernels.ref import bucket_count_ref, coarse_commit_ref, ssd_chunk_ref

SET = dict(max_examples=15, deadline=None)
RNG = np.random.default_rng(0)


@pytest.mark.parametrize("op", ["min", "max", "add"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
@pytest.mark.parametrize("v,n", [(64, 32), (513, 1000), (2048, 300),
                                 (100, 4096)])
def test_coarse_commit_sweep(op, dtype, v, n):
    state = jnp.asarray(RNG.integers(-50, 50, v)).astype(dtype)
    idx = jnp.asarray(RNG.integers(-1, v, n), jnp.int32)
    val = jnp.asarray(RNG.integers(-50, 50, n)).astype(dtype)
    out = ops.coarse_commit(state, idx, val, op=op, tile_m=128, block_v=256)
    exp = coarse_commit_ref(state, idx, val, op=op)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-5)


@pytest.mark.parametrize("op", ["or", "first"])
@pytest.mark.parametrize("v,n", [(64, 32), (513, 1000), (100, 4096)])
def test_coarse_commit_or_first_sweep(op, v, n):
    if op == "or":
        state = jnp.asarray(RNG.integers(0, 2, v), jnp.int32)
        val = jnp.asarray(RNG.integers(0, 2, n), jnp.int32)
    else:  # first: negative state = empty slot, payloads non-negative
        state = jnp.asarray(np.where(RNG.random(v) < 0.5, -1,
                                     RNG.integers(0, 50, v)), jnp.int32)
        val = jnp.asarray(RNG.integers(0, 50, n), jnp.int32)
    idx = jnp.asarray(RNG.integers(-1, v, n), jnp.int32)
    out = ops.coarse_commit(state, idx, val, op=op, tile_m=128, block_v=256)
    exp = coarse_commit_ref(state, idx, val, op=op)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))


def test_coarse_commit_stats_output():
    """stats=True returns the in-transaction duplicate-target count."""
    from repro.kernels.coarse_commit import coarse_commit_pallas
    state = jnp.zeros((16,), jnp.int32)
    idx = jnp.asarray([1, 1, 2, 3, 3, 3, -1, -1], jnp.int32)
    val = jnp.ones((8,), jnp.int32)
    out, conf = coarse_commit_pallas(state, idx, val, op="add", tile_m=8,
                                     block_v=16, stats=True,
                                     interpret=True)
    assert int(conf) == 5  # 2 on vertex 1 + 3 on vertex 3
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(coarse_commit_ref(state, idx, val,
                                                      op="add")))
    # two transactions of 4: the duplicate pair on vertex 3 splits 2|1
    _, conf2 = coarse_commit_pallas(state, idx, val, op="add", tile_m=4,
                                    block_v=16, stats=True,
                                    interpret=True)
    assert int(conf2) == 4


@given(st.integers(1, 500), st.integers(2, 300), st.integers(32, 256),
       st.integers(64, 512))
@settings(**SET)
def test_coarse_commit_tile_shapes(n, v, tile_m, block_v):
    """Transaction size M / state block B must not change semantics."""
    state = jnp.asarray(RNG.integers(0, 100, v), jnp.int32)
    idx = jnp.asarray(RNG.integers(-1, v, n), jnp.int32)
    val = jnp.asarray(RNG.integers(0, 100, n), jnp.int32)
    out = ops.coarse_commit(state, idx, val, op="min", tile_m=tile_m,
                            block_v=block_v)
    exp = coarse_commit_ref(state, idx, val, op="min")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))


@pytest.mark.parametrize("nb", [3, 37, 128, 200])
@pytest.mark.parametrize("n", [17, 512, 2000])
def test_bucket_count(nb, n):
    owner = jnp.asarray(RNG.integers(-1, nb, n), jnp.int32)
    out = ops.bucket_count(owner, num_buckets=nb, tile_m=256)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(bucket_count_ref(owner, nb)))


@pytest.mark.parametrize("g,L,n,p", [(2, 32, 8, 16), (4, 64, 16, 64),
                                     (1, 128, 64, 32)])
def test_ssd_chunk(g, L, n, p):
    C = jnp.asarray(RNG.normal(size=(g, L, n)), jnp.float32)
    B = jnp.asarray(RNG.normal(size=(g, L, n)), jnp.float32)
    x = jnp.asarray(RNG.normal(size=(g, L, p)), jnp.float32)
    a = jnp.asarray(-np.abs(RNG.normal(size=(g, L))) * 0.1, jnp.float32)
    y = ops.ssd_chunk(C, B, x, a)
    ye = jax.vmap(ssd_chunk_ref)(C, B, x, a)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ye), atol=1e-4)


def test_ssd_chunk_bf16_inputs():
    g, L, n, p = 2, 32, 8, 16
    C = jnp.asarray(RNG.normal(size=(g, L, n)), jnp.bfloat16)
    B = jnp.asarray(RNG.normal(size=(g, L, n)), jnp.bfloat16)
    x = jnp.asarray(RNG.normal(size=(g, L, p)), jnp.bfloat16)
    a = jnp.asarray(-np.abs(RNG.normal(size=(g, L))) * 0.1, jnp.float32)
    y = ops.ssd_chunk(C, B, x, a)
    ye = jax.vmap(ssd_chunk_ref)(C.astype(jnp.float32),
                                 B.astype(jnp.float32),
                                 x.astype(jnp.float32), a)
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(ye),
                               atol=0.15, rtol=0.1)
