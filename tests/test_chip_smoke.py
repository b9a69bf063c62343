"""``chip_smoke.py``: its CPU rehearsal, its refusal to run without a TPU,
its NumPy references, and where the compile cache goes."""
import importlib.util
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.graphs.algorithms.bfs import bfs_reference
from repro.graphs.algorithms.pagerank import (pagerank_reference,
                                              personalized_pagerank)
from repro.graphs.generators import kronecker
from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parent.parent


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def smoke(monkeypatch, tmp_path):
    # with the variable set the cache helper leaves JAX's config alone, so
    # the in-process run turns no cache on for the rest of the session
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path / "jax_cache"))
    return _load_smoke()


def _lines(capsys):
    return [json.loads(l) for l in capsys.readouterr().out.splitlines()
            if l.startswith("{")]


def test_rehearsal_runs_every_phase_in_process(smoke, capsys):
    assert smoke.main(["--rehearse", "--scale", "8"]) == 0
    lines = _lines(capsys)
    phases = [l.get("phase") for l in lines]
    for p in ("kernels", "algorithms", "service", "engine"):
        assert p in phases
    last = lines[-1]
    assert last["rehearsal"] is True and "ok" not in last
    assert last["device"]["platform"] == jax.devices()[0].platform
    summary = {l["phase"]: l for l in lines
               if "phase" in l and "step" not in l}
    engine = summary["engine"]
    assert all(r["delivered_all"] and not r["degraded"] and r["match"]
               for r in engine["runs"])
    kernels = summary["kernels"]
    assert {c["tier"] for c in kernels["calls"]} == {
        "atomic", "coarse", "pallas", "fused"}
    assert all(c["match"] for c in kernels["calls"])


def test_refuses_to_run_without_a_tpu(smoke, capsys):
    if jax.devices()[0].platform == "tpu":
        pytest.skip("a TPU is attached")
    assert smoke.main([]) != 0
    assert "ok" not in capsys.readouterr().out


@pytest.mark.parametrize("seed", [0, 1])
def test_vectorized_references_match_the_oracles(smoke, seed):
    g = kronecker(9, 8, seed=seed)
    indptr = np.asarray(g.indptr).astype(np.int64)
    dst = np.asarray(g.dst)
    root = int(np.argmax(np.asarray(g.degrees)))
    np.testing.assert_array_equal(smoke.bfs_reference(indptr, dst, root),
                                  bfs_reference(g, root))
    ranks = smoke.pagerank_references(indptr, dst, [None, root])
    np.testing.assert_allclose(ranks[0], pagerank_reference(g), rtol=1e-12)
    want = np.asarray(personalized_pagerank(g, root, iters=smoke.PR_ITERS)[0])
    np.testing.assert_allclose(ranks[1], want, rtol=1e-4, atol=1e-7)


def test_compile_cache_uses_the_variable_when_set(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # nothing set


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable_compile_cache() == str(
            ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(
            ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
