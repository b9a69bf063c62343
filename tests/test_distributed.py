"""Distributed tests on 8 forced host devices (subprocess: the dry-run is
the ONLY place allowed to force 512; tests use their own interpreter so the
main test session keeps 1 device).

The parity matrix runs every algorithm ported to the shared
``run_distributed`` harness against its single-shard and numpy-reference
results, for both ``coarse`` and ``pallas`` commit specs, on a kronecker
and a uniform random graph — with a coalescing capacity small enough to
force sub-round requeue, and asserting the harness ``delivered_all``
anti-wedge flag every time."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

pytestmark = pytest.mark.slow   # spawns 8-device subprocesses

REPO = Path(__file__).resolve().parent.parent


def _tail(x, n):
    if x is None:
        return ""
    if isinstance(x, bytes):
        x = x.decode(errors="replace")
    return x[-n:]


def run_devices(code: str, n: int = 8, timeout: int = 900) -> dict:
    env = dict(os.environ)
    flags = f"--xla_force_host_platform_device_count={n}"
    extra = env.get("REPRO_XLA_EXTRA")      # tier2 pins a fixed flag matrix
    env["XLA_FLAGS"] = f"{flags} {extra}" if extra else flags
    env["PYTHONPATH"] = str(REPO / "src")
    try:
        out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                             capture_output=True, text=True, env=env,
                             timeout=timeout)
    except subprocess.TimeoutExpired as e:
        pytest.fail(f"child timed out after {timeout}s\n"
                    f"--- captured stderr tail ---\n{_tail(e.stderr, 4000)}\n"
                    f"--- captured stdout tail ---\n{_tail(e.stdout, 2000)}")
    assert out.returncode == 0, out.stderr[-4000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")]
    assert line, out.stdout[-2000:]
    return json.loads(line[-1][len("RESULT "):])


# ---------------------------------------------------------------------------
# Distributed × single-shard × reference parity matrix (all six algorithms)
# ---------------------------------------------------------------------------

ALGORITHMS = ("bfs", "sssp", "pagerank", "coloring", "boruvka", "stconn")

PARITY_CHILD = """
import json, numpy as np, jax.numpy as jnp
from repro.launch.mesh import make_host_mesh
from repro.core.commit import CommitSpec
from repro.graphs.generators import kronecker, erdos_renyi, random_weights
from repro.graphs.algorithms import bfs as B, sssp as S, pagerank as PR
from repro.graphs.algorithms import coloring as CO, boruvka as BO
from repro.graphs.algorithms import stconn as ST

ALG = "{alg}"
mesh = make_host_mesh(8, 1)
out = {{}}
graphs = [("kron", kronecker(8, 8, seed=3)),
          ("uniform", erdos_renyi(300, 6.0, seed=11))]
for gname, g in graphs:
    gw = random_weights(g, seed=4)
    src = int(np.argmax(np.asarray(g.degrees)))
    t = int(np.argmin(np.asarray(g.degrees)))
    for backend in ("coarse", "pallas", "auto"):
        # capacity 64 < the hub in-degrees: forces coalescing requeue;
        # m=48 forces multi-transaction commits on the static backends,
        # "auto" calibrates + adapts M from the conflict feedback
        m = None if backend == "auto" else 48
        kw = dict(capacity=64, spec=CommitSpec(backend=backend, m=m),
                  telemetry=True)
        if ALG == "bfs":
            ref = B.bfs_reference(g, src)
            one = B.bfs(g, src)
            dist, _, res = B.distributed_bfs(mesh, g, src, **kw)
            ok = (np.array_equal(np.asarray(dist, np.int64), ref)
                  and np.array_equal(np.asarray(dist), np.asarray(one.dist)))
        elif ALG == "sssp":
            ref = S.sssp_reference(gw, src)
            one, _ = S.sssp(gw, src)
            dist, _, res = S.distributed_sssp(mesh, gw, src, **kw)
            d = np.asarray(dist, np.float64)
            reach = np.isfinite(ref)
            ok = (np.array_equal(np.asarray(dist), np.asarray(one))
                  and bool(np.allclose(d[reach], ref[reach], rtol=1e-5))
                  and bool((d[~reach] > 1e37).all()))
        elif ALG == "pagerank":
            ref = PR.pagerank_reference(g, iters=8)
            one, _ = PR.pagerank(g, iters=8)
            rank, res = PR.distributed_pagerank(mesh, g, iters=8, **kw)
            r = np.asarray(rank, np.float64)
            ok = (float(np.abs(r - ref).max()) < 1e-5
                  and float(np.abs(r - np.asarray(one, np.float64)).max())
                  < 1e-5)
        elif ALG == "coloring":
            one_c, one_r, _ = CO.coloring(g, seed=0)
            c, r, nc, res = CO.distributed_coloring(mesh, g, seed=0, **kw)
            ok = (np.array_equal(np.asarray(c), np.asarray(one_c))
                  and CO.validate_coloring(g, c) and not bool(nc)
                  and int(r) == int(one_r))
        elif ALG == "boruvka":
            one_comp, one_w, one_ne, _ = BO.boruvka(gw)
            ref_w = BO.mst_reference(gw)
            comp, w, ne, ro, res = BO.distributed_boruvka(mesh, gw, **kw)
            ok = (np.array_equal(np.asarray(comp), np.asarray(one_comp))
                  and abs(float(w) - ref_w) < 1e-3 * max(ref_w, 1.0)
                  and int(ne) == int(one_ne))
        else:
            ref = ST.st_reference(g, src, t)
            one_f, _ = ST.st_connectivity(g, src, t)
            f, r, res = ST.distributed_stconn(mesh, g, src, t, **kw)
            ok = bool(f) == bool(ref) == bool(one_f)
        out[gname + "/" + backend] = dict(
            ok=bool(ok), delivered_all=bool(res.delivered_all),
            subrounds=int(res.subrounds), rounds=int(res.rounds),
            conflicts=int(res.conflicts))
print("RESULT", json.dumps(out))
"""


@pytest.mark.parametrize("alg", ALGORITHMS)
def test_distributed_parity_matrix(alg):
    r = run_devices(PARITY_CHILD.format(alg=alg), timeout=1500)
    assert len(r) == 6, r          # 2 graphs x 3 backends (incl. auto)
    for case, row in r.items():
        assert row["ok"], (alg, case, row)
        # the anti-wedge flag: capacity C < max in-degree must terminate
        # by requeueing, never by silently dropping pending messages
        assert row["delivered_all"], (alg, case, row)
        assert row["subrounds"] >= row["rounds"], (alg, case, row)


# ---------------------------------------------------------------------------
# Lane-batched (multi-source) parity: 8-device fused waves == single-shard
# fused loops == L looped single-query runs (ISSUE 4)
# ---------------------------------------------------------------------------

MULTI_CHILD = """
import json, numpy as np, jax.numpy as jnp
from repro.launch.mesh import make_host_mesh
from repro.core.commit import CommitSpec
from repro.graphs.generators import kronecker, random_weights
from repro.graphs.algorithms import bfs as B, sssp as S
from repro.graphs.algorithms import pagerank as PR, stconn as ST

mesh = make_host_mesh(8, 1)
g = kronecker(8, 8, seed=3)
gw = random_weights(g, seed=4)
deg = np.asarray(g.degrees)
srcs = jnp.asarray([int(np.argmax(deg)), 0, 5, int(np.argmin(deg))],
                   jnp.int32)
ts = jnp.asarray([3, 0, int(np.argmin(deg)), 17], jnp.int32)
out = {}
for backend in ("coarse", "pallas", "auto"):
    # capacity 64 < hub in-degree: lane-tagged messages must survive the
    # sub-round requeue; m=48 forces multi-transaction composite commits
    m = None if backend == "auto" else 48
    kw = dict(capacity=64, spec=CommitSpec(backend=backend, m=m),
              max_subrounds=256, telemetry=True)

    one = B.multi_source_bfs(g, srcs)
    dist, _, res = B.distributed_multi_source_bfs(mesh, g, srcs, **kw)
    looped = all(
        np.array_equal(np.asarray(dist[l]),
                       np.asarray(B.bfs(g, int(srcs[l])).dist))
        for l in range(len(srcs)))
    out["bfs/" + backend] = dict(
        ok=bool(np.array_equal(np.asarray(dist), np.asarray(one.dist))
                and looped),
        dall=bool(res.delivered_all), subrounds=int(res.subrounds),
        rounds=int(res.rounds))

    md, _ = S.multi_source_sssp(gw, srcs)
    dd, _, res = S.distributed_multi_source_sssp(mesh, gw, srcs, **kw)
    out["sssp/" + backend] = dict(
        ok=bool(np.array_equal(np.asarray(dd), np.asarray(md))),
        dall=bool(res.delivered_all), subrounds=int(res.subrounds),
        rounds=int(res.rounds))

    mr, _ = PR.multi_source_pagerank(g, srcs, iters=6)
    dr, res = PR.distributed_multi_source_pagerank(mesh, g, srcs, iters=6,
                                                   **kw)
    out["pagerank/" + backend] = dict(
        ok=bool(np.abs(np.asarray(dr) - np.asarray(mr)).max() < 1e-6),
        dall=bool(res.delivered_all), subrounds=int(res.subrounds),
        rounds=int(res.rounds))

    mf, _ = ST.multi_source_stconn(g, srcs, ts)
    df, _, res = ST.distributed_multi_source_stconn(mesh, g, srcs, ts,
                                                    **kw)
    refs = [ST.st_reference(g, int(srcs[l]), int(ts[l]))
            for l in range(len(srcs))]
    out["stconn/" + backend] = dict(
        ok=bool(np.array_equal(np.asarray(df), np.asarray(mf))
                and all(bool(df[l]) == refs[l] for l in range(len(srcs)))),
        dall=bool(res.delivered_all), subrounds=int(res.subrounds),
        rounds=int(res.rounds))
print("RESULT", json.dumps(out))
"""


def test_distributed_multi_source_parity_matrix():
    r = run_devices(MULTI_CHILD, timeout=1500)
    assert len(r) == 12, r          # 4 algorithms x 3 backends
    for case, row in r.items():
        assert row["ok"], (case, row)
        assert row["dall"], (case, row)
        assert row["subrounds"] >= row["rounds"], (case, row)


# ---------------------------------------------------------------------------
# GraphBatch axis on 8 devices: batched_over_graphs_* through the union
# run_distributed path vs the looped single-graph references (ISSUE 5)
# ---------------------------------------------------------------------------

GRAPHS_CHILD = """
import json, numpy as np, jax.numpy as jnp
from repro.launch.mesh import make_host_mesh
from repro.core.commit import CommitSpec
from repro.graphs.csr import GraphSet
from repro.graphs.generators import kronecker, erdos_renyi, grid2d, \\
    random_weights
from repro.graphs.algorithms import bfs as B, sssp as S, pagerank as PR
from repro.graphs.algorithms import coloring as CO, boruvka as BO
from repro.graphs.algorithms import stconn as ST

mesh = make_host_mesh(8, 1)
graphs = [kronecker(6, 6, seed=1), erdos_renyi(90, 4.0, seed=2), grid2d(8),
          kronecker(5, 4, seed=7)]
wgraphs = [random_weights(g, seed=i) for i, g in enumerate(graphs)]
gs, gws = GraphSet(graphs), GraphSet(wgraphs)
srcs = [0, 3, 5, 1]
ts = [7, 7, 0, 0]
out = {}
for backend in ("coarse", "auto"):
    spec = CommitSpec(backend=backend, stats=False)
    # capacity 64 forces sub-round requeue of the flat union-keyed waves
    kw = dict(mesh=mesh, capacity=64, max_subrounds=256, spec=spec)

    rows = B.batched_over_graphs_bfs(gs, srcs, **kw)
    out["bfs/" + backend] = all(
        np.array_equal(np.asarray(rows[i]),
                       np.asarray(B.bfs(g, s, spec=spec).dist))
        for i, (g, s) in enumerate(zip(graphs, srcs)))

    rows = S.batched_over_graphs_sssp(gws, srcs, **kw)
    out["sssp/" + backend] = all(
        np.array_equal(np.asarray(rows[i]),
                       np.asarray(S.sssp(g, s, spec=spec)[0]))
        for i, (g, s) in enumerate(zip(wgraphs, srcs)))

    rows = PR.batched_over_graphs_pagerank(gs, srcs, iters=5, **kw)
    out["pagerank/" + backend] = all(
        np.allclose(np.asarray(rows[i]),
                    np.asarray(PR.personalized_pagerank(
                        g, s, iters=5, spec=spec)[0]), atol=1e-6)
        for i, (g, s) in enumerate(zip(graphs, srcs)))

    found = ST.batched_over_graphs_stconn(gs, srcs, ts, **kw)
    out["stconn/" + backend] = all(
        bool(found[i]) == ST.st_reference(g, s, t)
        for i, (g, s, t) in enumerate(zip(graphs, srcs, ts)))

    colors, _, not_conv = CO.batched_over_graphs_coloring(gs, seed=0, **kw)
    out["coloring/" + backend] = all(
        np.array_equal(np.asarray(colors[i]),
                       np.asarray(CO.coloring(g, seed=0)[0]))
        and CO.validate_coloring(g, colors[i])
        for i, g in enumerate(graphs)) and not bool(np.any(
            np.asarray(not_conv)))

    mst, _ = BO.batched_over_graphs_boruvka(gws, **kw)
    ok = True
    for i, g in enumerate(wgraphs):
        comp1, w1, ne1, _ = BO.boruvka(g)
        comp, w, ne = mst[i]
        ok = ok and bool(np.array_equal(np.asarray(comp),
                                        np.asarray(comp1))
                         and float(w) == float(w1) and int(ne) == int(ne1))
    out["boruvka/" + backend] = ok
print("RESULT", json.dumps(out))
"""


def test_distributed_batched_over_graphs_parity_matrix():
    """All six algorithms, graph batch of 4 heterogeneous tenants, on 8
    forced devices — each batched element must equal its looped
    single-graph run (ppr to float-add rounding)."""
    r = run_devices(GRAPHS_CHILD, timeout=1500)
    assert len(r) == 12, r          # 6 algorithms x {coarse, auto}
    for case, ok in r.items():
        assert ok, case


# ---------------------------------------------------------------------------
# Degraded-mesh mode: survive a host drop mid-query on 8 devices (ISSUE 6)
# ---------------------------------------------------------------------------

DEGRADED_CHILD = """
import json, numpy as np, jax.numpy as jnp
from repro.launch.mesh import make_host_mesh
from repro.core.commit import CommitSpec
from repro.graphs.generators import kronecker
from repro.graphs.algorithms import bfs as B

mesh = make_host_mesh(8, 1)
g = kronecker(8, 8, seed=3)
src = int(np.argmax(np.asarray(g.degrees)))
ref = B.bfs_reference(g, src)
out = {}

# a) vertex-state replay: BFS state is [vpad], so the 8->7 shrink re-homes
#    the round snapshot and resumes mid-query
fired = {"n": 0}
def injector(chunk, rounds_done):
    if chunk == 1 and fired["n"] == 0:
        fired["n"] = 1
        raise RuntimeError("host 7 lost")
dist, _, res = B.distributed_bfs(
    mesh, g, src, capacity=64, max_subrounds=256,
    spec=CommitSpec(backend="coarse", m=48), telemetry=True,
    snapshot_rounds=2, fault_injector=injector)
out["single"] = dict(
    ok=bool(np.array_equal(np.asarray(dist, np.int64), ref)),
    degraded=bool(res.degraded), delivered_all=bool(res.delivered_all),
    fired=fired["n"])

# b) lane-batched: vertex-major [vpad*L] state can't be re-homed, so the
#    shrink restarts the fused query from round 0 on the 7 survivors —
#    answers still exact
srcs = jnp.asarray([src, 0, 5, 17], jnp.int32)
fired2 = {"n": 0}
def injector2(chunk, rounds_done):
    if chunk == 1 and fired2["n"] == 0:
        fired2["n"] = 1
        raise RuntimeError("host 7 lost")
md, _, mres = B.distributed_multi_source_bfs(
    mesh, g, srcs, capacity=64, max_subrounds=256,
    spec=CommitSpec(backend="coarse", m=48), telemetry=True,
    snapshot_rounds=2, fault_injector=injector2)
looped = all(
    np.array_equal(np.asarray(md[l]),
                   np.asarray(B.bfs(g, int(srcs[l])).dist))
    for l in range(len(srcs)))
out["lanes"] = dict(ok=bool(looped), degraded=bool(mres.degraded),
                    delivered_all=bool(mres.delivered_all),
                    fired=fired2["n"])

# c) fault-free control on the same args: degraded must stay False
dist0, _, res0 = B.distributed_bfs(
    mesh, g, src, capacity=64, max_subrounds=256,
    spec=CommitSpec(backend="coarse", m=48), telemetry=True,
    snapshot_rounds=2)
out["control"] = dict(
    ok=bool(np.array_equal(np.asarray(dist0, np.int64), ref)),
    degraded=bool(res0.degraded))
print("RESULT", json.dumps(out))
"""


def test_degraded_mesh_parity_8_devices():
    """A host drop mid-query on 8 devices: the run shrinks to 7, replays
    the round snapshot (vertex state) or restarts from round 0 (lane
    state), and the answers still match the reference exactly."""
    r = run_devices(DEGRADED_CHILD, timeout=1500)
    for case in ("single", "lanes"):
        assert r[case]["fired"] == 1, (case, r[case])
        assert r[case]["degraded"], (case, r[case])
        assert r[case]["delivered_all"], (case, r[case])
        assert r[case]["ok"], (case, r[case])
    assert r["control"]["ok"] and not r["control"]["degraded"], r["control"]


# ---------------------------------------------------------------------------
# Conflict-telemetry invariant (Tables 3c/3f analogue across the refactor)
# ---------------------------------------------------------------------------


def test_distributed_conflicts_match_single_shard_counts():
    """With capacity >= the whole batch and one transaction per owner, the
    distributed per-owner conflict totals must equal the single-shard
    ``coarse_commit(stats=True)`` count on the same message multiset."""
    r = run_devices("""
        import json, numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as Ps
        from repro.launch.mesh import make_host_mesh
        from repro.core import commit as C
        from repro.core.engine import EngineConfig, wave_until_delivered
        from repro.core.messages import make_messages
        mesh = make_host_mesh(4, 1)
        P, block, n = 4, 32, 512
        V = P * block
        rng = np.random.default_rng(0)
        INIT = {"min": 2**20, "max": -2**20, "add": 0, "or": 0, "first": -1}
        out = {}
        for op in ("min", "max", "add", "or", "first"):
            tgt = rng.integers(0, V, n).astype(np.int32)
            if op == "or":
                pay = rng.integers(0, 2, n).astype(np.int32)
            elif op == "first":
                pay = rng.integers(0, 100, n).astype(np.int32)
            else:
                pay = rng.integers(-50, 50, n).astype(np.int32)
            state0 = np.full(V, INIT[op], np.int32)
            ref = C.coarse_commit(jnp.asarray(state0),
                                  make_messages(tgt, pay), op, stats=True)
            ecfg = EngineConfig(P, block, capacity=n, op=op)
            tgt_s = jnp.asarray(tgt.reshape(P, n // P))
            pay_s = jnp.asarray(pay.reshape(P, n // P))

            def shard_fn(st, tg, pl):
                st2, _, cf, _, dall = wave_until_delivered(
                    ecfg, st, tg[0], pl[0],
                    jnp.ones((n // P,), bool))
                return st2, cf, dall

            fn = jax.shard_map(
                shard_fn, mesh=mesh,
                in_specs=(Ps("data"), Ps("data"), Ps("data")),
                out_specs=(Ps("data"), Ps(), Ps()), check_vma=False)
            st2, cf, dall = jax.jit(fn)(jnp.asarray(state0), tgt_s, pay_s)
            # state parity holds for order-independent ops ('first' tie-
            # breaks by arrival order, which routing permutes)
            state_ok = (op == "first"
                        or np.array_equal(np.asarray(st2),
                                          np.asarray(ref.state)))
            out[op] = {"single": int(ref.conflicts), "dist": int(cf),
                       "state_ok": bool(state_ok), "dall": bool(dall)}
        # a multi-payload wave carries several fields per routed message —
        # conflicts must be counted once per message, not once per field
        tgt = rng.integers(0, V, n).astype(np.int32)
        pay = rng.integers(0, 2, n).astype(np.int32)
        ref = C.coarse_commit(jnp.zeros((V,), jnp.int32),
                              make_messages(tgt, pay), "or", stats=True)
        ecfg = EngineConfig(P, block, capacity=n, op="or")
        tgt_s = jnp.asarray(tgt.reshape(P, n // P))
        pay_s = jnp.asarray(pay.reshape(P, n // P))

        def shard2(st, tg, pl):
            st2, _, cf, _, _ = wave_until_delivered(
                ecfg, {"a": st, "b": st}, tg[0],
                {"a": pl[0], "b": pl[0]}, jnp.ones((n // P,), bool))
            return st2["a"], cf

        fn2 = jax.shard_map(
            shard2, mesh=mesh,
            in_specs=(Ps("data"), Ps("data"), Ps("data")),
            out_specs=(Ps("data"), Ps()), check_vma=False)
        st2, cf2 = jax.jit(fn2)(jnp.zeros((V,), jnp.int32), tgt_s, pay_s)
        out["or_2field"] = {
            "single": int(ref.conflicts), "dist": int(cf2),
            "state_ok": bool(np.array_equal(np.asarray(st2),
                                            np.asarray(ref.state))),
            "dall": True}
        print("RESULT", json.dumps(out))
    """, n=4)
    for op, row in r.items():
        assert row["dist"] == row["single"], (op, row)
        assert row["state_ok"] and row["dall"], (op, row)


# ---------------------------------------------------------------------------
# delivered_all anti-wedge flag (the silent-wedge bugfix)
# ---------------------------------------------------------------------------


def test_wave_surfaces_wedge_instead_of_silent_drop():
    """max_subrounds exhausted with messages pending => delivered_all is
    False (previously the wave returned quietly); with enough sub-rounds a
    capacity far below the per-owner in-degree still terminates and
    delivers everything."""
    r = run_devices("""
        import json, numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as Ps
        from repro.launch.mesh import make_host_mesh
        from repro.core.engine import EngineConfig, wave_until_delivered
        mesh = make_host_mesh(2, 1)
        P, block, n = 2, 16, 64
        V = P * block
        # every shard sends all 64 messages to vertex 0: per-owner load 128
        tgt = jnp.zeros((n,), jnp.int32)
        pay = jnp.arange(n, dtype=jnp.int32)
        out = {}
        for name, cap, msr in (("wedged", 4, 3), ("requeued", 4, 64)):
            ecfg = EngineConfig(P, block, capacity=cap, op="min")

            def shard_fn(st):
                st2, _, _, sr, dall = wave_until_delivered(
                    ecfg, st, tgt, pay, jnp.ones((n,), bool),
                    max_subrounds=msr)
                return st2, sr, dall

            fn = jax.shard_map(shard_fn, mesh=mesh,
                               in_specs=(Ps("data"),),
                               out_specs=(Ps("data"), Ps(), Ps()),
                               check_vma=False)
            st2, sr, dall = jax.jit(fn)(
                jnp.full((V,), 2**20, jnp.int32))
            out[name] = {"delivered_all": bool(dall), "subrounds": int(sr),
                         "min0": int(np.asarray(st2)[0])}
        print("RESULT", json.dumps(out))
    """, n=2)
    assert not r["wedged"]["delivered_all"], r
    assert r["requeued"]["delivered_all"], r
    assert r["requeued"]["min0"] == 0, r       # full multiset committed
    assert r["requeued"]["subrounds"] == 16, r  # 64 msgs / C=4 per shard


def test_ownership_protocol_converges_under_conflict():
    r = run_devices("""
        import json, numpy as np, jax.numpy as jnp
        from repro.launch.mesh import make_host_mesh
        from repro.core.ownership import run_transactions
        mesh = make_host_mesh(8, 1)
        rng = np.random.default_rng(7)
        P, X, K, V = 8, 32, 6, 512       # small V = heavy conflicts
        txns = rng.integers(0, V, (P, X, K)).astype(np.int32)
        visited, st = run_transactions(mesh, jnp.asarray(txns), V,
                                       capacity=512)
        exp = np.zeros(V, bool); exp[txns.reshape(-1)] = True
        print("RESULT", json.dumps({
            "ok": bool(np.array_equal(np.asarray(visited), exp)),
            "rounds": int(st.rounds), "retries": int(st.retries)}))
    """)
    assert r["ok"]
    assert r["retries"] > 0          # conflicts actually happened


def test_grad_compression_tracks_uncompressed_loss():
    r = run_devices("""
        import json, jax, jax.numpy as jnp, numpy as np
        from repro.configs.archs import ARCHS
        from repro.configs.base import RunConfig, ShapeConfig, smoke_model
        from repro.data.pipeline import TokenStream
        from repro.models import model as M
        from repro.train.optimizer import make_optimizer
        from repro.train.grad_compression import (init_error_feedback,
                                                  make_compressed_dp_step)
        from repro.train.train_step import make_train_step
        mesh = jax.make_mesh((2,), ("pod",))
        cfg = smoke_model(ARCHS["qwen2-1.5b"])
        shape = ShapeConfig("t", 64, 8, "train")
        rcfg = RunConfig(model=cfg, shape=shape, remat="none",
                         learning_rate=1e-3)
        params, _ = M.init(cfg, jax.random.PRNGKey(0))
        opt = make_optimizer(rcfg)
        stream = TokenStream(cfg, shape, seed=0)
        bat = lambda i: jax.tree.map(jnp.asarray, stream.batch(i))

        step0 = jax.jit(make_train_step(cfg, rcfg, opt))
        p0, o0 = params, opt.init(params)
        for i in range(25):
            p0, o0, m0 = step0(p0, o0, jnp.int32(i), bat(i))

        loss_fn = lambda p, b: M.loss_fn(cfg, rcfg, p, b)
        stepc = make_compressed_dp_step(loss_fn, opt, mesh, axis="pod")
        pc, oc = params, opt.init(params)
        ef = init_error_feedback(params)
        for i in range(25):
            b = jax.tree.map(
                lambda x: x.reshape((2, 4) + x.shape[1:]), bat(i))
            pc, oc, ef, lc = stepc(pc, oc, ef, jnp.int32(i), b)
        print("RESULT", json.dumps({
            "loss_base": float(m0["loss"]), "loss_comp": float(lc)}))
    """)
    # compressed loss within 10% of uncompressed after 25 steps
    assert abs(r["loss_comp"] - r["loss_base"]) / r["loss_base"] < 0.10, r


def test_pipeline_parallel_matches_plain_forward():
    r = run_devices("""
        import json, jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro.configs.archs import ARCHS
        from repro.configs.base import RunConfig, ShapeConfig, smoke_model
        from repro.models import model as M
        from repro.train.pipeline import pipeline_forward
        mesh = jax.make_mesh((2,), ("pod",))
        cfg = smoke_model(ARCHS["qwen2-1.5b"])
        cfg = dataclasses.replace(cfg, num_layers=4)   # 4 blocks / 2 stages
        shape = ShapeConfig("t", 32, 4, "train")
        rcfg = RunConfig(model=cfg, shape=shape, remat="none")
        params, _ = M.init(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                  cfg.vocab_size)
        ref, _, _ = M._forward(cfg, rcfg, params, {"tokens": toks},
                               mode="train")
        pp = pipeline_forward(cfg, rcfg, mesh, "pod", num_microbatches=2)
        with mesh:
            out = pp(params, toks)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32) -
                                    ref.astype(jnp.float32))))
        print("RESULT", json.dumps({"err": err}))
    """)
    assert r["err"] < 1e-2, r


def test_sharded_train_step_runs_on_2d_mesh():
    r = run_devices("""
        import json, jax, jax.numpy as jnp
        from repro.configs.archs import ARCHS
        from repro.configs.base import RunConfig, ShapeConfig, smoke_model
        from repro.data.pipeline import TokenStream
        from repro.launch.mesh import make_host_mesh
        from repro.models import model as M
        from repro.runtime import sharding as shd
        from repro.train.optimizer import make_optimizer
        from repro.train.train_step import make_train_step
        RULES = shd.ShardingRules(shd.TRAIN_RULES)
        mesh = make_host_mesh(2, 4)
        cfg = smoke_model(ARCHS["phi3.5-moe-42b-a6.6b"])
        # same shape/lr as the test_system learning tests: a (32, 4) batch
        # at the default lr carries too little signal per step to assert a
        # loss decrease deterministically
        shape = ShapeConfig("t", 64, 8, "train")
        rcfg = RunConfig(model=cfg, shape=shape, remat="full",
                         microbatches=2, learning_rate=3e-3)
        with mesh:
            params, _ = M.init(cfg, jax.random.PRNGKey(0))
            opt = make_optimizer(rcfg)
            opt_state = opt.init(params)
            psh = shd.tree_shardings(RULES, params, mesh)
            osh = shd.tree_shardings(RULES, opt_state, mesh)
            params = jax.device_put(params, psh)
            opt_state = jax.device_put(opt_state, osh)
            step = jax.jit(make_train_step(cfg, rcfg, opt),
                           donate_argnums=(0, 1))
            stream = TokenStream(cfg, shape, seed=0)
            losses = []
            for i in range(16):
                batch = jax.tree.map(jnp.asarray, stream.batch(i))
                params, opt_state, metrics = step(params, opt_state,
                                                  jnp.int32(i), batch)
                losses.append(float(metrics["loss"]))
        print("RESULT", json.dumps({"first": sum(losses[:4]) / 4,
                                    "last": sum(losses[-4:]) / 4}))
    """)
    assert r["last"] < r["first"]
