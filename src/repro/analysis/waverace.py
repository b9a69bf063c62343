"""Jaxpr-level wave-race detection.

An HTM transaction aborts when another core touches its read/write set;
our software rounds have no such tripwire — a round that scatters into
a state array OUTSIDE ``commit()``'s conflict resolution while also
reading it produces silently order-dependent results (the classic
in-wave read race the paper's Table 2 "conflicting access" aborts would
have caught in hardware).

The detector traces each algorithm's round step to a jaxpr and walks
it:

* the *state chain* starts at the round's state-leaf inputs and grows
  through aliasing primitives (reshape/convert/select/...) and through
  scatter outputs (a functional scatter's result aliases its operand);
* every ``commit()`` executes under ``jax.named_scope("aam_commit")``,
  which JAX records in each equation's ``source_info.name_stack`` —
  including inside ``while``/``scan`` sub-jaxprs;
* a scatter whose operand is on the chain **without** ``aam_commit`` on
  its name stack is a finding: a raw state write that bypasses conflict
  resolution.  Gathers of chained arrays outside the scope are recorded
  as the read half of the race (evidence, not findings — reading state
  is what rounds are for).

Round steps come from two seams:

* :func:`capture_algorithms` calls every public ``distributed_*`` /
  ``batched_over_graphs_*`` wrapper on a tiny graph with
  ``repro.core.engine._LINT_CAPTURE`` set; :class:`~repro.core.engine.
  LintCapture` carries out the normalized ``(alg, graph, batch)`` so the
  wrapper's own state/payload plumbing is what gets analyzed;
* :func:`repro.serve.product_wave.lint_traceables` exposes the three
  ``ProductWave`` chunk bodies as state-only callables.

The round is traced against :class:`LintRuntime`, a single-shard
``WaveRuntime`` stand-in whose ``wave`` is a plain ``commit()`` on the
same composite keys (so the scoped write path looks exactly like
production) and whose collectives are identities.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
from types import SimpleNamespace

import numpy as np

import jax
from jax.extend.core import Literal
import jax.numpy as jnp

from repro.core import engine as E
from repro.core.commit import CommitSpec, commit
from repro.core.coalescing import fuse_keys
from repro.core.messages import make_messages

# output var aliases input: chain propagates through
ALIAS_PRIMS = {
    "reshape", "convert_element_type", "transpose", "squeeze",
    "broadcast_in_dim", "select_n", "copy", "rev", "slice",
    "concatenate", "expand_dims", "add", "sub", "mul", "max", "min",
    "and", "or", "where", "pad",
}
# functional state writes
SCATTER_PRIMS = {"scatter", "scatter-add", "scatter-min", "scatter-max",
                 "scatter-mul"}
# kernel-launch state writes: a Pallas kernel whose operands include
# round state commits directly from VMEM (the fused route+commit pass of
# repro.kernels.fused_wave, the coarse-commit kernel) — same rule as the
# scatters: in-scope = the protected commit site, out-of-scope = a raw
# state write that bypasses conflict resolution.  Handled BEFORE the
# generic call-descent: a pallas_call's params carry the KERNEL jaxpr
# (refs + get/swap primitives, a different var universe), which must not
# be walked as if it were a pjit body.
KERNEL_PRIMS = {"pallas_call"}
# state reads
GATHER_PRIMS = {"gather", "dynamic_slice"}

_SCOPE = "aam_commit"


@dataclasses.dataclass
class RaceFinding:
    where: str          # algorithm / traceable name
    primitive: str
    scoped: bool
    detail: str


@dataclasses.dataclass
class RaceReport:
    name: str
    findings: list = dataclasses.field(default_factory=list)
    reads: int = 0          # unscoped gathers of chained state (evidence)
    commits: int = 0        # scoped writes (the healthy path)

    @property
    def ok(self) -> bool:
        return not self.findings


def _in_scope(eqn) -> bool:
    return _SCOPE in str(eqn.source_info.name_stack)


def _vars(atoms):
    return [a for a in atoms if not isinstance(a, Literal)]


def _walk(jaxpr, chain: set, rep: RaceReport, where: str,
          scoped: bool = False) -> set:
    """Walk one (open) jaxpr; ``chain`` holds this jaxpr's vars known to
    alias round state.  Returns the chain (mutated in place too).

    ``scoped=True`` means an ENCLOSING call eqn already carried the
    ``aam_commit`` scope: sub-jaxpr name stacks are relative to their
    call eqn (a jitted kernel wrapper records the scope on the pjit eqn,
    not inside it), so scope inherits down the descent."""
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        invars = _vars(eqn.invars)
        on_chain = [v for v in invars if v in chain]
        eqn_scoped = scoped or _in_scope(eqn)

        if prim in ("while",):
            _walk_while(eqn, chain, rep, where, eqn_scoped)
            continue
        if prim == "scan":
            _walk_scan(eqn, chain, rep, where, eqn_scoped)
            continue
        if prim == "cond":
            _walk_cond(eqn, chain, rep, where, eqn_scoped)
            continue
        if prim in KERNEL_PRIMS:
            if on_chain:
                if eqn_scoped:
                    rep.commits += 1
                else:
                    rep.findings.append(RaceFinding(
                        where=where, primitive=prim, scoped=False,
                        detail=f"kernel launch ({prim}) writes round "
                               f"state outside commit()'s conflict "
                               f"resolution — a fused-kernel commit "
                               f"site must run under "
                               f"jax.named_scope({_SCOPE!r}) (reads of "
                               f"the same array this round: "
                               f"{rep.reads})"))
                chain.update(_vars(eqn.outvars))
            continue
        inner = _call_jaxpr(eqn)
        if inner is not None:
            _walk_call(eqn, inner, chain, rep, where, eqn_scoped)
            continue

        if prim in SCATTER_PRIMS:
            operand = eqn.invars[0]
            if not isinstance(operand, Literal) \
                    and operand in chain:
                if eqn_scoped:
                    rep.commits += 1
                else:
                    rep.findings.append(RaceFinding(
                        where=where, primitive=prim, scoped=False,
                        detail=f"raw {prim} into round state outside "
                               f"commit()'s conflict resolution — an "
                               f"in-wave write race (reads of the same "
                               f"array this round: {rep.reads})"))
                chain.update(_vars(eqn.outvars))
            continue
        if prim in GATHER_PRIMS:
            if on_chain and not eqn_scoped:
                rep.reads += 1
            continue
        if on_chain and prim in ALIAS_PRIMS:
            chain.update(_vars(eqn.outvars))
    return chain


def _call_jaxpr(eqn):
    """ClosedJaxpr of a call-like primitive (pjit/closed_call/remat...)."""
    for key in ("jaxpr", "call_jaxpr"):
        ij = eqn.params.get(key)
        if ij is not None:
            return ij
    return None


def _map_in(inner_jaxpr, outer_invars, chain):
    return {iv for iv, ov in zip(inner_jaxpr.invars, outer_invars)
            if not isinstance(ov, Literal) and ov in chain}


def _map_out(inner_jaxpr, inner_chain, eqn, chain):
    for ov, res in zip(eqn.outvars, inner_jaxpr.outvars):
        if not isinstance(res, Literal) and res in inner_chain:
            chain.add(ov)


def _walk_call(eqn, closed, chain, rep, where, scoped=False):
    ij = closed.jaxpr if hasattr(closed, "jaxpr") else closed
    inner = _map_in(ij, eqn.invars, chain)
    _walk(ij, inner, rep, where, scoped)
    _map_out(ij, inner, eqn, chain)


def _walk_while(eqn, chain, rep, where, scoped=False):
    cn = eqn.params["cond_nconsts"]
    bn = eqn.params["body_nconsts"]
    body = eqn.params["body_jaxpr"].jaxpr
    cond = eqn.params["cond_jaxpr"].jaxpr
    body_outer = eqn.invars[cn:]                  # body consts + carry
    inner = _map_in(body, body_outer, chain)
    # carry fixpoint: a chained carry slot may only become chained after
    # one body pass — two passes reach the fixpoint for alias chains
    for _ in range(2):
        snapshot = set(inner)
        _walk(body, inner, rep, where, scoped)
        # feed body outputs (carry') back into carry invars
        carry_in = body.invars[bn:]
        for civ, res in zip(carry_in, body.outvars):
            if not isinstance(res, Literal) and res in inner:
                inner.add(civ)
        if inner == snapshot:
            break
    cond_inner = _map_in(cond, eqn.invars[:cn] + body_outer[bn:], chain)
    _walk(cond, cond_inner, rep, where, scoped)
    # while outvars = final carry
    carry_results = body.outvars
    for ov, res in zip(eqn.outvars, carry_results):
        if not isinstance(res, Literal) and res in inner:
            chain.add(ov)


def _walk_scan(eqn, chain, rep, where, scoped=False):
    nc = eqn.params["num_consts"]
    ncar = eqn.params["num_carry"]
    body = eqn.params["jaxpr"].jaxpr
    inner = _map_in(body, eqn.invars, chain)
    for _ in range(2):
        snapshot = set(inner)
        _walk(body, inner, rep, where, scoped)
        carry_in = body.invars[nc:nc + ncar]
        for civ, res in zip(carry_in, body.outvars[:ncar]):
            if not isinstance(res, Literal) and res in inner:
                inner.add(civ)
        if inner == snapshot:
            break
    for ov, res in zip(eqn.outvars, body.outvars):
        if not isinstance(res, Literal) and res in inner:
            chain.add(ov)


def _walk_cond(eqn, chain, rep, where, scoped=False):
    operands = eqn.invars[1:]
    for closed in eqn.params["branches"]:
        ij = closed.jaxpr
        inner = _map_in(ij, operands, chain)
        _walk(ij, inner, rep, where, scoped)
        _map_out(ij, inner, eqn, chain)


@contextlib.contextmanager
def _no_env_sanitize():
    """Trace the SHIPPED program: the REPRO_SANITIZE shadow replay would
    otherwise inject its own commit dispatch into the jaxpr and skew
    commit counts (spec-level ``sanitize=True`` is still honored — that
    is part of the program under analysis)."""
    old = os.environ.get("REPRO_SANITIZE")
    os.environ["REPRO_SANITIZE"] = "0"
    try:
        yield
    finally:
        if old is None:
            os.environ.pop("REPRO_SANITIZE", None)
        else:
            os.environ["REPRO_SANITIZE"] = old


def check_traceable(name: str, fn, *example_args) -> RaceReport:
    """Race-check one callable whose positional args are ALL round
    state (each pytree leaf seeds the chain)."""
    rep = RaceReport(name=name)
    with _no_env_sanitize():
        closed = jax.make_jaxpr(fn)(*example_args)
    n_state = len(jax.tree.leaves(example_args))
    chain = set(closed.jaxpr.invars[:n_state])
    _walk(closed.jaxpr, chain, rep, name)
    return rep


# -- single-shard WaveRuntime stand-in --------------------------------------

class LintRuntime:
    """Single-shard :class:`repro.core.engine.WaveRuntime` mimic.

    ``wave`` commits on the same composite keys production uses (so the
    protected write path carries the ``aam_commit`` scope); collectives
    are identities (one shard owns everything); telemetry attributes
    exist so round functions can read them."""

    def __init__(self, block: int, batch=None,
                 spec: CommitSpec | None = None):
        self.block = int(block)
        self.batch = batch
        self.spec = spec if spec is not None \
            else CommitSpec(backend="atomic", stats=False)
        self.level = None
        self.max_subrounds = 1
        self.conflicts = jnp.zeros((), jnp.int32)
        self.subrounds = jnp.zeros((), jnp.int32)
        self.messages = jnp.zeros((), jnp.int32)
        self.delivered_all = jnp.ones((), bool)

    @property
    def shard(self):
        return jnp.zeros((), jnp.int32)

    @property
    def gid(self):
        return jnp.arange(self.block, dtype=jnp.int32)

    def psum(self, x):
        return x

    def any(self, mask):
        return jnp.any(mask)

    def wave(self, state_l, target, payload, valid, *, op: str,
             major=None, batch=None):
        batch = batch if batch is not None else self.batch
        width = batch.wave_width if batch is not None else 1
        key = jnp.clip(jnp.asarray(target, jnp.int32), 0, self.block - 1)
        if width > 1:
            if major is None:
                raise ValueError("wave_width > 1 needs per-message "
                                 "`major` item ids")
            key = fuse_keys(key, jnp.clip(jnp.asarray(major, jnp.int32),
                                          0, width - 1), width)
        key = jnp.where(jnp.asarray(valid, bool), key, -1)
        s_leaves, tdef = jax.tree.flatten(state_l)
        p_leaves = jax.tree.leaves(payload)
        if len(p_leaves) != len(s_leaves):
            raise ValueError("state/payload pytrees must match")
        new_s, succ = [], []
        for s, p in zip(s_leaves, p_leaves):
            res = commit(s, make_messages(key, jnp.asarray(p),
                                          jnp.asarray(valid, bool)),
                         op, self.spec)
            new_s.append(res.state)
            succ.append(res.success)
        return tdef.unflatten(new_s), tdef.unflatten(succ)

    def gather(self, arr_l, idx, valid=None, *, fill=0):
        idx = jnp.asarray(idx, jnp.int32)
        if valid is None:
            valid = jnp.ones(idx.shape, bool)
        idxc = jnp.clip(idx, 0, self.block - 1)

        def read(a):
            out = a[idxc]
            f = jnp.asarray(fill, out.dtype)
            return jnp.where(valid, out, f)

        return jax.tree.map(read, arr_l)


# -- entry-point catalog ----------------------------------------------------

def _tiny_graphs(seed: int = 0):
    """One weighted tiny graph + a 2-graph GraphSet (sizes differ so
    graph-batch offsets are non-trivial)."""
    from repro.graphs.csr import GraphSet
    from repro.graphs.generators import erdos_renyi, random_weights
    g = random_weights(erdos_renyi(12, avg_degree=3.0, seed=seed), seed=1)
    gs = GraphSet([
        random_weights(erdos_renyi(7, avg_degree=3.0, seed=seed + 1),
                       seed=2),
        random_weights(erdos_renyi(11, avg_degree=3.0, seed=seed + 2),
                       seed=3),
    ])
    return g, gs


def _one_device_mesh(axis: str = "data"):
    return jax.sharding.Mesh(np.asarray(jax.devices()[:1]), (axis,))


def entry_points():
    """``(label, thunk)`` for every public distributed/batched wrapper —
    the thunk raises :class:`repro.core.engine.LintCapture`."""
    from repro.graphs.algorithms import (bfs, boruvka, coloring, pagerank,
                                         sssp, stconn)
    g, gs = _tiny_graphs()
    mesh = _one_device_mesh()
    L = 2
    srcL = jnp.zeros((L,), jnp.int32)
    srcG = jnp.zeros((len(gs.graphs),), jnp.int32)
    srcLG = jnp.zeros((L, len(gs.graphs)), jnp.int32)
    tG = jnp.ones((len(gs.graphs),), jnp.int32)
    return [
        ("bfs/distributed",
         lambda: bfs.distributed_bfs(mesh, g, 0)),
        ("bfs/lanes",
         lambda: bfs.distributed_multi_source_bfs(mesh, g, srcL)),
        ("bfs/product",
         lambda: bfs.distributed_product_bfs(mesh, gs, srcLG)),
        ("bfs/graphs",
         lambda: bfs.batched_over_graphs_bfs(gs, srcG, mesh=mesh)),
        ("sssp/distributed",
         lambda: sssp.distributed_sssp(mesh, g, 0)),
        ("sssp/lanes",
         lambda: sssp.distributed_multi_source_sssp(mesh, g, srcL)),
        ("sssp/graphs",
         lambda: sssp.batched_over_graphs_sssp(gs, srcG, mesh=mesh)),
        ("pagerank/distributed",
         lambda: pagerank.distributed_pagerank(mesh, g)),
        ("pagerank/lanes",
         lambda: pagerank.distributed_multi_source_pagerank(mesh, g,
                                                            srcL)),
        ("pagerank/graphs",
         lambda: pagerank.batched_over_graphs_pagerank(gs, srcG,
                                                       mesh=mesh)),
        ("coloring/distributed",
         lambda: coloring.distributed_coloring(mesh, g)),
        ("coloring/graphs",
         lambda: coloring.batched_over_graphs_coloring(gs, mesh=mesh)),
        ("stconn/distributed",
         lambda: stconn.distributed_stconn(mesh, g, 0, 1)),
        ("stconn/lanes",
         lambda: stconn.distributed_multi_source_stconn(mesh, g, srcG,
                                                        tG)),
        ("stconn/graphs",
         lambda: stconn.batched_over_graphs_stconn(gs, srcG, tG,
                                                   mesh=mesh)),
        ("boruvka/distributed",
         lambda: boruvka.distributed_boruvka(mesh, g)),
        ("boruvka/forest",
         lambda: boruvka.distributed_boruvka_forest(mesh, g)),
        ("boruvka/graphs",
         lambda: boruvka.batched_over_graphs_boruvka(gs, mesh=mesh)),
    ]


def capture_algorithms(points=None):
    """Run every entry point under the capture seam; returns
    ``[(label, LintCapture)]``."""
    out = []
    points = entry_points() if points is None else points
    E._LINT_CAPTURE = True
    try:
        for label, thunk in points:
            try:
                thunk()
            except E.LintCapture as cap:
                out.append((label, cap))
                continue
            raise RuntimeError(
                f"{label}: run_distributed was never reached — entry "
                f"point changed shape; update the aamlint catalog")
    finally:
        E._LINT_CAPTURE = False
    return out


def _lint_edges(g):
    n = g.src.shape[0]
    return E.EdgeSlice(
        src=jnp.asarray(g.src, jnp.int32),
        dst=jnp.asarray(g.dst, jnp.int32),
        weight=jnp.asarray(g.weights, jnp.float32),
        valid=jnp.ones((n,), bool),
        eid=jnp.arange(n, dtype=jnp.int32),
        my_src=jnp.asarray(g.src, jnp.int32))


def check_algorithm(label: str, cap) -> RaceReport:
    """Trace one captured algorithm's round step and race-check it."""
    g, batch = cap.g, cap.batch
    layout = SimpleNamespace(num_shards=1, block=g.num_vertices,
                             emax=g.src.shape[0],
                             num_vertices=g.num_vertices,
                             num_edges=g.src.shape[0],
                             vpad=g.num_vertices)
    state0, scalars0 = cap.alg.init(g, layout)
    edges = _lint_edges(g)
    # block = vertex range; wave() clamps targets to it and fuses the
    # major ids itself, so fused [block * width] state needs no special
    # casing here
    rt = LintRuntime(block=layout.block, batch=batch)

    def round_step(state, scalars):
        return cap.alg.round_fn(rt, edges, state, scalars, 0)

    rep = RaceReport(name=f"{label} ({cap.alg.name})")
    closed = jax.make_jaxpr(round_step)(state0, scalars0)
    n_state = len(jax.tree.leaves(state0))
    chain = set(closed.jaxpr.invars[:n_state])
    _walk(closed.jaxpr, chain, rep, rep.name)
    return rep


def check_all(extra_traceables=()) -> list[RaceReport]:
    """Race-check every distributed entry point + the ProductWave chunk
    bodies (+ any ``(name, fn, example_state)`` extras, e.g. planted
    fixtures)."""
    reports = [check_algorithm(label, cap)
               for label, cap in capture_algorithms()]
    from repro.serve.product_wave import lint_traceables
    for name, fn, example in lint_traceables():
        reports.append(check_traceable(name, fn, example))
    for name, fn, example in extra_traceables:
        reports.append(check_traceable(name, fn, example))
    return reports
