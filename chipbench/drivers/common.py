"""What the drivers share: the graph a configuration describes, drawn
once from the configuration's ``structure_seed`` by the benchmark's own
generator, its vertices relabelled by the run's seed, and ingested
through the program's ``from_edges`` (set-up); the tuner's verdict,
resolved in set-up; and the tier ``auto`` resolved to."""
from __future__ import annotations

import numpy as np

from chipbench.lib import graph500


class GraphData:
    """The edge list of a configuration at ``scale`` (the configuration's
    own unless a rehearsal shrinks it): the Kronecker draw of the
    configuration's structure seed under the labels of the run's seed.
    Once built, also its reference CSR."""

    def __init__(self, config: dict, seed: int, scale: int | None):
        self.config = config
        self.seed = seed
        self.structure_seed = config["structure_seed"]
        self.scale = scale or config["scale"]
        self.n = 1 << self.scale
        self.src, self.dst = graph500.kronecker_edges(
            self.scale, config["edge_factor"], self.structure_seed,
            a=config["A"], b=config["B"], c=config["C"], label_seed=seed)
        self._csr = None

    def search_keys(self, count: int) -> np.ndarray:
        """Graph500's ``count`` search keys (degree >= 1), drawn once per
        configuration from its structure seed, under this run's labels,
        in the order they were drawn."""
        relabel = graph500.relabelling(self.n, self.structure_seed,
                                       self.seed)
        deg = graph500.degree(self.src, self.dst, self.n)[relabel]
        return relabel[graph500.search_keys(deg, count,
                                            self.structure_seed)]

    def isolated_vertex(self) -> int | None:
        """A vertex without edges, if there is one: a search from it runs
        the same program as any other for one round."""
        idle = np.flatnonzero(graph500.degree(self.src, self.dst,
                                              self.n) == 0)
        return int(idle[0]) if idle.size else None

    def program_graph(self):
        """The program's ingest of the edge list (``from_edges``, as
        ``repro.graphs.generators.kronecker`` calls it)."""
        import jax
        from repro.graphs.csr import from_edges
        g = from_edges(self.src, self.dst, self.n, symmetrize=True)
        return jax.block_until_ready(g)

    def csr(self):
        """``(indptr, adj)`` of the reference, built by the benchmark."""
        if self._csr is None:
            self._csr = graph500.csr(self.src, self.dst, self.n)
        return self._csr


def pretune(spec, op: str, vertices: int, dtype, n: int):
    """Resolve the ``auto`` ``spec`` of an ``op`` commit of ``n`` messages
    into a ``[vertices]`` state of ``dtype`` outside any trace, through
    the program's own ``policy_for``, as the entry point calls it from
    its trace.  Called there, the tuner's micro-commits take tracers and
    it races the time it takes to trace them (a coin toss between tiers
    four times apart); called here they run, and the entry's call finds
    this verdict in the tuner's cache.  Part of set-up.

    Every run races anew: the tuner's file cache is switched off, so no
    run reads a verdict an earlier run left in the working directory.
    Running the race leaves the chip 5-7% slower on the solves that
    follow, so a run that skipped it would read faster than the rest."""
    import os

    import jax
    from repro.core.autotune import policy_for
    os.environ["REPRO_AUTOTUNE_CACHE"] = "off"
    return policy_for(spec, jax.ShapeDtypeStruct((vertices,), dtype), n=n,
                      op=op).backend


def tuner_races(since: int) -> list:
    """The tuner's races since audit entry ``since``: each finalist's
    time and the winner."""
    from repro.core.autotune import DEFAULT_TUNER
    return [{"op": e["op"], "times_us": e["times_us"],
             "winner": e["winner"]}
            for e in DEFAULT_TUNER.audit[since:] if e.get("event") == "race"]


def resolved_tiers(since: int) -> list:
    """Commit tiers the program's tuner chose since audit entry ``since``."""
    from repro.core.autotune import DEFAULT_TUNER
    return sorted({e["backend"] for e in DEFAULT_TUNER.audit[since:]
                   if e.get("event") == "policy"})


def audit_mark() -> int:
    from repro.core.autotune import DEFAULT_TUNER
    return len(DEFAULT_TUNER.audit)
