"""LDBC Graphalytics PageRank: one whole solve per unit,
``repro.graphs.algorithms.pagerank.pagerank(g, d=..., iters=...,
spec=CommitSpec(backend="auto"))`` with the configuration's damping
factor and iteration count.

Every solve's full rank vector is compared with the float64 NumPy
reference on the benchmark's own CSR; the number compared is the largest
relative gap of any vertex, held to the configuration's limit.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

from chipbench.drivers import common
from chipbench.harness import Check
from chipbench.lib import reference


class Driver:
    def __init__(self, config, traffic, seed, devices, scale=None):
        self.config, self.traffic, self.devices = config, traffic, devices
        self.data = common.GraphData(config, seed, scale)
        self.d = float(config["damping_factor"])
        self.iters = int(config["iterations"])

    def setup(self):
        import jax
        import jax.numpy as jnp
        from repro.core.commit import CommitSpec
        self.spec = CommitSpec(backend="auto")
        self.mark = common.audit_mark()
        self.g = self.data.program_graph()
        common.pretune(self.spec, "add", self.g.num_vertices, jnp.float32,
                       int(self.g.src.shape[0]))
        jax.block_until_ready(self.unit(0))        # compile, warm

    def unit(self, i: int):
        from repro.graphs.algorithms.pagerank import pagerank
        rank, _ = pagerank(self.g, d=self.d, iters=self.iters,
                           spec=self.spec)
        return rank

    def report(self, records) -> dict:
        return {"tier": common.resolved_tiers(self.mark),
                "races": common.tuner_races(self.mark),
                "vertices": self.g.num_vertices, "edges": self.g.num_edges,
                "rounds_per_unit": [self.iters] * len(records)}

    def check(self, records) -> Check:
        indptr, adj = self.data.csr()
        with ThreadPoolExecutor(max_workers=os.cpu_count() or 1) as pool:
            want = reference.pagerank_reference(indptr, adj, d=self.d,
                                                iters=self.iters, pool=pool)
        limit = self.config["limits"]["rank_rel_error"]
        errs = [reference.rank_error(r, want) for r in records]
        return Check([e <= limit for e in errs],
                     {"rank_rel_error": {"value": max(errs),
                                         "limit": limit}},
                     [1] * len(records))

    def end_to_end(self, records, check: Check, seconds: float) -> dict:
        return {"pr_solve_s": seconds / len(records)}

    def counters(self, records) -> dict:
        rounds = self.iters * len(records)
        return {"units": len(records), "vertices": self.g.num_vertices,
                "edges": self.g.num_edges, "rounds": rounds,
                "messages": rounds * self.g.num_edges}

    def hlo_texts(self, records) -> list:
        from repro.graphs.algorithms.pagerank import pagerank
        return [pagerank.lower(self.g, d=self.d, iters=self.iters,
                               spec=self.spec).compile().as_text()]
