"""Graph500 kernel 2: one breadth-first search per unit, from the
configuration's search keys in turn.

The keys are Graph500's: ``search_keys`` vertices of degree >= 1, drawn
once per configuration from its structure seed, so every seed searches
the same set.  The run's seed relabels the vertices and draws their
order, stratified by depth (``graph500.depth_stratified``): every prefix
of the order holds 6- and 7-level searches (at scale 19) in their share
of the 64, so every window holds the same work whatever the seed.  The
number of levels of each key is the configuration's ``key_levels``
(label-invariant, from the reference BFS); a rehearsal at a smaller
scale computes them instead.

On one chip the unit is ``repro.graphs.algorithms.bfs.bfs(g, key,
spec=CommitSpec(backend="auto"))``.  A configuration with a ``mesh``
runs ``distributed_bfs(mesh, g, key, capacity=..., spec=...)`` over it,
vertex-owner 1-D partitioned, as a user of ``run_distributed`` calls it.

After the window every unit's distances are compared with the NumPy
level-synchronous BFS on the benchmark's own CSR, vertex by vertex: the
limit is 0.  A distributed unit also has to report ``delivered_all``.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from chipbench.drivers import common
from chipbench.harness import Check
from chipbench.lib import graph500, reference


class Driver:
    def __init__(self, config, traffic, seed, devices, scale=None):
        self.config, self.traffic, self.devices = config, traffic, devices
        self.seed = seed
        self.rehearsal = scale is not None
        self.data = common.GraphData(config, seed, scale)
        self.mesh = None

    # -- set-up and the unit ----------------------------------------------

    def setup(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import AxisType
        from repro.core.commit import CommitSpec

        self.keys = self._keys()
        self.spec = CommitSpec(backend="auto")
        self.mark = common.audit_mark()
        self.g = self.data.program_graph()
        mesh = self.config.get("mesh")
        if mesh:
            (axis, size), = mesh.items()
            self.axis = axis
            self.mesh = jax.make_mesh((size,), (axis,),
                                      axis_types=(AxisType.Auto,),
                                      devices=self.devices[:size])
        else:
            common.pretune(self.spec, "min", self.g.num_vertices,
                           jnp.int32, int(self.g.src.shape[0]))
        warm = self.data.isolated_vertex()     # compile, warm
        jax.block_until_ready(
            self._search(self.keys[0] if warm is None else warm))

    def _keys(self) -> list:
        keys = [int(k) for k in
                self.data.search_keys(self.traffic["keys"])]
        if self.rehearsal:             # a smaller graph has other depths
            indptr, adj = self.data.csr()
            levels = [reference.levels(reference.bfs_reference(
                indptr, adj, k)) for k in keys]
        else:
            levels = self.config["key_levels"]
        if len(levels) != len(keys):
            raise ValueError(f"{len(keys)} keys, {len(levels)} key_levels")
        self.levels = dict(zip(keys, levels))
        return [keys[i] for i in graph500.depth_stratified(levels,
                                                           self.seed)]

    def _search(self, key: int):
        if self.mesh is None:
            from repro.graphs.algorithms.bfs import bfs
            return bfs(self.g, np.int32(key), spec=self.spec)
        from repro.graphs.algorithms.bfs import distributed_bfs
        dist, _, res = distributed_bfs(
            self.mesh, self.g, key, capacity=self.config["capacity"],
            axis=self.axis, max_subrounds=self.config["max_subrounds"],
            spec=self.spec, telemetry=True)
        return dist, res

    def unit(self, i: int):
        key = int(self.keys[i % len(self.keys)])
        return key, self._search(key)

    # -- after the window -------------------------------------------------

    def _dist(self, out):
        return np.asarray(out.dist if self.mesh is None else out[0])

    def _telemetry(self, out) -> dict:
        if self.mesh is None:
            return {"rounds": int(out.rounds), "messages": int(out.messages)}
        res = out[1]
        return {"rounds": int(res.rounds), "subrounds": int(res.subrounds),
                "delivered_all": bool(res.delivered_all),
                "capacity": int(res.capacity)}

    def report(self, records) -> dict:
        tel = [self._telemetry(out) for _, out in records]
        rep = {"tier": common.resolved_tiers(self.mark),
               "races": common.tuner_races(self.mark),
               "vertices": self.g.num_vertices, "edges": self.g.num_edges,
               "keys": [k for k, _ in records],
               "rounds_per_unit": [t["rounds"] for t in tel]}
        if self.mesh is not None:
            rep["subrounds_per_unit"] = [t["subrounds"] for t in tel]
            rep["capacity"] = tel[0]["capacity"] if tel else None
        return rep

    def _references(self, keys) -> dict:
        """Reference distances of each distinct key, checked against the
        depth the key order was built from."""
        indptr, adj = self.data.csr()
        keys = sorted(set(keys))
        with ThreadPoolExecutor(max_workers=os.cpu_count() or 1) as pool:
            dists = pool.map(
                lambda k: reference.bfs_reference(indptr, adj, k), keys)
            want = dict(zip(keys, dists))
        for k, dist in want.items():
            if reference.levels(dist) != self.levels[k]:
                raise RuntimeError(
                    f"key {k} has {reference.levels(dist)} BFS levels, the "
                    f"configuration's key_levels say {self.levels[k]}")
        return want

    def check(self, records) -> Check:
        indptr, _ = self.data.csr()
        want = self._references(k for k, _ in records)
        wrong, unit_ok, work = 0, [], []
        undelivered = 0
        for key, out in records:
            bad = int(np.count_nonzero(self._dist(out) != want[key]))
            ok = bad == 0
            if self.mesh is not None and not bool(out[1].delivered_all):
                undelivered += 1
                ok = False
            wrong += bad
            unit_ok.append(ok)
            work.append(reference.reached_edges(indptr, want[key]))
        limits = self.config["limits"]
        numbers = {"wrong_distances": {"value": wrong,
                                       "limit": limits["wrong_distances"]}}
        if self.mesh is not None:
            numbers["undelivered_units"] = {
                "value": undelivered, "limit": limits["undelivered_units"]}
        return Check(unit_ok, numbers, work)

    def end_to_end(self, records, check: Check, seconds: float) -> dict:
        return {"teps": sum(check.work) / seconds}

    def counters(self, records) -> dict:
        tel = [self._telemetry(out) for _, out in records]
        c = {"units": len(records), "vertices": self.g.num_vertices,
             "edges": self.g.num_edges,
             "rounds": sum(t["rounds"] for t in tel)}
        if self.mesh is None:
            c["messages"] = sum(t["messages"] for t in tel)
        else:
            c["subrounds"] = sum(t["subrounds"] for t in tel)
            c["devices"] = self.mesh.size
        return c

    def hlo_texts(self, records) -> list:
        if self.mesh is not None:
            return []          # the runner's program is built per call
        from repro.graphs.algorithms.bfs import bfs
        key = np.int32(records[0][0])
        return [bfs.lower(self.g, key, spec=self.spec).compile().as_text()]
