"""The benchmark's run loop, found-by-name lookup and result line.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix; the
mix names a driver.  A driver module (``chipbench/drivers/<name>.py``)
defines ``Driver(config, traffic, seed, devices, scale)`` with:

* ``setup()`` — make the data, ingest it through the program, warm every
  program the window uses;
* ``unit(i)`` — dispatch the ``i``-th unit of work and return its record
  without waiting for it: the harness waits (``block_until_ready``), and
  the driver reads the record after the window;
* ``report(records)`` — counters of the window (tier, rounds, ...), as a
  JSON-able dict printed on an earlier line;
* ``check(records)`` — a :class:`Check`;
* ``end_to_end(records, check, seconds)`` — ``{metric: value}``;
* ``counters(records)`` — what per-layer readers need (rounds, messages,
  vertices, edges, ...);
* ``hlo_texts(records)`` — optimized HLO of the programs the window ran,
  which names each device op of a traced run's breakdown (may be empty).

The window keeps the mix's ``ahead`` units (default 0) dispatched beyond
the one it waits for, so the chip stays fed while the host stands still.

Each per-layer metric ``m`` is read by ``chipbench/metrics/<m>.py``'s
``read(ctx)``, which returns a number or ``None`` when it finds nothing to
read (the metric is then left out of the line).
"""
from __future__ import annotations

import collections
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

from chipbench.lib import peaks as peaks_lib

WINDOW_SPAN = "chipbench.window"
UNIT_SPAN = "chipbench.unit"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Check:
    """Outcome of the comparison with the reference.

    ``unit_ok`` holds one flag per unit of the window; ``numbers`` maps a
    short name to ``{"value": ..., "limit": ...}``, each value held to
    ``value <= limit``; ``work`` is the work of each unit that an
    end-to-end rate counts (edges reached, solves)."""
    unit_ok: list
    numbers: dict
    work: list

    @property
    def correct(self) -> bool:
        return (bool(self.unit_ok) and all(self.unit_ok)
                and all(n["value"] <= n["limit"]
                        for n in self.numbers.values()))


@dataclasses.dataclass
class Cell:
    name: str
    workload: dict
    config: dict
    traffic: dict
    driver_path: Path
    end_to_end: list      # metric entries this cell reports (trace 0)
    per_layer: list       # (entry, reader path) this cell reports (trace 1)


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """``BENCHMARK.json`` and the files it names, under ``root``."""

    def __init__(self, root: Path, doc: dict):
        self.root, self.doc = root, doc
        self.base = root / doc["paths"][0]
        self.run_seconds = doc["run_seconds"]

    @classmethod
    def load(cls, root: Path) -> "Bench":
        with open(root / "BENCHMARK.json") as f:
            return cls(root, json.load(f))

    def _reports(self, metric: dict, cell: str, e2e: list) -> bool:
        if "workloads" in metric:
            return cell in metric["workloads"]
        return metric.get("moves") in e2e if "moves" in metric else True

    def cell(self, name: str) -> Cell:
        by_name = {w["name"]: w for w in self.doc["workloads"]}
        if name not in by_name:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(have {sorted(by_name)})")
        w = by_name[name]
        conf = {c["name"]: c for c in self.doc["configs"]}[w["config"]]
        with open(self.root / conf["file"]) as f:
            config = json.load(f)
        with open(self.base / "traffic" / f"{w['traffic']}.json") as f:
            traffic = json.load(f)
        driver = self.base / "drivers" / f"{traffic['driver']}.py"
        e2e = [m for m in self.doc["end_to_end"]
               if self._reports(m, name, [])]
        names = [m["name"] for m in e2e]
        per_layer = [(m, self.base / "metrics" / f"{m['name']}.py")
                     for m in self.doc["per_layer"]
                     if self._reports(m, name, names)]
        for _, p in per_layer:
            if not p.is_file():
                raise FileNotFoundError(f"no reader {p}")
        if not driver.is_file():
            raise FileNotFoundError(f"no driver {driver}")
        return Cell(name, w, config, traffic, driver, e2e, per_layer)

    def listing(self) -> dict:
        cells = {}
        for w in self.doc["workloads"]:
            c = self.cell(w["name"])
            cells[c.name] = {
                "config": w["config"], "traffic": w["traffic"],
                "chips": w["chips"], "driver": c.driver_path.stem,
                "end_to_end": [m["name"] for m in c.end_to_end],
                "per_layer": [m["name"] for m, _ in c.per_layer]}
        return {"cells": cells,
                "configs": [c["name"] for c in self.doc["configs"]],
                "traffic": sorted(p.stem for p in
                                  (self.base / "traffic").glob("*.json")),
                "metrics": sorted(p.stem for p in
                                  (self.base / "metrics").glob("*.py"))}


def clean_environment() -> None:
    """The benchmark defines the deployment: the program's own switches
    (``REPRO_TRACE``'s round taps, ``REPRO_AUTOTUNE``, the sanitizer) stay
    off in every run, traced runs included."""
    for k in [k for k in os.environ if k.startswith("REPRO_")]:
        print(f"chipbench: ignoring {k}={os.environ.pop(k)!r}",
              file=sys.stderr)


class Meter:
    """JAX's compile, persistent-cache and trace events of this process."""

    def __init__(self):
        import jax
        self.requests = self.hits = self.traces = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.compile_s += secs
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self) -> dict:
        return {"compiles": self.requests - self.hits,
                "cache_loads": self.hits, "traces": self.traces,
                "compile_s": self.compile_s}


def _compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where it is set, else a fixed directory in the checkout.  Every
    program is written to it, so only a checkout's first run compiles."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _tuner_runs() -> int:
    from repro.core.autotune import DEFAULT_TUNER
    return DEFAULT_TUNER.timed_runs


def _memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _log(**kw) -> None:
    print(json.dumps(kw), file=sys.stderr, flush=True)


def run(cell: Cell, *, seed: int, seconds: float, trace: bool,
        rehearse: bool = False, scale: int | None = None,
        t_start: float | None = None, root: Path | None = None) -> dict:
    """One run of ``cell``; returns the result line as a dict."""
    t_start = time.perf_counter() if t_start is None else t_start
    root = root or Path(__file__).resolve().parents[1]
    if str(root / "src") not in sys.path:
        sys.path.insert(0, str(root / "src"))
    import jax

    chips = cell.workload["chips"]
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not rehearse:
        raise NoChip(f"JAX found no TPU (platform {dev.platform!r})")
    if len(devices) < chips:
        raise NoChip(f"{cell.name} needs {chips} chips, JAX found "
                     f"{len(devices)}")
    devices = devices[:chips]
    peaks = None if rehearse else peaks_lib.peaks_for(dev.device_kind)
    cache = _compile_cache(root)
    meter = Meter()
    drv_mod = _load_module(cell.driver_path,
                           f"chipbench_driver_{cell.driver_path.stem}")
    driver = drv_mod.Driver(cell.config, cell.traffic, seed, devices, scale)
    driver.setup()
    setup_s = time.perf_counter() - t_start
    _log(phase="setup", setup_s=setup_s, compile_cache=cache,
         **meter.snapshot())

    before, tuned0 = meter.snapshot(), _tuner_runs()
    tdir = tempfile.mkdtemp(prefix="chipbench_trace_") if trace else None
    records = []
    try:
        if trace:
            # Python calls are not traced: the host spans that label idle
            # gaps are the harness's annotations and JAX's own
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
        ahead = int(cell.traffic.get("ahead", 0))
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            t0 = t_end = time.perf_counter()
            deadline = t0 + seconds
            unit_s, inflight, started = [], collections.deque(), 0
            while True:
                # once the time is up nothing more is sent; what was sent
                # is waited for and counts, over all of its time
                while len(inflight) <= ahead and (
                        started == 0 or t_end < deadline):
                    inflight.append(driver.unit(started))
                    started += 1
                with jax.profiler.TraceAnnotation(UNIT_SPAN):
                    records.append(jax.block_until_ready(inflight.popleft()))
                unit_s.append(time.perf_counter() - t_end)
                t_end += unit_s[-1]
                if not inflight and t_end >= deadline:
                    break
        if trace:
            jax.profiler.stop_trace()
        window_s = t_end - t0
        after = meter.snapshot()
        in_window = {k: after[k] - before[k] for k in before}
        in_window["tuner_calibrations"] = _tuner_runs() - tuned0
        memory_peak = _memory_peak(devices)
        _log(phase="window", units=len(records), ahead=ahead,
             window_s=window_s,
             unit_s=unit_s, in_window=in_window,
             memory_peak_bytes=memory_peak,
             **driver.report(records))

        check = driver.check(records)
        _log(phase="check", correct=check.correct, checks=check.numbers)
        if trace:
            from chipbench.lib import layers, trace as trace_lib
            tr = trace_lib.load_dir(tdir, WINDOW_SPAN)
            ctx = Context(trace=tr, counters=driver.counters(records),
                          peaks=peaks,
                          hlo_texts=driver.hlo_texts(records))
            metrics = {}
            for m, path in cell.per_layer:
                reader = _load_module(path, "chipbench_metric_"
                                      + m["name"].replace(".", "_")
                                      .replace("-", "_"))
                value = reader.read(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            busy = [tr.busy_s(d) for d in tr.devices[:len(devices)]]
            device_extra = {"busy_s": sum(busy) / max(len(busy), 1),
                            "window_s": tr.window_s}
            breakdown = tr.breakdown(layers.op_labels(ctx))
        else:
            values = driver.end_to_end(records, check, window_s)
            values["setup_s"] = setup_s
            metrics = {m["name"]: {"value": values[m["name"]],
                                   "unit": m["unit"]}
                       for m in cell.end_to_end}
            device_extra, breakdown = {}, None
    finally:
        if tdir:
            shutil.rmtree(tdir, ignore_errors=True)

    result = {"correct": check.correct, "attempted": len(records),
              "failed": sum(not ok for ok in check.unit_ok),
              "metrics": metrics,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devices),
                         "memory_peak_bytes": memory_peak, **device_extra}}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = check.numbers
    return result


@dataclasses.dataclass
class Context:
    """What a per-layer reader reads: the traced window, the ``Driver``'s
    counters of that window, the chip's peaks and the optimized HLO of
    the programs that ran."""
    trace: object
    counters: dict
    peaks: dict | None
    hlo_texts: list
