"""Runs one benchmark cell in this process and prints its result line.

Everything is found by name: the cell in ``BENCHMARK.json``; its
configuration at the entry's ``file``; its traffic mix at
``bench/traffic/<traffic>.json``; the traffic's driver at
``bench/drivers/<driver>.py``; the configuration's model at
``bench/models/<model>.py``; the cell's correctness limits at
``bench/limits/<cell>.json``; each per-layer metric's reader at
``bench/metrics/<metric>.py``.  A new cell, mix, model or metric is new
files and new entries; no file here changes.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import os
import re
import sys
import tempfile
import time
from typing import Optional

BENCH_DIR = "bench"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    pass


def load_json(path: str):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_module(path: str):
    name = "bench_" + re.sub(r"\W", "_", os.path.relpath(path))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Registry:
    """The benchmark's files under a checkout root, found by name."""

    def __init__(self, root: str):
        self.root = root
        self.bench = load_json(os.path.join(root, "BENCHMARK.json"))

    def _path(self, *parts) -> str:
        return os.path.join(self.root, BENCH_DIR, *parts)

    def cell(self, name: str) -> dict:
        for w in self.bench["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.bench["configs"]:
            if c["name"] == name:
                return load_json(os.path.join(self.root, c["file"]))
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return load_json(self._path("traffic", f"{name}.json"))

    def driver(self, name: str):
        return load_module(self._path("drivers", f"{name}.py"))

    def model(self, name: str):
        return load_module(self._path("models", f"{name}.py"))

    def limits(self, cell: str) -> dict:
        return load_json(self._path("limits", f"{cell}.json"))

    def reader(self, metric: str):
        return load_module(self._path("metrics", f"{metric}.py"))

    def end_to_end(self, cell: str) -> list:
        return [m for m in self.bench["end_to_end"]
                if cell in m.get("workloads", [cell])]

    def per_layer(self, cell: str) -> list:
        moved = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.bench["per_layer"]
                if (cell in m["workloads"] if "workloads" in m
                    else m["moves"] in moved)]


@dataclasses.dataclass
class Cell:
    """What a driver is given."""
    name: str
    cfg: dict
    traffic: dict
    model: object
    seed: int
    chips: int


@dataclasses.dataclass
class Outcome:
    """What a driver returns."""
    e2e: dict                  # end-to-end metric name -> value
    attempted: int
    failed: int
    checks: dict               # compared number name -> value
    layer: dict                # inputs of the per-layer readers
    ok: bool = True            # False when an output was not finite


class Session:
    """Set-up clock, measured window, host spans, compile count and the
    profiler, for one run."""

    def __init__(self, t_start: float, seconds: float,
                 trace_dir: Optional[str]):
        import jax
        self._jax = jax
        self.t_start = t_start
        self.seconds = float(seconds)
        self.trace_dir = trace_dir
        self.tracing = trace_dir is not None
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        self.setup_s = self.t0 = self.t1 = None
        self.window_compiles = None
        self._win = None

    def _on_event(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.compiles += 1

    def span(self, name: str):
        if not self.tracing:
            return contextlib.nullcontext()
        return self._jax.profiler.TraceAnnotation(name)

    def begin_window(self) -> None:
        self.setup_s = time.perf_counter() - self.t_start
        self._c0 = self.compiles
        if self.tracing:
            opts = self._jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            opts.enable_hlo_proto = False
            self._jax.profiler.start_trace(self.trace_dir,
                                           profiler_options=opts)
            self._win = self._jax.profiler.TraceAnnotation("bench.window")
            self._win.__enter__()
        self.t0 = time.perf_counter()

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0

    def window_over(self) -> bool:
        return self.elapsed() >= self.seconds

    def end_window(self) -> None:
        self.t1 = time.perf_counter()
        if self.tracing:
            self._win.__exit__(None, None, None)
            self._jax.profiler.stop_trace()
        self.window_compiles = self.compiles - self._c0

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def memory_peak(self) -> Optional[int]:
        stats = self._jax.devices()[0].memory_stats() or {}
        return stats.get("peak_bytes_in_use")


def require_chip(chips: int):
    """The devices to run on; raises ``NoChip`` without a TPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX's first device is {devs[0].platform} "
                     f"({devs[0].device_kind}); this benchmark runs on a "
                     f"TPU only")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} TPU chips, JAX finds "
                     f"{len(devs)}")
    return devs


def use_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (or ``JAX_COMPILATION_CACHE_DIR``), every program cached."""
    import jax
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(root, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def judge(checks: dict, limits: dict) -> bool:
    missing = set(limits) - set(checks)
    if missing:
        raise KeyError(f"no reading for the limits {sorted(missing)}")
    return all(math.isfinite(checks[k]) and checks[k] <= limits[k]
               for k in limits)


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, *, t_start: float, require_tpu: bool = True,
             trace_dir: Optional[str] = None) -> dict:
    """Run one cell and return its result object (not printed)."""
    reg = Registry(root)
    w = reg.cell(workload)
    cfg = reg.config(w["config"])
    traffic = reg.traffic(w["traffic"])
    limits = reg.limits(workload)
    if require_tpu:
        devs = require_chip(int(w["chips"]))
        use_compile_cache(root)
    else:
        import jax
        devs = jax.devices()
    src = os.path.join(root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    cell = Cell(name=workload, cfg=cfg, traffic=traffic,
                model=reg.model(cfg["model"]), seed=seed,
                chips=int(w["chips"]))
    if trace:
        seconds = min(seconds, traffic["trace_seconds"])
    with contextlib.ExitStack() as stack:
        tdir = None
        if trace:
            tdir = trace_dir or stack.enter_context(
                tempfile.TemporaryDirectory(prefix="bench-trace-"))
        session = Session(t_start, seconds, tdir)
        driver = reg.driver(traffic["driver"])
        out = driver.run(cell, session)
        summary = None
        if trace:
            from bench.yardstick import trace as trace_lib
            summary = trace_lib.summarize(trace_lib.load(tdir))
    print(f"[bench] {workload}: compilations inside the window: "
          f"{session.window_compiles}; set-up {session.setup_s!r} s; "
          f"window {session.window_s!r} s", flush=True)
    d = devs[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devs),
              "memory_peak_bytes": out.layer.get("memory_peak_bytes")}
    if trace:
        from bench.yardstick import peaks
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        ctx = dict(out.layer, trace=summary, cfg=cfg, model=cell.model,
                   peaks=peaks.peaks(d.device_kind))
        metrics = {}
        for m in reg.per_layer(workload):
            value = reg.reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(out.e2e, setup_s=session.setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in reg.end_to_end(workload)}
    correct = out.ok and judge(out.checks, limits)
    result = {"correct": bool(correct), "attempted": int(out.attempted),
              "failed": int(out.failed), "metrics": metrics,
              "device": device}
    if trace:
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    result["checks"] = {k: {"value": out.checks[k], "limit": limits[k]}
                        for k in limits}
    return result


def main(args, t_start: float) -> int:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        result = run_cell(root, args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=t_start,
                          trace_dir=args.trace_dir)
    except NoChip as e:
        print(f"bench: {e}. Nothing was run.", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
