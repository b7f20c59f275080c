"""Reduction of a JAX profiler trace (``.xplane.pb``) to device numbers.

What a TPU trace holds (read from a v5e trace): one plane per chip,
``/device:TPU:<i>``, with the line ``XLA Modules`` (one event per program
run, named ``jit_<function>(<fingerprint>)``) and the line ``XLA Ops``
(one event per HLO instruction run, named by its HLO text
``%<instr> = <shape> <opcode>(...)``; a ``while`` instruction's event
spans all the instructions of its body).  Host threads are lines of the
plane ``/host:CPU``; ``jax.profiler.TraceAnnotation`` spans sit on the
line of the Python thread, named after the process (``python3``).
Device and host events share one clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: Instructions whose event spans other instructions' events.
CONTAINER_OPCODES = ("while", "conditional", "call")
_HLO = re.compile(r"^%?(?P<instr>[^\s=]+)\s*=\s*(?P<shape>.*?)\s"
                  r"(?P<opcode>[a-z][a-z0-9_\-]*)\(")
_MODULE = re.compile(r"^(?P<name>.*?)\(\d+\)$")

Interval = Tuple[float, float]


@dataclasses.dataclass
class Op:
    instr: str          # HLO instruction name, e.g. "fusion.37"
    shape: str          # its result shape text
    opcode: str         # e.g. "fusion", "custom-call", "while"
    start_ns: float
    dur_ns: float


@dataclasses.dataclass
class DeviceTrace:
    """One chip's programs and instructions, in ns on the trace clock."""
    name: str
    modules: List[Tuple[str, float, float]]      # (name, start, dur)
    ops: List[Op]


@dataclasses.dataclass
class Trace:
    devices: List[DeviceTrace]
    spans: List[Tuple[str, float, float]]        # host (name, start, dur)


def parse_op(text: str) -> Optional[Tuple[str, str, str]]:
    m = _HLO.match(text)
    if m is None:
        return None
    return m.group("instr"), m.group("shape"), m.group("opcode")


def module_name(text: str) -> str:
    """``jit_cloud_round(5295170185746817458)`` -> ``jit_cloud_round``."""
    m = _MODULE.match(text)
    return m.group("name") if m else text


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str, span_prefix: str = "bench.") -> Trace:
    """Read an ``.xplane.pb`` (or a directory holding one, or a gzipped
    ``.xplane.pb.gz``)."""
    import gzip

    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            modules, ops = [], []
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules = [(module_name(e.name), e.start_ns,
                                e.duration_ns) for e in line.events]
                elif line.name == OPS_LINE:
                    for e in line.events:
                        parsed = parse_op(e.name)
                        if parsed is None:
                            parsed = (e.name, "", "")
                        ops.append(Op(*parsed, e.start_ns, e.duration_ns))
            devices.append(DeviceTrace(plane.name, modules, ops))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.duration_ns)
                          for e in line.events
                          if e.name.startswith(span_prefix)]
    if not devices:
        raise ValueError(f"{path}: no /device:TPU:<i> plane; the trace "
                         f"was not taken on a TPU")
    return Trace(devices, spans)


def merge(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy_intervals(dev: DeviceTrace, lo: float, hi: float) -> List[Interval]:
    """Union of the intervals in which an instruction ran, in [lo, hi]."""
    return merge(clip([(o.start_ns, o.start_ns + o.dur_ns)
                       for o in dev.ops], lo, hi))


def window(trace: Trace, name: str = "bench.window") -> Interval:
    for n, s, d in trace.spans:
        if n == name:
            return s, s + d
    raise ValueError(f"the trace holds no host span {name!r}")


def _inside(start: float, dur: float, lo: float, hi: float) -> bool:
    mid = start + dur / 2.0
    return lo <= mid <= hi


def module_time_ns(dev: DeviceTrace, lo: float, hi: float,
                   names) -> float:
    """Device time of the programs named (``jit_<fn>``) in [lo, hi]."""
    return sum(min(s + d, hi) - max(s, lo) for n, s, d in dev.modules
               if n in names and s + d > lo and s < hi)


def module_runs(dev: DeviceTrace, lo: float, hi: float, names) -> int:
    return sum(1 for n, s, d in dev.modules
               if n in names and _inside(s, d, lo, hi))


def op_time_ns(dev: DeviceTrace, lo: float, hi: float, pred,
               within=None) -> float:
    """Device time of the (non-container) instructions for which
    ``pred(op)`` holds, in [lo, hi]; with ``within`` (module names), only
    those inside a run of one of those programs."""
    spans = None
    if within is not None:
        spans = merge([(s, s + d) for n, s, d in dev.modules
                       if n in within])
    total = 0.0
    for o in dev.ops:
        if o.opcode in CONTAINER_OPCODES or not pred(o):
            continue
        if not _inside(o.start_ns, o.dur_ns, lo, hi):
            continue
        if spans is not None and not any(
                s <= o.start_ns + o.dur_ns / 2.0 <= e for s, e in spans):
            continue
        total += o.dur_ns
    return total


def top_ops(dev: DeviceTrace, lo: float, hi: float,
            k: int = 10) -> List[list]:
    """The k (non-container) instructions that took most device time,
    as ``[name, seconds]``; the name is the program, the instruction and
    its result shape."""
    spans = sorted((s, s + d, n) for n, s, d in dev.modules)
    acc: Dict[str, float] = {}
    j = 0
    for o in sorted(dev.ops, key=lambda o: o.start_ns):
        if o.opcode in CONTAINER_OPCODES:
            continue
        if not _inside(o.start_ns, o.dur_ns, lo, hi):
            continue
        mid = o.start_ns + o.dur_ns / 2.0
        while j < len(spans) and spans[j][1] < mid:
            j += 1
        prog = spans[j][2] if j < len(spans) and spans[j][0] <= mid else "?"
        key = f"{prog}/{o.instr} {o.shape}"[:120]
        acc[key] = acc.get(key, 0.0) + o.dur_ns
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns * 1e-9] for name, ns in ranked]


def idle_gaps(trace: Trace, dev: DeviceTrace, lo: float, hi: float,
              k: int = 10) -> List[list]:
    """Idle device time in [lo, hi], summed by what the host was doing:
    the innermost ``bench.`` host span around each gap's midpoint (the
    chain of spans joined by ``>``), as ``[name, seconds]``, largest
    first."""
    busy = busy_intervals(dev, lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    spans = [(s, s + d, n) for n, s, d in trace.spans
             if n != "bench.window"]
    acc: Dict[str, float] = {}
    for g0, g1 in gaps:
        mid = (g0 + g1) / 2.0
        around = sorted((s, n) for s, e, n in spans if s <= mid <= e)
        name = ">".join(n for _, n in around) or "outside bench spans"
        acc[name] = acc.get(name, 0.0) + (g1 - g0)
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns * 1e-9] for name, ns in ranked]


@dataclasses.dataclass
class Summary:
    """What the per-layer readers and the result line take from a trace."""
    window_s: float
    busy_s: float                       # mean over the chips
    module_s: Dict[str, float]          # device seconds per program
    module_runs: Dict[str, int]
    agg_kernel_s: Dict[str, float]      # aggregation kernels per program
    device_ops: List[list]
    idle_gaps: List[list]


def is_agg_kernel(op: Op) -> bool:
    """The Pallas aggregation kernels (``kernels/hier_aggregate.py``):
    custom calls named after their jitted wrappers, ``hier_*``."""
    return op.opcode == "custom-call" and op.instr.startswith("hier_")


def summarize(trace: Trace, window_span: str = "bench.window") -> Summary:
    lo, hi = window(trace, window_span)
    devs = trace.devices
    busy = [sum(e - s for s, e in busy_intervals(d, lo, hi)) for d in devs]
    names = sorted({n for d in devs for n, _, _ in d.modules})
    module_s = {n: sum(module_time_ns(d, lo, hi, {n}) for d in devs) * 1e-9
                for n in names}
    runs = {n: sum(module_runs(d, lo, hi, {n}) for d in devs)
            for n in names}
    agg = {n: sum(op_time_ns(d, lo, hi, is_agg_kernel, within={n})
                  for d in devs) * 1e-9 for n in names}
    return Summary(
        window_s=(hi - lo) * 1e-9,
        busy_s=sum(busy) / len(busy) * 1e-9,
        module_s={n: v for n, v in module_s.items() if v > 0},
        module_runs={n: v for n, v in runs.items() if v > 0},
        agg_kernel_s={n: v for n, v in agg.items() if v > 0},
        device_ops=top_ops(devs[0], lo, hi),
        idle_gaps=idle_gaps(trace, devs[0], lo, hi))
