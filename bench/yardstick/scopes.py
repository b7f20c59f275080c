"""Reduction of the program's own labels in a profiler trace.

The program labels its work itself (``src/repro``): host spans named
``hfl.*`` (``jax.profiler.TraceAnnotation``: ``hfl.round`` and
``hfl.eval`` in ``HFLSimulator.run``; ``hfl.update`` with
``hfl.engine_step``, ``hfl.merge_row``, ``hfl.publish``, ``hfl.masks``
and ``hfl.wave``, and ``hfl.checkpoint`` with ``hfl.ckpt_state`` and
``hfl.ckpt_write``, in ``HFLService``), and device instructions whose HLO
metadata carries a named scope ``hfl.*`` (``hfl.local_step``,
``hfl.edge_agg``, ``hfl.cloud_agg``, ``hfl.wave_select``, ``hfl.merge``),
mapped per program by ``HFLSimulator.op_scopes()``:
``{program: {instruction: scope}}``.

Read the host spans with ``trace.load(path, span_prefix="hfl.")``.  The
functions here take those spans, the ``bench.`` spans of
``trace.load(path)`` and one chip's ``trace.DeviceTrace``, and give what
the per-layer metrics of the program's layers read.  A trace of a program
without the labels gives empty results, never an error.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from bench.yardstick import trace as trace_lib

Span = Tuple[str, float, float]          # (name, start_ns, dur_ns)
ROUND_PROGRAM = "jit_cloud_round"
#: Host spans in which the service's control plane, not the device, holds
#: the update: the innermost program span of a gap that ``ctrl_ms`` counts.
CONTROL_SPANS = ("hfl.update", "hfl.engine_step", "hfl.masks",
                 "hfl.publish")


def instr_seconds(dev: trace_lib.DeviceTrace, lo: float,
                  hi: float) -> Dict[Tuple[str, str], float]:
    """Device seconds in [lo, hi] per ``(program, instruction)`` of the
    (non-container) instructions; the program is the one whose run holds
    the instruction's midpoint (``"?"`` for none)."""
    runs = sorted((s, s + d, n) for n, s, d in dev.modules)
    acc: Dict[Tuple[str, str], float] = {}
    j = 0
    for o in sorted(dev.ops, key=lambda o: o.start_ns):
        if o.opcode in trace_lib.CONTAINER_OPCODES:
            continue
        mid = o.start_ns + o.dur_ns / 2.0
        if not lo <= mid <= hi:
            continue
        while j < len(runs) and runs[j][1] < mid:
            j += 1
        prog = runs[j][2] if j < len(runs) and runs[j][0] <= mid else "?"
        acc[(prog, o.instr)] = acc.get((prog, o.instr), 0.0) + o.dur_ns * 1e-9
    return acc


def scope_seconds(instr_s: Dict[Tuple[str, str], float],
                  op_scopes: Dict[str, Dict[str, str]],
                  program: str) -> Dict[Optional[str], float]:
    """Device seconds of ``program``'s instructions per ``hfl.*`` scope;
    the key None holds the instructions in no scope."""
    scopes = op_scopes.get(program, {})
    out: Dict[Optional[str], float] = {}
    for (prog, instr), sec in instr_s.items():
        if prog == program:
            key = scopes.get(instr)
            out[key] = out.get(key, 0.0) + sec
    return out


def _length(intervals: List[trace_lib.Interval]) -> float:
    return sum(e - s for s, e in trace_lib.merge(intervals))


def _minus(intervals, holes) -> List[trace_lib.Interval]:
    """The parts of ``intervals`` (merged) that no interval of ``holes``
    covers."""
    out, holes = [], trace_lib.merge(holes)
    for s, e in trace_lib.merge(intervals):
        t = s
        for hs, he in holes:
            if he <= t or hs >= e:
                continue
            if hs > t:
                out.append((t, hs))
            t = max(t, he)
        if t < e:
            out.append((t, e))
    return out


def eval_seconds(hfl_spans: List[Span], dev: trace_lib.DeviceTrace,
                 lo: float, hi: float,
                 program: str = ROUND_PROGRAM) -> float:
    """Wall seconds in [lo, hi] inside an ``hfl.eval`` span during which no
    run of ``program`` is on the device."""
    evals = trace_lib.clip([(s, s + d) for n, s, d in hfl_spans
                            if n == "hfl.eval"], lo, hi)
    rounds = [(s, s + d) for n, s, d in dev.modules if n == program]
    return _length(_minus(evals, rounds)) * 1e-9


def gaps(dev: trace_lib.DeviceTrace, lo: float,
         hi: float) -> List[trace_lib.Interval]:
    """The intervals of [lo, hi] in which no instruction ran."""
    out, t = [], lo
    for s, e in trace_lib.busy_intervals(dev, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def chain(spans: List[Span], at: float) -> List[str]:
    """Names of the spans that hold time ``at``, outermost first (the
    window's own span left out)."""
    return [n for _, n in sorted((s, n) for n, s, d in spans
                                 if s <= at <= s + d and n != "bench.window")]


def idle_gaps(bench_spans: List[Span], hfl_spans: List[Span],
              dev: trace_lib.DeviceTrace, lo: float, hi: float,
              k: int = 10) -> List[list]:
    """``trace.idle_gaps`` with the program's spans: each gap is named by
    the ``bench.`` chain around its midpoint, exactly as there, followed
    by the ``hfl.`` chain, joined by ``>``; ``[name, seconds]``, largest
    first."""
    acc: Dict[str, float] = {}
    for g0, g1 in gaps(dev, lo, hi):
        mid = (g0 + g1) / 2.0
        name = ">".join(chain(bench_spans, mid)) or "outside bench spans"
        name = ">".join([name] + chain(hfl_spans, mid))
        acc[name] = acc.get(name, 0.0) + (g1 - g0)
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns * 1e-9] for name, ns in ranked]


def idle_by_innermost(hfl_spans: List[Span], dev: trace_lib.DeviceTrace,
                      lo: float, hi: float) -> Dict[Optional[str], float]:
    """Idle device seconds in [lo, hi] by the innermost ``hfl.`` span that
    holds each idle instant (the latest started; None: outside every
    program span).  A gap that crosses span boundaries is split there."""
    idle = gaps(dev, lo, hi)
    edges = sorted([(s, 1, i) for i, (_, s, _) in enumerate(hfl_spans)]
                   + [(s + d, -1, i)
                      for i, (_, s, d) in enumerate(hfl_spans)])
    acc: Dict[Optional[str], float] = {}
    active: Dict[int, float] = {}
    gi, cur = 0, lo

    def add(a, b):
        nonlocal gi
        while gi < len(idle) and idle[gi][1] <= a:
            gi += 1
        sec, k = 0.0, gi
        while k < len(idle) and idle[k][0] < b:
            sec += max(0.0, min(b, idle[k][1]) - max(a, idle[k][0]))
            k += 1
        if sec > 0.0:
            key = (hfl_spans[max(active, key=lambda i: (active[i], i))][0]
                   if active else None)
            acc[key] = acc.get(key, 0.0) + sec * 1e-9

    for x, kind, i in edges:
        x = min(max(x, lo), hi)
        if x > cur:
            add(cur, x)
            cur = x
        if kind > 0:
            active[i] = hfl_spans[i][1]
        else:
            active.pop(i, None)
    if hi > cur:
        add(cur, hi)
    return acc


def ctrl_seconds(hfl_spans: List[Span], dev: trace_lib.DeviceTrace,
                 lo: float, hi: float) -> float:
    """Idle device seconds whose innermost program span is one of
    ``CONTROL_SPANS``: the control plane holds the chip."""
    by = idle_by_innermost(hfl_spans, dev, lo, hi)
    return sum(by.get(n, 0.0) for n in CONTROL_SPANS)
