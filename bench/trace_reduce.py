"""Reduces a profiler trace (`.xplane.pb`) to what the metrics read.

Read with `jax.profiler.ProfileData`, nothing else. On a TPU trace each
chip is a plane ``/device:TPU:<n>``. Its ``XLA Modules`` line holds every
program execution, named ``<jit name>(<id>)``; its ``XLA Ops`` line every
operation, named by its HLO text (``%_paged_decode.10 = (...)
custom-call(...)``), nested in time: a loop's event spans the events of
its body. Host threads live on ``/host:CPU``, where the harness's
`jax.profiler.TraceAnnotation` spans appear by name. All timestamps share
one clock.

From these:

* the traced window: from the first to the last harness span;
* busy time: the union of the intervals in which an operation ran on a
  chip, clipped to the window, averaged over the chips;
* device time per program (jit name without its id) and per operation:
  an operation is named ``<program>/<HLO name without its number>``
  (``jit_run/_paged_decode``), its program being the module execution
  that holds it, and its time is its self time (its duration less the
  operations nested in it), so that loops do not count their bodies
  twice;
* idle gaps: the stretches of the window with no operation on the chip,
  each put down to the harness span that covers most of it ("other"
  where none does).

A trace recorded on the CPU backend has no device plane; its operations
are the host events that carry an ``hlo_module`` stat, so the same
reduction runs there (used by the tests, never reported as a device
number).
"""
from __future__ import annotations

import bisect
import dataclasses
import pathlib
import re

HOST_SPANS = ("step", "submit", "bookkeeping")


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    chips: int
    program_s: dict          # program name -> device seconds
    program_n: dict          # program name -> executions
    op_s: dict               # "program/op" -> device self seconds
    op_n: dict               # "program/op" -> executions
    idle_by_span: dict       # host span -> idle seconds in it
    gaps: list               # [(host span, seconds)], longest first
    spans: dict              # host span -> (count, seconds)


def find_xplane(trace_dir) -> pathlib.Path:
    found = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


_ID = re.compile(r"\(\d+\)$")
_NUM = re.compile(r"\.\d+$")


def program_name(name: str) -> str:
    """``jit_run(4293513019402158429)`` -> ``jit_run``."""
    return _ID.sub("", name).strip()


def op_name(name: str) -> str:
    """``%_paged_decode.10 = (...) custom-call(...)`` -> ``_paged_decode``."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return _NUM.sub("", head)


def _union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _self_times(ops) -> list:
    """ops: [(start, end, name)] of one line, nested in time. Returns
    [(start, self_ns, name)]: each duration less its children's."""
    ops = sorted(ops, key=lambda o: (o[0], -o[1]))
    selfs = [o[1] - o[0] for o in ops]
    stack = []
    for i, (s, e, _) in enumerate(ops):
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][1]:
            selfs[stack[-1]] -= e - s
        stack.append(i)
    return [(o[0], d, o[2]) for o, d in zip(ops, selfs)]


def load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))


def reduce(path, host_spans=HOST_SPANS) -> Summary:
    planes = list(load(path).planes)
    chips = [p for p in planes if p.name.startswith("/device:TPU")]
    spans = []                       # (start, end, name) harness spans
    cpu_ops = []                     # CPU backend: (start, end, name, prog)
    for p in planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            for ev in line.events:
                end = ev.start_ns + ev.duration_ns
                if ev.name in host_spans:
                    spans.append((ev.start_ns, end, ev.name))
                elif not chips and ev.duration_ns > 0:
                    mod = _stats(ev).get("hlo_module")
                    if mod is not None:
                        cpu_ops.append((ev.start_ns, end, ev.name, str(mod)))
    if not spans:
        raise ValueError(f"{path}: no harness spans {host_spans}")
    lo = min(s for s, _, _ in spans)
    hi = max(e for _, e, _ in spans)

    program_s, program_n, op_s, op_n = {}, {}, {}, {}

    def add(d, n, key, sec):
        d[key] = d.get(key, 0.0) + sec
        n[key] = n.get(key, 0) + 1

    per_chip = []                    # [(start, end)] operations per chip
    for p in chips:
        lines = {ln.name: ln for ln in p.lines}
        mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                       program_name(ev.name))
                      for ev in (lines["XLA Modules"].events
                                 if "XLA Modules" in lines else []))
        for s, e, name in mods:
            if lo <= s <= hi:
                add(program_s, program_n, name, (e - s) * 1e-9)
        starts = [m[0] for m in mods]
        ops = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
               for ev in (lines["XLA Ops"].events if "XLA Ops" in lines
                          else []) if ev.duration_ns > 0]
        per_chip.append([[s, e] for s, e, _ in ops])
        for s, d, name in _self_times(ops):
            if not lo <= s <= hi:
                continue
            k = bisect.bisect_right(starts, s) - 1
            prog = mods[k][2] if k >= 0 and s < mods[k][1] else "?"
            add(op_s, op_n, f"{prog}/{op_name(name)}", d * 1e-9)
    if not chips:
        per_chip.append([[s, e] for s, e, _, _ in cpu_ops])
        for s, e, name, mod in cpu_ops:
            if lo <= s <= hi:
                add(op_s, op_n, f"{program_name(mod)}/{op_name(name)}",
                    (e - s) * 1e-9)

    unions = [_clip(_union(ops), lo, hi) for ops in per_chip]
    busy = sum(sum(e - s for s, e in u) for u in unions) / len(unions)

    # idle gaps on the first chip, put down to the covering host span
    spans.sort()
    span_starts = [s for s, _, _ in spans]
    gaps, idle_by_span = [], {}
    edges = [lo] + [x for iv in unions[0] for x in iv] + [hi]
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if g1 <= g0:
            continue
        best, best_ov = "other", 0
        k = bisect.bisect_right(span_starts, g1)
        for s, e, name in spans[max(0, k - 64):k]:
            ov = min(e, g1) - max(s, g0)
            if ov > best_ov:
                best, best_ov = name, ov
        gaps.append((best, (g1 - g0) * 1e-9))
        idle_by_span[best] = idle_by_span.get(best, 0.0) + (g1 - g0) * 1e-9
    gaps.sort(key=lambda g: -g[1])
    span_tot = {}
    for s, e, name in spans:
        c, t = span_tot.get(name, (0, 0.0))
        span_tot[name] = (c + 1, t + (e - s) * 1e-9)
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9,
                   chips=len(unions), program_s=program_s,
                   program_n=program_n, op_s=op_s, op_n=op_n,
                   idle_by_span=idle_by_span, gaps=gaps, spans=span_tot)


def breakdown(s: Summary, top: int = 10) -> dict:
    """The ledger's view of a trace: the device operations that took most
    (self) time, and the longest idle gaps by what the host was doing."""
    ops = sorted(s.op_s.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in s.gaps[:top]]}
