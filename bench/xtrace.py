"""Reduction of a profiler trace to device busy time, module time and
idle gaps named by what the host was doing.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
``jax.profiler.ProfileData``: device planes (``/device:TPU:n``) give the
device operations (line ``XLA Ops``) and the compiled programs that ran
(line ``XLA Modules``); the host plane gives the benchmark's own spans,
written as ``TraceAnnotation``s on the same clock. Everything after
``load`` works on plain ``(name, start_ns, end_ns)`` tuples, so the tests
build small traces by hand.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")


@dataclasses.dataclass
class Trace:
    ops: dict            # device name -> [(name, start_ns, end_ns)]
    modules: dict        # device name -> [(name, start_ns, end_ns)]
    spans: list          # host spans [(name, start_ns, end_ns)]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str, span_names) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, modules, spans = {}, {}, []
    wanted = set(span_names)
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            op_line = lines.get(OPS_LINE)
            evs = (op_line.events if op_line is not None else
                   [e for ln in plane.lines if ln.name != MODULES_LINE
                    for e in ln.events])
            ops[plane.name] = [(op_name(e.name), e.start_ns, e.end_ns)
                               for e in evs if e.duration_ns > 0]
            mod_line = lines.get(MODULES_LINE)
            modules[plane.name] = ([(e.name, e.start_ns, e.end_ns)
                                    for e in mod_line.events]
                                   if mod_line is not None else [])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.end_ns)
                          for e in line.events if e.name in wanted]
    return Trace(ops, modules, spans)


# ---------------------------------------------------------------------------
# pure reductions over (name, start, end) tuples
# ---------------------------------------------------------------------------

def union(intervals, lo: float, hi: float) -> list:
    """Merged ``[(start, end)]`` of the intervals, clipped to [lo, hi]."""
    iv = sorted((max(s, lo), min(e, hi)) for _, s, e in intervals
                if e > lo and s < hi)
    out = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(intervals, lo: float, hi: float) -> float:
    return float(sum(e - s for s, e in union(intervals, lo, hi)))


def gaps(intervals, lo: float, hi: float) -> list:
    """The idle ``[(start, end)]`` between busy intervals in [lo, hi]."""
    out, t = [], lo
    for s, e in union(intervals, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def covering_span(spans, t: float, default: str) -> str:
    """Name of the innermost (shortest) host span that holds time ``t``."""
    best, width = default, None
    for name, s, e in spans:
        if s <= t < e and (width is None or e - s < width):
            best, width = name, e - s
    return best


def named_gaps(intervals, spans, lo: float, hi: float,
               default: str) -> list:
    """Every idle gap as ``(span name, seconds)``, named by the host span
    covering its midpoint, longest first."""
    out = [(covering_span(spans, (s + e) / 2, default), (e - s) / 1e9)
           for s, e in gaps(intervals, lo, hi)]
    return sorted(out, key=lambda x: -x[1])


def gaps_by_span(named) -> dict:
    tot = defaultdict(float)
    for name, sec in named:
        tot[name] += sec
    return dict(tot)


def op_name(text: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...), ...`` -> ``fusion.3``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def module_base(name: str) -> str:
    """``jit__unscored_live(12)`` -> ``jit__unscored_live``."""
    return name.split("(", 1)[0].strip()


def time_by_name(events, lo: float, hi: float, key=lambda n: n) -> dict:
    """Summed device seconds and counts per (keyed) event name inside
    [lo, hi]: ``{name: [seconds, count]}``."""
    out = defaultdict(lambda: [0.0, 0])
    for name, s, e in events:
        if s >= lo and e <= hi:
            rec = out[key(name)]
            rec[0] += (e - s) / 1e9
            rec[1] += 1
    return dict(out)


def top(table: dict, n: int = 10) -> list:
    """``[[name, seconds]]`` of the ``n`` largest entries."""
    rows = sorted(table.items(), key=lambda kv: -kv[1][0]
                  if isinstance(kv[1], list) else -kv[1])
    return [[k, v[0] if isinstance(v, list) else v] for k, v in rows[:n]]


def window_of(spans, name: str = "window"):
    for n, s, e in spans:
        if n == name:
            return s, e
    raise ValueError(f"no {name!r} span in the trace")


def reduce(trace: Trace, default_span: str = "train_step") -> dict:
    """Busy and idle seconds per device (averaged over the devices),
    seconds per program and per op, and the idle gaps by host span, all
    within the ``window`` host span."""
    lo, hi = window_of(trace.spans)
    window_s = (hi - lo) / 1e9
    devices = sorted(trace.ops)
    if not devices:
        raise ValueError("the trace holds no device plane")
    busy = [busy_ns(trace.ops[d], lo, hi) / 1e9 for d in devices]
    first = devices[0]
    inner = [sp for sp in trace.spans if sp[0] != "window"]
    named = named_gaps(trace.ops[first], inner, lo, hi, default_span)
    modules = time_by_name(trace.modules.get(first, []), lo, hi,
                           key=module_base)
    ops = time_by_name(trace.ops[first], lo, hi)
    head = top(modules, 5)
    return {"window_s": window_s,
            "busy_s": sum(busy) / len(busy),
            "devices": len(devices),
            "modules": modules,
            "ops": ops,
            "idle_by_span": gaps_by_span(named),
            "idle_gaps": [[n, s] for n, s in named[:10]],
            # the programs that took most device time, then their ops
            "device_ops": head + top(ops, 10 - len(head))}
