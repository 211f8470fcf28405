"""Host spans of the fault-tolerance paths, on the profiler's clock.

``span("scar/save/tiles_to_host")`` is a nestable context manager. Every
span, with or without a :class:`~repro.telemetry.recorder.Recorder`:

- writes a ``jax.profiler.TraceAnnotation`` of the same name, so any
  profile (xprof, an operator's, a benchmark's) shows it on the device
  trace's clock and an idle gap of the device can be put down to it;
- never waits for the device: it times host work (a dispatch, a copy to
  the host, a file write). Device time comes from the device trace;
- adds its host seconds to its tracer's per-step rollup, which
  ``TrainLoop.run`` takes into each step's record (:meth:`SpanTracer.take`);
- under a tracer that keeps records (a Recorder's), also keeps a
  :class:`SpanRecord` with its parent and step — what ``trace.json``
  exports (Chrome ``trace_event`` format, loadable in Perfetto).

A span belongs to the tracer it is given, else to the tracer of the span
that encloses it on the same thread, else to none (annotation only). So a
controller, fabric or store with no Recorder of its own books into the
training loop's rollup whenever the loop calls it, and a background writer
is handed the tracer that was current when its work was queued.

Counters are booked at the same boundaries: bytes a span moved
(:meth:`Span.add_bytes`), the lag of each background store write
(:meth:`SpanTracer.add_lag`), and backend compiles. One ``jax.monitoring``
listener per process books each compile (its ``backend_compile_duration``
seconds, and whether the persistent cache served it) to the innermost
open span of the compiling thread. Every in-program span name starts with
``scar/``.
"""
from __future__ import annotations

import dataclasses
import itertools
import json
import os
import threading
import time
from typing import Any, Optional

from jax.profiler import TraceAnnotation

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

# the open spans of each thread, innermost last: spans inherit their
# tracer and step from here, and the compile listener books to the top
_open = threading.local()
_listen_lock = threading.Lock()
_listening = False


def _stack() -> list:
    stack = getattr(_open, "stack", None)
    if stack is None:
        stack = _open.stack = []
    return stack


def current_span() -> Optional["Span"]:
    """The innermost open span of the calling thread, or None."""
    stack = getattr(_open, "stack", None)
    return stack[-1] if stack else None


def current_tracer(default: Optional["SpanTracer"] = None,
                   ) -> Optional["SpanTracer"]:
    """The tracer of the calling thread's innermost open span, else
    ``default`` — what a background worker should book into."""
    sp = current_span()
    return sp.tracer if sp is not None and sp.tracer is not None \
        else default


def _on_duration(event: str, duration: float, **kw: Any) -> None:
    if event == COMPILE_EVENT:
        sp = current_span()
        if sp is not None and sp.tracer is not None:
            sp.tracer._book_compile(sp, float(duration), kw.get("fun_name"))


def _on_event(event: str, **_: Any) -> None:
    if event == CACHE_HIT_EVENT:
        sp = current_span()
        if sp is not None and sp.tracer is not None:
            sp.tracer._book_cache_hit(sp)


def _listen() -> None:
    """Register the compile listeners once per process."""
    global _listening
    with _listen_lock:
        if _listening:
            return
        import jax
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _listening = True


def new_rollup() -> dict:
    """An empty per-step rollup: ``spans`` {name: seconds} (inclusive,
    summed), ``compiles`` {name: [count, seconds, cache_hits]} (the count
    includes compiles the persistent cache served, which JAX times under
    the same event), ``bytes`` {name: bytes} and ``store_lag_s`` (seconds
    from enqueue to published manifest, one per completed background
    write)."""
    return {"spans": {}, "compiles": {}, "bytes": {}, "store_lag_s": []}


def merge_rollup(into: dict, more: dict) -> dict:
    """Add rollup ``more`` into ``into`` in place (and return it)."""
    for k, v in more["spans"].items():
        into["spans"][k] = into["spans"].get(k, 0.0) + v
    for k, v in more["compiles"].items():
        c = into["compiles"].setdefault(k, [0, 0.0, 0])
        for i, x in enumerate(v):
            c[i] += x
    for k, v in more["bytes"].items():
        into["bytes"][k] = into["bytes"].get(k, 0) + v
    into["store_lag_s"].extend(more["store_lag_s"])
    return into


@dataclasses.dataclass
class SpanRecord:
    name: str
    t0: float          # seconds since tracer start
    t1: float
    sid: int           # this span's id within its tracer
    parent: Optional[int]   # the enclosing span's id (None = top level)
    step: Optional[int]
    tid: int           # recording thread id
    args: dict

    @property
    def duration(self) -> float:
        return self.t1 - self.t0


class Span:
    """One open span; made by :func:`span` and entered with ``with``."""

    __slots__ = ("name", "tracer", "step", "args", "sid", "parent",
                 "_ann", "_t0")

    def __init__(self, name: str, tracer: Optional["SpanTracer"],
                 step: Optional[int], args: dict) -> None:
        self.name, self.tracer, self.step, self.args = name, tracer, step, args
        self.sid = self.parent = None

    def __enter__(self) -> "Span":
        stack = _stack()
        outer = stack[-1] if stack else None
        if outer is not None:
            if self.tracer is None:
                self.tracer = outer.tracer
            if self.step is None:
                self.step = outer.step
            if outer.tracer is self.tracer:
                self.parent = outer.sid
        if self.tracer is not None and self.tracer.keep:
            self.sid = next(self.tracer._ids)
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        stack.append(self)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        _stack().pop()
        self._ann.__exit__(*exc)
        if self.tracer is not None:
            self.tracer._close(self, self._t0, t1)
        return False

    def add_bytes(self, n: int) -> None:
        """Book ``n`` bytes moved to this span's name."""
        if self.tracer is not None:
            self.tracer._book_bytes(self, int(n))

    def set(self, **args: Any) -> None:
        """Attach attributes to the kept record (queue depth, ...)."""
        self.args.update(args)


def span(name: str, tracer: Optional["SpanTracer"] = None, *,
         step: Optional[int] = None, **args: Any) -> Span:
    """A span named ``name`` (see the module docstring for whose tracer
    it books into). ``step`` defaults to the enclosing span's."""
    return Span(name, tracer, step, args)


class SpanTracer:
    """One run's spans: the per-step rollup (always) and, with ``keep``,
    the :class:`SpanRecord`s that ``trace.json`` exports."""

    def __init__(self, keep: bool = True) -> None:
        self.keep = keep
        self._t0 = time.perf_counter()
        self._lock = threading.Lock()
        self._ids = itertools.count()
        self._roll = new_rollup()
        self.spans: list[SpanRecord] = []
        _listen()

    def span(self, name: str, *, step: Optional[int] = None,
             **args: Any) -> Span:
        return Span(name, self, step, args)

    def take(self) -> dict:
        """The rollup booked since the last take (and start a new one)."""
        with self._lock:
            out, self._roll = self._roll, new_rollup()
        return out

    def add_lag(self, seconds: float) -> None:
        """One background store write's enqueue-to-publish lag."""
        with self._lock:
            self._roll["store_lag_s"].append(float(seconds))

    # -- booking (spans and the compile listener) ----------------------------

    def _close(self, sp: Span, t0: float, t1: float) -> None:
        with self._lock:
            d = self._roll["spans"]
            d[sp.name] = d.get(sp.name, 0.0) + (t1 - t0)
            if self.keep:
                self.spans.append(SpanRecord(
                    name=sp.name, t0=t0 - self._t0, t1=t1 - self._t0,
                    sid=sp.sid, parent=sp.parent, step=sp.step,
                    tid=threading.get_ident(), args=sp.args))

    def _book_bytes(self, sp: Span, n: int) -> None:
        with self._lock:
            d = self._roll["bytes"]
            d[sp.name] = d.get(sp.name, 0) + n
        if self.keep:
            sp.args["bytes"] = sp.args.get("bytes", 0) + n

    def _book_compile(self, sp: Span, seconds: float,
                      program: Optional[str]) -> None:
        with self._lock:
            c = self._roll["compiles"].setdefault(sp.name, [0, 0.0, 0])
            c[0] += 1
            c[1] += seconds
        if self.keep:
            sp.args.setdefault("compiled", []).append(program)

    def _book_cache_hit(self, sp: Span) -> None:
        with self._lock:
            self._roll["compiles"].setdefault(sp.name, [0, 0.0, 0])[2] += 1

    # -- kept records ---------------------------------------------------------

    def now(self) -> float:
        """Current tracer-relative timestamp (seconds since tracer start)
        — the time base :meth:`record` expects."""
        return time.perf_counter() - self._t0

    def record(self, name: str, t0: float, t1: float,
               step: Optional[int] = None, **args: Any) -> None:
        """Keep a record of an interval no ``with`` block can cover: an
        async maintenance sweep is dispatched inside one step and settled
        in a later one, so its [dispatch, settle] interval is closed after
        the fact by whoever settles it. Top level, not in the rollup (it
        is device work in flight, not host time)."""
        if not self.keep:
            return
        with self._lock:
            self.spans.append(SpanRecord(
                name=name, t0=float(t0), t1=float(t1), sid=next(self._ids),
                parent=None, step=step, tid=threading.get_ident(),
                args=dict(args)))

    def durations(self, name: str) -> list[float]:
        """All kept durations (seconds) of spans named ``name``."""
        return [s.duration for s in self.spans if s.name == name]

    def intervals(self, name: str) -> list[tuple[float, float]]:
        """All kept (t0, t1) intervals of spans named ``name`` — overlap
        assertions (does an async sweep run under ``scar/step/train``?)
        read these directly instead of re-parsing the Chrome export."""
        return [(s.t0, s.t1) for s in self.spans if s.name == name]

    # -- export -------------------------------------------------------------

    def chrome_trace(self) -> dict:
        """The ``trace_event`` document: one complete ("X") event per
        kept span. Timestamps/durations are microseconds per the format."""
        events = []
        for s in sorted(self.spans, key=lambda s: s.t0):
            args = {"step": s.step, "parent": s.parent, **s.args}
            events.append({
                "name": s.name, "cat": "repro", "ph": "X",
                "ts": round(s.t0 * 1e6, 3),
                "dur": round(s.duration * 1e6, 3),
                "pid": os.getpid(), "tid": s.tid,
                "args": {k: v for k, v in args.items() if v is not None},
            })
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "otherData": {"source": "repro.telemetry"}}

    def write_chrome_trace(self, path: str) -> str:
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.chrome_trace(), f)
        os.replace(tmp, path)
        return path
