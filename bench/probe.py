"""The benchmark's own instruments around the program's layer calls.

``Probe.attach`` wraps the controller's public entry points (maintain,
save, recovery, heal) and the store's drain, so every call records a
host-clock span and, inside a profiler window, a ``TraceAnnotation`` of
the same name on the profiler's clock. No span waits for device work it
did not start, so the loop's own ordering and fences stand as they are;
only the recovery span ends when the recovered arena is ready.

A host loss erases what the lost host held: before the program's
recovery sees the live arena, the words of every block homed on the lost
devices are overwritten (``LostWords``), and the recovered arena is then
compared with the arena as it was before the loss.

``CompileCounter`` counts backend compilations and persistent-cache
reads through JAX's monitoring hooks (copied from ``chip_smoke.py``).
"""
from __future__ import annotations

import functools
import logging
import time

import numpy as np

SPANS = ("window", "input", "maintain", "save", "recovery", "heal",
         "store_drain")


class CompileCounter:
    """Backend compile seconds, compile count and persistent-cache hits,
    and the names of the programs JAX lowered, with the time it did."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.lowered: list = []        # (perf_counter, program name)
        counter = self

        class Names(logging.Handler):
            def emit(self, record):
                if str(record.msg).startswith("Compiling ") and record.args:
                    counter.lowered.append((time.perf_counter(),
                                            str(record.args[0])))

        lg = logging.getLogger("jax._src.interpreters.pxla")
        lg.setLevel(logging.DEBUG)
        lg.addHandler(Names(logging.DEBUG))
        lg.propagate = False

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def lowered_between(self, lo: float, hi: float) -> list:
        return [n for t, n in self.lowered if lo <= t <= hi]

    def snapshot(self) -> dict:
        return {"compile_seconds": self.seconds, "compiles": self.compiles,
                "cache_hits": self.cache_hits}


class Probe:
    def __init__(self):
        self.spans: list = []          # (name, t0, t1) on perf_counter
        self.recovery_checks: list = []  # device scalars: differing words

    def span(self, name: str):
        return _Span(self, name)

    def timed(self, name: str, fn):
        """``fn`` wrapped in a span."""
        @functools.wraps(fn)
        def wrapper(*a, **kw):
            with self.span(name):
                return fn(*a, **kw)
        return wrapper

    def attach(self, controller, store):
        import jax
        if store is not None:
            store.flush = self.timed("store_drain", store.flush)
        if controller is None:
            return
        controller.maintain = self.timed("maintain", controller.maintain)
        controller.maybe_checkpoint = self.timed(
            "save", controller.maybe_checkpoint)
        controller.heal_domain = self.timed("heal", controller.heal_domain)
        fab = controller.fabric
        if fab is None or controller.arena_layout is None:
            return
        words = LostWords(controller.arena_layout)
        recover = controller.on_domain_event

        @functools.wraps(recover)
        def on_domain_event(live, kind, index, step=None):
            # the lost host's values are gone: its blocks' words are
            # overwritten in place (``live`` is donated: the loop drops
            # it for the recovered arena) before recovery starts
            lost, _ = fab.domain_failure(kind, index)
            sel = words.select(lost)
            erased = words.poison(live, *sel)
            with self.span("recovery"):
                out, info = recover(erased, kind, index, step=step)
                jax.block_until_ready(out)
            # the configuration's guarantee: one host's loss is recovered
            # from the replica and parity tiers, which hold this step's
            # values, so the recovered arena is bit-equal to the arena
            # before the loss
            self.recovery_checks.append(words.differing(out, erased, *sel))
            return out, info

        controller.on_domain_event = on_domain_event

    def durations(self, name: str, lo: float = float("-inf"),
                  hi: float = float("inf")) -> list:
        return [t1 - t0 for n, t0, t1 in self.spans
                if n == name and t0 >= lo and t1 <= hi]


class LostWords:
    """The words of a flat arena that lost blocks held, and two device
    programs over them that need no second copy of the arena:
    ``poison`` flips bits of every such word in place, ``differing``
    counts the words in which a recovered arena differs from the arena
    before the loss, given the poisoned one.

    ``PATTERN`` flips the top mantissa bit of each bfloat16 half of a
    word (of a float32 word: bits 6 and 22), so every lost word changes
    and every finite value stays finite. Each block is visited through a
    window of the widest block's words, masked to its own."""

    PATTERN = 0x00400040

    def __init__(self, layout):
        import jax
        blocks = [ab for ab in layout.blocks if ab.payload > 0]
        self.gids = np.array([ab.gid for ab in blocks], np.int64)
        self.starts = np.array([ab.offset for ab in blocks], np.int32)
        self.lens = np.array([ab.payload for ab in blocks], np.int32)
        width = int(self.lens.max())
        self.poison = jax.jit(functools.partial(_poison, width),
                              donate_argnums=0)
        self.differing = jax.jit(functools.partial(_differing, width))

    def select(self, lost) -> tuple:
        """Device arrays ``(starts, lens, count)`` of the arena blocks of
        the lost global blocks ``lost`` (a mask over block ids), padded to
        a fixed length so that every loss runs one compiled program."""
        import jax.numpy as jnp
        keep = np.asarray(lost, bool)[self.gids]
        k = int(keep.sum())
        starts = np.zeros_like(self.starts)
        lens = np.zeros_like(self.lens)
        starts[:k], lens[:k] = self.starts[keep], self.lens[keep]
        return jnp.asarray(starts), jnp.asarray(lens), jnp.int32(k)


def _window(width: int, total: int, start, length):
    """Where a window of ``width`` words that covers ``[start, start +
    length)`` begins, and the mask of those words within it."""
    import jax.numpy as jnp
    c = jnp.clip(start, 0, total - width)
    j = jnp.arange(width, dtype=jnp.int32)
    return c, (j >= start - c) & (j < start - c + length)


def _poison(width, arena, starts, lens, n):
    import jax
    import jax.numpy as jnp
    w = jax.lax.bitcast_convert_type(arena, jnp.int32)
    width = min(width, w.shape[0])

    def body(i, w):
        c, m = _window(width, w.shape[0], starts[i], lens[i])
        seg = jax.lax.dynamic_slice(w, (c,), (width,))
        seg = jnp.where(m, seg ^ LostWords.PATTERN, seg)
        return jax.lax.dynamic_update_slice(w, seg, (c,))

    w = jax.lax.fori_loop(0, n, body, w)
    return jax.lax.bitcast_convert_type(w, arena.dtype)


def _differing(width, out, erased, starts, lens, n):
    """Words of ``out`` that differ from the arena before the loss: from
    ``erased`` outside the lost blocks, from ``erased`` with the poison
    undone inside them."""
    import jax
    import jax.numpy as jnp
    a = jax.lax.bitcast_convert_type(out, jnp.int32)
    p = jax.lax.bitcast_convert_type(erased, jnp.int32)
    width = min(width, a.shape[0])

    def body(i, acc):
        c, m = _window(width, a.shape[0], starts[i], lens[i])
        sa = jax.lax.dynamic_slice(a, (c,), (width,))
        sp = jax.lax.dynamic_slice(p, (c,), (width,))
        return (acc + jnp.sum(m & (sa != (sp ^ LostWords.PATTERN)))
                - jnp.sum(m & (sa != sp)))

    return jax.lax.fori_loop(0, n, body, jnp.sum(a != p))


class _Span:
    def __init__(self, probe: Probe, name: str):
        self.probe, self.name = probe, name

    def __enter__(self):
        import jax
        self.ann = jax.profiler.TraceAnnotation(self.name)
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.ann.__exit__(*exc)
        self.probe.spans.append((self.name, self.t0, t1))
        return False
