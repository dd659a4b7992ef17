"""Bounded ring-buffer span tracer with Chrome trace-event export.

Stdlib-only on purpose: the serving hot path (engine worker thread, the
asyncio pump, the HTTP handlers) imports this module, so it must never
pull jax — telemetry code that needs jnp lives in ``obs.telemetry``.

Design points:

  * **Bounded**: events land in a ``collections.deque(maxlen=capacity)``;
    when the ring wraps, the oldest events are dropped (and counted in
    ``dropped``). A long-lived server can leave tracing on forever and the
    buffer stays O(capacity).
  * **Thread/async-safe**: one ``threading.Lock`` guards the ring and the
    aggregate table. Events record ``threading.get_ident()`` as their
    ``tid``, so spans emitted concurrently from the engine worker thread
    and the asyncio event loop land on separate tracks and never pair
    against each other.
  * **Monotonic clock**: timestamps are ``time.monotonic_ns() // 1000``
    (microseconds) — the unit Chrome trace-event JSON expects — so traces
    are immune to wall-clock steps.
  * **~zero cost when disabled**: every emitting entry point checks
    ``self._enabled`` first and returns a cached no-op context manager, so
    a disabled tracer costs one attribute load, one branch, and whatever
    the caller spent building kwargs (callers on hot paths guard arg
    construction with ``TRACER.enabled``). See tests/test_obs.py for the
    measured bound.
  * **Export-time sanitization**: ``chrome_trace()`` drops orphan ``E``
    events (whose ``B`` was evicted by the ring) and unterminated ``B``
    events (spans still open at export), so every exported trace has
    matched B/E pairs and loads cleanly in Perfetto / chrome://tracing.
  * **Profiler bridge**: ``annotate`` is an optional callable returning a
    context manager (``obs.profiler.install`` sets it to
    ``jax.profiler.TraceAnnotation``). While the tracer is enabled every
    span also enters ``annotate(name)`` around its body, so a profiler
    capture shows the span on the same host timeline as the device's ops;
    a ``complete()`` event, whose start has passed, is marked there by a
    zero-length annotation at its end. Disabled, the hook is never called.
  * **GC spans**: the collector is process-wide, so one tracer records
    it: with ``gc_spans=True`` (the shared ``TRACER``), ``enable()``
    registers a ``gc.callbacks`` hook that records every collection as a
    ``runtime.gc`` span (``generation``; ``collected`` on its end), and
    ``disable()`` removes it. The lock is re-entrant because a collection
    can start inside the tracer's own critical section.

A module-level ``TRACER`` is the instance the whole stack shares; the
``REPRO_TRACE=1`` environment variable enables it at import time.
"""
from __future__ import annotations

import collections
import gc
import os
import threading
import time
import weakref

__all__ = ["Tracer", "TRACER", "span", "instant", "counter"]

_DEFAULT_CAPACITY = 65536


class _NullSpan:
    """Reused no-op context manager: the disabled-tracer fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def note(self, **args) -> None:
        pass


_NULL_SPAN = _NullSpan()


class _Span:
    """Live span: emits a matched B/E pair and feeds the aggregate table."""

    __slots__ = ("_tr", "_name", "_cat", "_args", "_t0", "_end_args", "_ann")

    def __init__(self, tr: "Tracer", name: str, cat: str, args: dict):
        self._tr = tr
        self._name = name
        self._cat = cat
        self._args = args
        self._end_args = None
        self._ann = None

    def note(self, **args) -> None:
        """Args known only inside the body (bytes stored, items handled):
        they ride on the span's E event, which trace viewers merge with
        the B event's args."""
        if self._end_args is None:
            self._end_args = args
        else:
            self._end_args.update(args)

    def __enter__(self):
        annotate = self._tr.annotate
        if annotate is not None:
            self._ann = annotate(self._name)
            self._ann.__enter__()
        self._t0 = time.monotonic_ns() // 1000
        self._tr._push(
            {
                "name": self._name,
                "cat": self._cat,
                "ph": "B",
                "ts": self._t0,
                "pid": self._tr.pid,
                "tid": threading.get_ident(),
                "args": self._args,
            }
        )
        return self

    def __exit__(self, *exc):
        t1 = time.monotonic_ns() // 1000
        tr = self._tr
        ev = {
            "name": self._name,
            "cat": self._cat,
            "ph": "E",
            "ts": t1,
            "pid": tr.pid,
            "tid": threading.get_ident(),
        }
        if self._end_args is not None:
            ev["args"] = self._end_args
        tr._push(ev)
        with tr._lock:
            cnt, tot = tr._agg.get(self._name, (0, 0))
            tr._agg[self._name] = (cnt + 1, tot + (t1 - self._t0))
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        return False


def _gc_hook(ref):
    """A ``gc.callbacks`` entry recording collections on the tracer
    ``ref`` points to. It holds the tracer weakly, so a tracer dropped
    while enabled leaves a hook that does nothing."""
    state = {"span": None}

    def hook(phase, info):
        tr = ref()
        if tr is None or not tr._enabled:
            return
        if phase == "start":
            sp = _Span(tr, "runtime.gc", "runtime",
                       {"generation": info["generation"]})
            sp.__enter__()
            state["span"] = sp
        else:
            sp, state["span"] = state["span"], None
            if sp is not None:
                sp.note(collected=info["collected"])
                sp.__exit__(None, None, None)

    return hook


class Tracer:
    """Thread-safe bounded tracer. See module docstring for semantics."""

    def __init__(
        self, capacity: int = _DEFAULT_CAPACITY, pid: int = 0,
        gc_spans: bool = False,
    ):
        if capacity < 2:
            raise ValueError("capacity must hold at least one B/E pair")
        self.capacity = capacity
        self.pid = pid
        self._events: collections.deque = collections.deque(maxlen=capacity)
        # re-entrant: the GC hook pushes from inside whatever allocation
        # triggered the collection, which may be under this lock
        self._lock = threading.RLock()
        self._enabled = False
        # profiler bridge: name -> context manager, entered around spans
        self.annotate = None
        self.gc_spans = gc_spans
        self._gc_hook = None
        self.emitted = 0  # total events pushed since last clear()
        self.dropped = 0  # ... of which the ring evicted
        # per-span-name aggregates survive ring eviction: name -> (count,
        # total duration in us). Powers /metrics span totals.
        self._agg: dict = {}

    # -- switches --------------------------------------------------------
    @property
    def enabled(self) -> bool:
        return self._enabled

    def enable(self) -> None:
        self._enabled = True
        if self.gc_spans and self._gc_hook is None:
            self._gc_hook = _gc_hook(weakref.ref(self))
            gc.callbacks.append(self._gc_hook)

    def disable(self) -> None:
        self._enabled = False
        if self._gc_hook is not None:
            gc.callbacks.remove(self._gc_hook)
            self._gc_hook = None

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._agg = {}
            self.emitted = 0
            self.dropped = 0

    # -- emission --------------------------------------------------------
    def _push(self, ev: dict) -> None:
        with self._lock:
            if len(self._events) == self.capacity:
                self.dropped += 1
            self._events.append(ev)
            self.emitted += 1

    def span(self, name: str, cat: str = "repro", **args):
        """Context manager emitting a matched B/E pair around the body."""
        if not self._enabled:
            return _NULL_SPAN
        return _Span(self, name, cat, args)

    def complete(
        self, name: str, ts_us: int, dur_us: int, cat: str = "repro", **args
    ) -> None:
        """Retroactive complete event (``ph: "X"``) for scopes that await:
        a ``span()`` on the asyncio event loop would interleave its B/E
        with other coroutines on the same thread and break nesting, so
        async scopes take a start stamp (``time.monotonic_ns() // 1000``)
        and emit one X event with an explicit duration at completion —
        X events need no pairing and tolerate same-tid overlap. With a
        profiler bridge, a zero-length annotation marks its end there."""
        if not self._enabled:
            return
        if self.annotate is not None:
            with self.annotate(name):
                pass
        self._push(
            {
                "name": name,
                "cat": cat,
                "ph": "X",
                "ts": ts_us,
                "dur": max(int(dur_us), 0),
                "pid": self.pid,
                "tid": threading.get_ident(),
                "args": args,
            }
        )
        with self._lock:
            cnt, tot = self._agg.get(name, (0, 0))
            self._agg[name] = (cnt + 1, tot + max(int(dur_us), 0))

    def instant(self, name: str, cat: str = "repro", **args) -> None:
        """Point event (``ph: "i"``) — admissions, retires, flushes."""
        if not self._enabled:
            return
        self._push(
            {
                "name": name,
                "cat": cat,
                "ph": "i",
                "s": "t",  # thread-scoped instant
                "ts": time.monotonic_ns() // 1000,
                "pid": self.pid,
                "tid": threading.get_ident(),
                "args": args,
            }
        )
        with self._lock:
            cnt, tot = self._agg.get(name, (0, 0))
            self._agg[name] = (cnt + 1, tot)

    def counter(self, name: str, cat: str = "repro", **values) -> None:
        """Counter-track sample (``ph: "C"``) — queue depth over time."""
        if not self._enabled:
            return
        self._push(
            {
                "name": name,
                "cat": cat,
                "ph": "C",
                "ts": time.monotonic_ns() // 1000,
                "pid": self.pid,
                "tid": threading.get_ident(),
                "args": values,
            }
        )

    # -- export / introspection -----------------------------------------
    def events(self) -> list:
        """Raw snapshot of the ring (unsanitized), oldest first."""
        with self._lock:
            return list(self._events)

    def chrome_trace(self) -> dict:
        """Chrome trace-event JSON document, sanitized so every B has a
        matching E on the same tid (ring eviction can orphan either end;
        see module docstring)."""
        events = self.events()
        # X events are pushed at completion but stamped with their start
        # ts; a stable sort restores global ts order (ties keep push
        # order, so a B still precedes its same-microsecond E).
        events.sort(key=lambda e: e["ts"])
        keep = [True] * len(events)
        open_b: dict = {}  # tid -> stack of indices of open B events
        for i, ev in enumerate(events):
            ph = ev["ph"]
            if ph == "B":
                open_b.setdefault(ev["tid"], []).append(i)
            elif ph == "E":
                stack = open_b.get(ev["tid"])
                if stack:
                    stack.pop()
                else:
                    keep[i] = False  # orphan E: its B was evicted
        for stack in open_b.values():
            for i in stack:
                keep[i] = False  # span still open at export time
        return {
            "traceEvents": [ev for i, ev in enumerate(events) if keep[i]],
            "displayTimeUnit": "ms",
        }

    def stats(self) -> dict:
        """Aggregates for /metrics: totals plus per-span-name counts and
        cumulative durations (seconds). Cheap; safe to call while tracing."""
        with self._lock:
            agg = dict(self._agg)
            return {
                "enabled": self._enabled,
                "emitted": self.emitted,
                "dropped": self.dropped,
                "buffered": len(self._events),
                "spans": {
                    name: {"count": cnt, "total_s": tot / 1e6}
                    for name, (cnt, tot) in sorted(agg.items())
                },
            }


#: Process-wide tracer shared by every layer of the stack.
TRACER = Tracer(gc_spans=True)

if os.environ.get("REPRO_TRACE", "") not in ("", "0"):
    TRACER.enable()


def span(name: str, cat: str = "repro", **args):
    return TRACER.span(name, cat, **args)


def instant(name: str, cat: str = "repro", **args) -> None:
    TRACER.instant(name, cat, **args)


def counter(name: str, cat: str = "repro", **values) -> None:
    TRACER.counter(name, cat, **values)
