"""The program's own spans (``repro.obs.trace`` ring, host monotonic clock
in microseconds) mapped onto the profiler's clock (nanoseconds).

The benchmark's ``bench.pump`` annotations (``trace["host"]``, profiler
clock) and the ring's ``router.pump`` spans (tracer clock) time the same
pumps, one inside the other, in the same order. ``fit`` pairs them, last
with last (a ring that wrapped lost its oldest pumps), and fits
``profiler_ns = a + b * tracer_ns`` to the midpoints by least squares. It
gives nothing if fewer than ``MIN_PAIRS`` pairs match or the 99th
percentile of the residuals exceeds ``MAX_RESIDUAL_NS``: the pairs do not
time the same pumps, or the clocks do not run together.
"""
from __future__ import annotations

import numpy as np

MIN_PAIRS = 100
MAX_RESIDUAL_NS = 50_000
PUMP_HOST = "bench.pump"
PUMP_RING = "router.pump"


def spans(events, name: str) -> list:
    """[(start_us, end_us, args)] of the completed B/E spans called
    ``name``, in order of start; ``args`` merges the B and E events'."""
    open_, out = {}, []
    for ev in events or []:
        if ev.get("name") != name:
            continue
        tid = ev.get("tid")
        if ev["ph"] == "B":
            open_.setdefault(tid, []).append(ev)
        elif ev["ph"] == "E" and open_.get(tid):
            b = open_[tid].pop()
            out.append((b["ts"], ev["ts"], {**b.get("args", {}), **ev.get("args", {})}))
    out.sort(key=lambda s: s[0])
    return out


def all_spans(events) -> list:
    """[(name, start_us, end_us, args)] of every completed span in the
    ring: B/E pairs and complete (X) events."""
    names = {ev["name"] for ev in events or [] if ev.get("ph") == "B"}
    out = [(n, a, b, args) for n in names for a, b, args in spans(events, n)]
    out += [(ev["name"], ev["ts"], ev["ts"] + ev["dur"], ev.get("args", {}))
            for ev in events or [] if ev.get("ph") == "X"]
    out.sort(key=lambda s: s[1])
    return out


def fit(host, events) -> dict | None:
    """The clock map, or None. Keys: ``a``, ``b`` (profiler ns = a + b *
    tracer ns), ``pairs``, ``residual_p99_ns``; ``to_profiler`` uses the
    centred form (``x0``, ``y0``, ``c``), which keeps float precision."""
    bench = sorted((s, s + d) for n, s, d in host or [] if n == PUMP_HOST)
    ring = [(a, b) for a, b, _ in spans(events, PUMP_RING)]
    n = min(len(bench), len(ring))
    if n < MIN_PAIRS:
        return None
    bench, ring = bench[-n:], ring[-n:]
    x0, y0 = 1000 * ring[0][0], bench[0][0]
    # midpoints, relative to the first pair: exact integers before the cast
    x = np.array([(1000 * (a + b) // 2) - x0 for a, b in ring], np.float64)
    y = np.array([(a + b) // 2 - y0 for a, b in bench], np.float64)
    xm, ym = x.mean(), y.mean()
    var = float(((x - xm) ** 2).sum())
    if var == 0:
        return None
    b = float(((x - xm) * (y - ym)).sum()) / var
    c = ym - b * xm
    resid = np.abs(y - (c + b * x))
    p99 = float(np.percentile(resid, 99))
    if p99 > MAX_RESIDUAL_NS:
        return None
    return {"a": y0 + c - b * x0, "b": b, "pairs": n, "residual_p99_ns": p99,
            "x0": x0, "y0": y0, "c": c}


def to_profiler(clock: dict, ts_us) -> float:
    """A tracer timestamp (us) on the profiler's clock (ns)."""
    return clock["y0"] + clock["c"] + clock["b"] * (1000 * ts_us - clock["x0"])


def mapped(clock: dict, events, name: str) -> list:
    """[(start_ns, end_ns, args)] of the spans called ``name``, on the
    profiler's clock."""
    return [(to_profiler(clock, a), to_profiler(clock, b), args)
            for a, b, args in spans(events, name)]


def idle_within(intervals, busy) -> float:
    """Nanoseconds of the sorted, disjoint ``intervals`` ([start, end])
    not covered by the sorted, disjoint ``busy`` intervals."""
    idle, j = 0.0, 0
    for a, b in intervals:
        covered = 0.0
        while j < len(busy) and busy[j][1] <= a:
            j += 1
        k = j
        while k < len(busy) and busy[k][0] < b:
            covered += min(b, busy[k][1]) - max(a, busy[k][0])
            k += 1
        idle += (b - a) - covered
    return idle
