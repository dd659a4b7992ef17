"""Roofline share of a kernel in the traced part of a run: the least time
the chip could take for the calls the traced steps made (``bench.cost``),
over the time the trace shows that kernel's events taking."""
from __future__ import annotations

from bench.cost import floor_seconds
from bench.harness import trace as T


def share(run, pattern: str, calls) -> float | None:
    """``calls``: [(count, (flops, bytes)), ...] per device for the traced
    steps. Percent, or None where the trace shows no such kernel."""
    tr = run.result.get("trace")
    if not tr or not tr["devices"]:
        return None
    secs, n = T.op_seconds(tr, pattern)
    if secs <= 0:
        return None
    floor = sum(c * floor_seconds(f, b, run.peaks)[0] for c, (f, b) in calls)
    return 100.0 * floor / secs


def serve_steps(run):
    """(prefill steps, decode steps) inside the trace, or None."""
    st = getattr(run, "layer", {}).get("traced_steps")
    if not st:
        return None
    return st["prefill"], st["decode"]
