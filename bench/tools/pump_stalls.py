"""Run one serving cell with --trace 1 and list its longest pumps: each
pump's length, the device-idle time inside it, and the innermost program
span (``runtime.gc``, ``cache.evict``, ``cache.insert``,
``runtime.compile``, ...) that covers the longest idle stretch in it. The
program's ring is put on the profiler's clock by ``span_clock``; its fit
(pairs matched, residual p99) is printed too.

    python3 bench/tools/pump_stalls.py <cell> <seed> <seconds> [<top>]

Prints the result line, then one JSON line of the analysis, with the
traced run's end-to-end numbers, which a traced result line leaves out.
"""
import json
import os
import sys
import time

T0 = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.harness import span_clock  # noqa: E402
from bench.harness import trace as T  # noqa: E402


def idle_stretches(a, b, busy):
    """The [start, end] stretches of [a, b] that no busy interval covers."""
    out, t = [], a
    for s, e in busy:
        if e <= a:
            continue
        if s >= b:
            break
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < b:
        out.append((t, b))
    return out


def analyse(trace: dict, events: list, top: int = 5) -> dict:
    """The ``top`` longest pumps of a traced run, with what covers their
    longest idle stretch; a summary of the GC and compile spans."""
    clock = span_clock.fit(trace["host"], events)
    if clock is None:
        return {"fit": None}
    t0, t1 = trace["window"]
    devs = list(trace["devices"].values())
    busy = T.busy_intervals(devs[0], t0, t1) if devs else []
    spans = [(n, span_clock.to_profiler(clock, a), span_clock.to_profiler(clock, b), args)
             for n, a, b, args in span_clock.all_spans(events)]
    pumps = sorted((s for s in spans if s[0] == span_clock.PUMP_RING),
                   key=lambda s: s[1] - s[2])[:top]
    rows = []
    for _, a, b, _ in pumps:
        gaps = idle_stretches(a, b, busy)
        idle = sum(e - s for s, e in gaps)
        row = {"at_s": (a - t0) / 1e9, "pump_ms": (b - a) / 1e6, "idle_ms": idle / 1e6}
        if gaps:
            s, e = max(gaps, key=lambda g: g[1] - g[0])
            mid = (s + e) / 2
            cover = [x for x in spans if x[1] <= mid <= x[2]]
            inner = min(cover, key=lambda x: x[2] - x[1])
            row.update(longest_idle_ms=(e - s) / 1e6, covered_by=inner[0],
                       covered_args=inner[3], covered_ms=(inner[2] - inner[1]) / 1e6)
        inside = {}
        for n, sa, sb, _ in spans:
            if a <= sa and sb <= b and n != span_clock.PUMP_RING:
                inside[n] = inside.get(n, 0.0) + (sb - sa) / 1e6
        row["spans_ms"] = dict(sorted(inside.items(), key=lambda kv: -kv[1]))
        rows.append(row)
    gcs = [(args.get("generation"), (b - a) / 1e6) for n, a, b, args in spans
           if n == "runtime.gc"]
    by_gen = {}
    for g, ms in gcs:
        n, tot, mx = by_gen.get(g, (0, 0.0, 0.0))
        by_gen[g] = (n + 1, tot + ms, max(mx, ms))
    compiles = [(args.get("fun"), (b - a) / 1e6) for n, a, b, args in spans
                if n == "runtime.compile"]
    return {
        "fit": {k: clock[k] for k in ("pairs", "residual_p99_ns", "b")},
        "pumps": rows,
        "gc": {str(g): {"count": n, "total_ms": tot, "max_ms": mx}
               for g, (n, tot, mx) in sorted(by_gen.items())},
        "compiles": compiles,
        "evictions": sum(1 for s in spans if s[0] == "cache.evict"),
    }


def main():
    import bench.run as R

    cell, seed, secs = sys.argv[1], sys.argv[2], sys.argv[3]
    top = int(sys.argv[4]) if len(sys.argv) > 4 else 5
    _, run = R.run_cell(["--workload", cell, "--seed", seed, "--seconds", secs,
                         "--trace", "1"], {"t_start": T0})
    tr = run.result.get("trace")
    out = analyse(tr, run.layer.get("spans") or [], top) if tr else {"fit": None}
    out.update(cell=cell, seed=int(seed), e2e=run.result.get("e2e"),
               trace_events_dropped=run.result["extra"].get("trace_events_dropped"))
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
