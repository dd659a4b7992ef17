"""Share of the traced window in which no op ran on the device while the
host was inside a ``router.pump``: the idle the program's own host work
causes, as opposed to waiting for arrivals. The ring's pump spans are put
on the profiler's clock by ``span_clock``; nothing where it finds no fit."""
from bench.harness import span_clock
from bench.harness import trace as T


def read(run):
    tr = run.result.get("trace")
    events = run.layer.get("spans")
    if not tr or not tr["devices"] or not events:
        return None
    clock = span_clock.fit(tr["host"], events)
    if clock is None:
        return None
    t0, t1 = tr["window"]
    pumps = [(max(a, t0), min(b, t1))
             for a, b, _ in span_clock.mapped(clock, events, span_clock.PUMP_RING)
             if b > t0 and a < t1]
    devs = list(tr["devices"].values())
    idle = sum(span_clock.idle_within(pumps, T.busy_intervals(ev, t0, t1))
               for ev in devs)
    return 100.0 * idle / len(devs) / (t1 - t0)
