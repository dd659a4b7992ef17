"""Mean over the traced pumps of the host's time in a ``router.pump``: the
pump's length less the ``engine.wait`` spans (the host waiting for the
device's step) inside it. Nothing where the ring holds no ``engine.wait``."""
import numpy as np

from bench.harness import span_clock


def read(run):
    events = run.layer.get("spans")
    pumps = span_clock.spans(events, "router.pump")
    waits = span_clock.spans(events, "engine.wait")
    if not pumps or not waits:
        return None
    host, j = [], 0
    for a, b, _ in pumps:
        while j < len(waits) and waits[j][0] < a:
            j += 1
        wait = 0
        while j < len(waits) and waits[j][1] <= b:
            wait += waits[j][1] - waits[j][0]
            j += 1
        host.append(b - a - wait)
    return float(np.mean(host)) / 1e3
