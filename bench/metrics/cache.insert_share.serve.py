"""Share of the traced window spent in prefix-cache inserts: the summed
``cache.insert`` spans of every site (``kind`` block, prompt or retire;
each covers the lane's extract to the host and the FP8 store). Nothing
where no span names its kind."""
from bench.harness import span_clock


def read(run):
    window = run.layer.get("window_s")
    ins = [(a, b) for a, b, args in span_clock.spans(run.layer.get("spans"), "cache.insert")
           if "kind" in args]
    if not ins or not window:
        return None
    return 100.0 * sum(b - a for a, b in ins) / 1e6 / window
