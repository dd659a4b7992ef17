"""Share of the traced window in which no op ran on the device."""
from bench.harness import trace as T


def read(run):
    tr = run.result.get("trace")
    s = T.idle_share(tr) if tr else None
    return None if s is None else 100.0 * s
