"""Mean host-clock length of the engine's prefill steps (``engine.step``
spans of kind prefill, which end on the step's outputs read back)."""
import numpy as np

from bench.harness.spans import durations


def read(run):
    d = durations(run.layer.get("spans"), "engine.step", kind="prefill")
    return 1e3 * float(np.mean(d)) if d else None
