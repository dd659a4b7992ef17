"""Mean host-clock length of the engine's decode steps (``engine.step``
spans of kind decode, which end on the step's outputs read back)."""
import numpy as np

from bench.harness.spans import durations


def read(run):
    d = durations(run.layer.get("spans"), "engine.step", kind="decode")
    return 1e3 * float(np.mean(d)) if d else None
