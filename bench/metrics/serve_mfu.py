"""The served model FLOPs of the window over the window and the chip's
bf16 peak: tokens through the LSTM stack and tokens whose logits were
needed, from the engine's counters (``bench.cost.model``)."""
from bench.cost.model import head_flops, lstm_flops


def read(run):
    t = run.layer.get("window_tokens")
    if not t or not run.peaks:
        return None
    flops = t["through_lstm"] * lstm_flops(run.cfg) + t["emitted"] * head_flops(run.cfg)
    return 100.0 * flops / run.layer["window_s"] / run.peaks["bf16_flops"]
