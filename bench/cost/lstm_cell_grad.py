"""Backward of the LSTM cell, gates recomputed: reads z [B, 4H], c_prev,
dh, dc [B, H] (float32), writes dz [B, 4H] and dc_prev [B, H] (float32).
About 30 element-wise operations per hidden unit, counted as flops."""


def cost(b: int, h: int) -> tuple[int, int]:
    return 30 * b * h, 4 * (4 * b * h + 3 * b * h) + 4 * (4 * b * h + b * h)
