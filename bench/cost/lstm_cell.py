"""Element-wise LSTM cell on [B, 4H] gate pre-activations (float32) and the
float16 cell state: reads z and c, writes h (float32) and c (float16).
About 12 element-wise operations per hidden unit, counted as flops."""


def cost(b: int, h: int) -> tuple[int, int]:
    return 12 * b * h, 4 * b * 4 * h + 2 * b * h + 4 * b * h + 2 * b * h
