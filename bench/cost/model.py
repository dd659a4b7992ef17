"""Model FLOPs of the LSTM language model per token (matmuls only; the
embedding lookup and the element-wise cell are not counted).

A token through the LSTM stack costs 2 * (in + H) * 4H per layer; a token
whose next-token logits are needed costs 2 * emb * vocab in the tied head.
Training costs three times the forward (forward, and the two products of
the backward); recomputation is not counted.
"""


def lstm_flops(cfg: dict) -> int:
    h = cfg["hidden"]
    return sum(2 * ((cfg["emb"] if i == 0 else h) + h) * 4 * h
               for i in range(cfg["n_layers"]))


def head_flops(cfg: dict) -> int:
    return 2 * cfg["emb"] * cfg["vocab"]


def train_flops_per_token(cfg: dict) -> int:
    return 3 * (lstm_flops(cfg) + head_flops(cfg))
