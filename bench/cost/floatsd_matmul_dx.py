"""g [M, N] float32 @ decode(codes [K, N])^T -> dx [M, K] float32."""


def cost(m: int, k: int, n: int) -> tuple[int, int]:
    return 2 * m * k * n, 4 * m * n + k * n + 4 + 4 * m * k
