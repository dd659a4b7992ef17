"""x [M, K]^T @ g [M, N] -> dW [K, N], all float32 (M = batch x time)."""


def cost(m: int, k: int, n: int) -> tuple[int, int]:
    return 2 * m * k * n, 4 * (m * k + m * n + k * n)
