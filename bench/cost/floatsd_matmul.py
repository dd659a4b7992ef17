"""x [M, K] float32 @ FloatSD8 codes [K, N] -> [M, N] float32."""


def cost(m: int, k: int, n: int) -> tuple[int, int]:
    return 2 * m * k * n, 4 * m * k + k * n + 4 + 4 * m * n
