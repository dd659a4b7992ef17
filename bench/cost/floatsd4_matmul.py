"""x [M, K] float32 @ FloatSD4 [K, N] -> [M, N] float32; the weight streams
ceil(K/2)*N code bytes and ceil(K/32)*N exponent bytes."""


def cost(m: int, k: int, n: int) -> tuple[int, int]:
    codes = -(-k // 2) * n + -(-k // 32) * n
    return 2 * m * k * n, 4 * m * k + codes + 4 * m * n
