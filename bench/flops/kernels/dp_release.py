"""The guard's fused release (``kernels/dp_release``) on ``[B, f]`` float32
rows: per-row sum of squares (2 flops an element), the clip scale (1) and
``x * scale + sigma * noise`` (3). It reads x and noise and writes the
release once each."""


def cost(*, rows: int, features: int) -> dict:
    elems = rows * features
    return {"flops": 6 * elems, "bytes": 3 * 4 * elems}
