"""The client's fused privacy layer (``kernels/privacy_conv``): a 3x3 SAME
convolution, ReLU, 2x2 max-pool and Gaussian noise, float32. Operations are
the convolution's multiply-adds; bytes are the input, the weights, the noise
read and the pooled output written, once each."""


def cost(*, batch: int, h: int, w: int, cin: int, cout: int) -> dict:
    flops = 2 * batch * h * w * 9 * cin * cout
    pooled = batch * (h // 2) * (w // 2) * cout
    nbytes = 4 * (batch * h * w * cin + 9 * cin * cout + cout + 2 * pooled)
    return {"flops": flops, "bytes": nbytes}
