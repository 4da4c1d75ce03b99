"""What the routed experts' grouped matmuls have to do in one training step,
all layers.

A layer routes T = batch x seq_len tokens to ``top_k`` experts each: T*k rows.
Its SwiGLU experts are three grouped matmuls (gate and up: d -> f; down: f ->
d), each run in three passes: forward, the gradient to the rows (against the
transposed weights) and the gradient to the weights (per group rows^T x
dout). Every one of the nine is 2 * T*k * d * f FLOPs: the routed work only,
whatever the number of experts.

Bytes, in the dtype the kernel is fed (the widest activation dtype the run
found; the float32 parameters are cast to it): each of the nine touches its
expert weights once (E * d * f: read in the first two passes, written as the
gradient in the third) and its rows once (T*k * d on one side, T*k * f on the
other: read and written, or both read).

Left out, and so charged against the kernel's share where the kernel does it:
row tiles that straddle two groups and are read once per group, float32
accumulators. Left out because other ops do them: the SiLU and gate products
between the matmuls, the gathers and the scatter-add around them, the router,
and the optimizer's pass over the expert weights.
"""

ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2}


def required_per_step(shape):
    rows = shape["batch"] * shape["seq_len"] * shape["top_k"]
    d, f, e, n = (shape["d_model"], shape["ffn_dim"], shape["experts"],
                  shape["layers"])
    itemsize = max(ITEMSIZE[t] for t in shape["activation_dtypes"])
    calls = n * 3 * 3
    flops = calls * 2 * rows * d * f
    nbytes = calls * (e * d * f + rows * (d + f)) * itemsize
    return flops, nbytes
