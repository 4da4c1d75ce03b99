"""What the routed experts' grouped matmuls have to do in one training step in
a model that holds a share of each layer's experts AND whose stack has layers
that do not route (``shape["moe_layers"]`` of ``shape["layers"]`` route; the
others are dense and call no grouped matmul).

Everything else is ``moe_grouped_matmul_held.py``'s count, which multiplies by
every layer and so reads a quarter too high where one layer of five is dense:
at balance T * k * held / E rows fall on the experts held; nine grouped matmuls
a routing layer (gate, up, down x forward, gradient to the rows, gradient to the
weights) of 2 * rows * d * f FLOPs each; bytes as they are moved: the rows in
the dtype the kernel is fed (rows * d on one side, rows * f on the other), the
held experts' weights in float32 (read in two passes, written as the float32
gradient in the third). The rows' dtype is the NARROWEST floating dtype the run
found among the activations (bfloat16 as the cell runs: the router's float32
logits are among the activations of every sparse model, and the older count,
which takes the widest, charges the rows at 4 bytes).

Left out as there: row tiles that straddle two groups, float32 accumulators,
the products between the matmuls, the gathers and the scatter-add, the router,
the shared expert and the dense layers (plain XLA matmuls:
``moe_shared_ms_per_step`` times the first), the optimizer's pass, and what
``--remat`` repeats. A shape without ``moe_layers`` routes in every layer.
"""

ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2}


def required_per_step(shape):
    held, e = shape["experts_held"], shape["experts"]
    rows = shape["batch"] * shape["seq_len"] * shape["top_k"] * held // e
    d, f = shape["d_model"], shape["ffn_dim"]
    n = shape.get("moe_layers", shape["layers"])
    itemsize = min(ITEMSIZE[t] for t in shape["activation_dtypes"]
                   if t in ITEMSIZE)
    calls = n * 3 * 3
    flops = calls * 2 * rows * d * f
    nbytes = calls * (held * d * f * 4 + rows * (d + f) * itemsize)
    return flops, nbytes
