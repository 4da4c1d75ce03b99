"""What the routed experts' grouped matmuls have to do in one training step in
a model that HOLDS A SHARE of each layer's experts (``shape["experts_held"]`` of
``shape["experts"]``), whose experts have NO gate projection, and of whose
layers only ``shape["expert_layers"]`` route.

A layer routes T = batch x seq_len tokens to ``top_k`` of all the experts; at
balance T * k * held / E of those assignments fall on the experts held, and
only they are multiplied. The ungated experts are two grouped matmuls (up: d
-> f; down: f -> d), each run in three passes: forward, the gradient to the
rows (against the transposed weights) and the gradient to the weights. Every
one of the six is 2 * rows * d * f FLOPs. ``moe_grouped_matmul_held.py``
counts nine calls in every one of ``shape["layers"]`` layers: two thirds of
the truth a layer here, and nine layers for four.

Bytes, as they are really moved: the rows in the dtype the kernel is fed (the
widest activation dtype the run found; bfloat16 as the cell runs), rows * d on
one side and rows * f on the other, read and written or both read; the held
experts' weights in float32 (held * d * f * 4: read in the first two passes,
where a tile is cast in VMEM, written as the float32 gradient in the third).

Left out as there: row tiles that straddle two groups, float32 accumulators,
the activation between the matmuls, the gathers and the scatter-add, the
router, the optimizer's pass, and what ``--remat`` repeats. The held share is
taken at balance: a run whose block of experts draws more (``moe_held_share``
over held / E) does more than is counted here.
"""

ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2}


def required_per_step(shape):
    held, e = shape["experts_held"], shape["experts"]
    rows = shape["batch"] * shape["seq_len"] * shape["top_k"] * held // e
    d, f, n = shape["d_model"], shape["ffn_dim"], shape["expert_layers"]
    itemsize = max(ITEMSIZE[t] for t in shape["activation_dtypes"])
    calls = n * 2 * 3
    flops = calls * 2 * rows * d * f
    nbytes = calls * (held * d * f * 4 + rows * (d + f) * itemsize)
    return flops, nbytes
