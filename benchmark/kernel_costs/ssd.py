"""What the recurrences of a model's Mamba-2 layers have to do in one training
step: ``h_t = exp(dt_t A) h_{t-1} + (dt_t x_t) B_t^T``, ``y_t = h_t C_t + D
x_t`` over ``ssd_layers`` layers of ``ssd_heads`` states of ``ssd_head_dim`` x
``ssd_state``, forward once and backward once.

FLOPs BY THE RECURRENCE, so that the count is the same whatever chunk size or
kernel computes it: forward, for each state element and token, the decay's
multiply and two multiply-adds (the rank-one update, ``h C``): five; the
backward is counted as twice the forward (``reference/nemotron3_nano_30b_a3b.py``
``recurrence_macs_per_token`` says the same, with the 1.5 multiply-adds a
feature of ``dt x`` and ``D x`` that are left out here: 0.002 of the count). A
chunked form does other arithmetic than this (the intra-chunk scores and their
product with the values, in place of a state a token) and does it as matmuls;
that is the implementation's choice and not required.

Bytes, each array once a pass at the narrowest float dtype it is moved in: x
and y a head, B and C a GROUP (the heads of a group share them; they are never
repeated in HBM), in the activations' dtype; dt float32 a head, which is what
the softplus hands the rule; the backward reads all four again and dY, and
writes the four gradients; and the states the program's schedule keeps
(``shape["ssd_kept_bytes"]`` a layer), written by the forward and read by the
backward. A, D and their gradients are a few hundred bytes. The forward a
``--remat`` run repeats is recomputation and is not required, nor is what a
backward forms again inside a chunk, nor dt's running sum, nor a copy into or
out of a kernel's layout.
"""

ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2}
FLOPS_PER_STATE = 5


def required_per_step(shape):
    tokens = shape["batch"] * shape["seq_len"]
    heads, p, n, groups, layers = (
        shape["ssd_heads"], shape["ssd_head_dim"], shape["ssd_state"],
        shape["ssd_groups"], shape["ssd_layers"])
    act = min(ITEMSIZE[t] for t in shape["activation_dtypes"])
    flops = 3 * tokens * heads * p * n * FLOPS_PER_STATE
    xbc = (heads * p + 2 * groups * n) * act    # x, B, C: their gradients too
    dt = heads * 4                              # dt: its gradient too
    out = heads * p * act                       # y; dY
    nbytes = tokens * ((xbc + dt + out)         # forward
                       + 2 * (xbc + dt) + out) \
        + 2 * shape["ssd_kept_bytes"]
    return layers * flops, layers * nbytes
