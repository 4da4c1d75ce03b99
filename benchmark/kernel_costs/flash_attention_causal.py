"""What causal flash attention has to do in one training step, all layers.

Per (batch, head) a causal S x S x head_dim product is S^2/2 * head_dim
multiply-adds, 2 FLOPs each. Forward requires two of them (the scores and the
weighted sum), backward four (dV = P^T dO, dP = dO V^T, dQ = dS K, dK = dS^T
Q). The scores the backward kernels form again are recomputation and are not
required. Bytes: forward reads q, k, v and writes o once; backward reads q,
k, v, o, do and writes dq, dk, dv once — in the dtype the kernel is fed (the
widest activation dtype the run found) — plus the float32 log-sum-exp, written
once and read twice.
"""

ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2}


def required_per_step(shape):
    b, s, h, d, n = (shape["batch"], shape["seq_len"], shape["heads"],
                     shape["head_dim"], shape["layers"])
    itemsize = max(ITEMSIZE[t] for t in shape["activation_dtypes"])
    pair = b * h * (s * s / 2) * d * 2
    flops = n * 6 * pair
    tensor = b * h * s * d * itemsize
    lse = b * h * s * 4
    nbytes = n * (12 * tensor + 3 * lse)
    return flops, nbytes
