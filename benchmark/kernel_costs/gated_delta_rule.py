"""What the gated delta rules of a model's linear-attention layers have to do
in one training step: ``S <- exp(g_t) S``, ``S <- S + k_t (beta_t (v_t - S^T
k_t))^T``, ``o_t = S^T q_t`` over ``gdn_layers`` layers of ``gdn_value_heads``
states of ``gdn_key_dim`` x ``gdn_value_dim``, forward once and backward once.

FLOPs BY THE RECURRENCE, so that the count is the same whatever chunk size or
kernel computes it: forward, for each state element and token, the decay's
multiply and three multiply-adds (``S^T k``, the rank-one update, ``S^T q``):
seven; the backward is counted as twice the forward
(``reference/qwen3_next_80b_a3b.py`` ``recurrence_macs_per_token`` says the
same). A chunked form does more arithmetic than this (the chunk's triangular
system, ``U`` and ``W``, the intra-chunk scores: about as much again at chunks
of 64) and does it as matmuls; that is the implementation's choice and not
required, so a share of this roofline reads low by construction wherever a
chunked form runs.

Bytes, each array once a pass at the narrowest float dtype it is moved in: q
and k a KEY head (the value heads of a key head share them; they are never
repeated in HBM), v and o a value head, in the activations' dtype; g and beta
float32 a value head, which is what the softplus and the sigmoid hand the
rule; the backward reads all five again and dO, and writes the five
gradients; and the states the program's schedule keeps
(``shape["gdn_kept_bytes"]`` a layer), written by the forward and read by the
backward. The forward a ``--remat`` run repeats is recomputation and is not
required, nor is what a backward forms again inside a chunk, nor the copies
into and out of the kernels' layout.
"""

ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2}
FLOPS_PER_STATE = 7


def required_per_step(shape):
    tokens = shape["batch"] * shape["seq_len"]
    hk, hv = shape["gdn_key_heads"], shape["gdn_value_heads"]
    dk, dv, layers = (shape["gdn_key_dim"], shape["gdn_value_dim"],
                      shape["gdn_layers"])
    act = min(ITEMSIZE[t] for t in shape["activation_dtypes"])
    flops = 3 * tokens * hv * dk * dv * FLOPS_PER_STATE
    qkv = (2 * hk * dk + hv * dv) * act         # q, k, v: their gradients too
    gates = 2 * hv * 4                          # g, beta: their gradients too
    out = hv * dv * act                         # o; dO
    nbytes = tokens * ((qkv + gates + out)      # forward
                       + 2 * (qkv + gates) + out) \
        + 2 * shape["gdn_kept_bytes"]
    return layers * flops, layers * nbytes
