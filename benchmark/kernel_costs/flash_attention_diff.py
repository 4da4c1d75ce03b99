"""What differential flash attention under grouped-query heads has to do in
one training step, in every attention layer of a hybrid LM (``shape[
"windows"]``: one entry an attention layer, the keys a query sees, 0 for
every key before it).

Heads pair up: ``heads / 2`` query pairs, each two softmaxes over ``head_dim``
wide queries and keys and ONE value ``2 head_dim`` wide (the pair's two value
heads side by side). Per softmax the mask admits ``pairs(S, window)`` (query,
key) pairs (``flash_attention_gqa_causal.py``'s count). Forward requires the
scores (head_dim multiply-adds a pair) and the weighted sum (2 head_dim);
backward dV = P^T dO and dP = dO V^T (2 head_dim each), dQ = dS K and dK =
dS^T Q (head_dim each): 9 head_dim multiply-adds a pair and softmax, against
6 where values are as wide as keys. A system that runs each softmax once
against each half of the value (the published code's four calls) forms the
scores twice: that is its cost, not a requirement. The scores the backward
forms again and the forward a ``--remat`` run repeats are recomputation.

Bytes, in the dtype the kernels are fed: q read by both passes and dQ
written, the pair's output written, read again with dO: six tensors of
``heads x head_dim`` a token; K and V read by both passes and dK, dV
written: six of ``kv_heads x head_dim``, counted once a key/value head
(a cross layer reads another layer's K and V and still writes their
gradients); a float32 log-sum-exp a softmax and query, written once and read
twice.
"""

import os

import harness

_causal = harness.load_module(os.path.join(
    harness.HERE, "kernel_costs", "flash_attention_gqa_causal.py"))


def required_per_step(shape):
    b, s, h, h_kv, d = (shape["batch"], shape["seq_len"], shape["heads"],
                        shape["kv_heads"], shape["head_dim"])
    itemsize = max(_causal.ITEMSIZE[t] for t in shape["activation_dtypes"])
    flops = nbytes = 0
    for window in shape["windows"]:
        flops += 9 * b * h * _causal.pairs(s, window) * d * 2
        nbytes += 6 * b * (h + h_kv) * s * d * itemsize + 3 * b * h * s * 4
    return flops, nbytes
