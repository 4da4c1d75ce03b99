"""What causal flash attention under grouped-query heads has to do in one
training step, in the layers WITHOUT a window (``shape["windows"][l] == 0``;
the window layers are ``flash_attention_gqa_window.py``'s).

Per (batch, query head) the causal mask admits S (S + 1) / 2 (query, key)
pairs; a product over them is that many x head_dim multiply-adds, 2 FLOPs
each. Forward requires two products (the scores and the weighted sum),
backward four (dV = P^T dO, dP = dO V^T, dQ = dS K, dK = dS^T Q). The scores
the backward kernel forms again are recomputation and are not required, nor
is the forward a ``--remat`` run repeats.

Bytes, in the dtype the kernel is fed (the widest activation dtype the run
found): forward reads q and writes o, backward reads q, o, dO and writes dQ —
six tensors of ``heads``; forward reads k, v, backward reads them again and
writes dK, dV — six tensors of ``kv_heads``: K and V are counted once a
key/value head, not once a query head (the kernels address them by ``head //
group`` and never repeat them in HBM). Plus the float32 log-sum-exp a query
head, written once and read twice.
"""

ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2}


def pairs(s, window):
    """(query, key) pairs one head's mask admits."""
    w = min(window, s) if window else s
    return w * (w + 1) // 2 + (s - w) * w


def required(shape, window_layers: bool):
    b, s, h, h_kv, d = (shape["batch"], shape["seq_len"], shape["heads"],
                        shape["kv_heads"], shape["head_dim"])
    itemsize = max(ITEMSIZE[t] for t in shape["activation_dtypes"])
    flops = nbytes = 0
    for window in shape["windows"]:
        if bool(window) != window_layers:
            continue
        flops += 6 * b * h * pairs(s, window) * d * 2
        nbytes += 6 * b * (h + h_kv) * s * d * itemsize + 3 * b * h * s * 4
    return flops, nbytes


def required_per_step(shape):
    return required(shape, window_layers=False)
