"""What the cores of a model's EVA layers have to do in one training step: for
each head, ONE softmax over the tokens of the query's window up to itself and
the chunk summaries of every earlier window.

FLOPs BY THE LIVE PAIRS, so that the count is the same whatever tile or kernel
computes it: a (query, key) or (query, summary) pair the mask admits costs a
score and a weighted sum forward (2 products of ``head_dim`` multiply-adds: 4
``head_dim`` FLOPs) and four products backward (dV = P^T dO, dP = dO V^T, dQ =
dS K, dK = dS^T Q: 8 ``head_dim``). The scores the backward forms again are
recomputation and are not required, nor is the forward a ``--remat`` run
repeats. ``reference/evabyte_6_5b.py:live_pairs`` counts the same pairs.

Bytes, each array once a pass in the dtype the kernel is fed (the widest
activation dtype the run found): forward reads q, k, v and the summaries (ks,
vs: 1 / chunk of k and v) and writes o; backward reads q, k, v, ks, vs, o, dO
and writes dQ, dK, dV and the summaries' gradients; plus the float32
log-sum-exp a query, written once and read once. The copies into and out of
the kernels' layout are not required.
"""

ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2}


def pairs(s, window, chunk):
    """(query, key) and (query, summary) pairs one head's mask admits."""
    total, start, w = 0, 0, 0
    while start < s:
        n = min(window, s - start)
        total += n * (n + 1) // 2 + n * w * (window // chunk)
        start, w = start + n, w + 1
    return total


def required_per_step(shape):
    b, s, h, d, layers = (shape["batch"], shape["seq_len"], shape["heads"],
                          shape["head_dim"], shape["layers"])
    window, chunk = shape["eva_window"], shape["eva_chunk"]
    itemsize = max(ITEMSIZE[t] for t in shape["activation_dtypes"])
    flops = 12 * d * b * h * pairs(s, window, chunk)
    rows = b * h * s * d * itemsize                 # one of q, k, v, o, ...
    sums = rows // chunk                            # one of ks, vs, ...
    nbytes = (4 * rows + 2 * sums) + (10 * rows + 4 * sums) + 2 * b * h * s * 4
    return layers * flops, layers * nbytes
