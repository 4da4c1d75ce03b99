"""What the chunk summaries of a model's EVA layers have to move in one
training step: ``alpha = softmax_chunk(s k . phi)``, ``ks = sum alpha k + mu``,
``vs = sum alpha v``, forward once and backward once.

Bytes alone (its arithmetic is a few operations an element: the bound is the
memory's), each array once a pass in the dtype the op is fed (the widest
activation dtype the run found): forward reads k and v and writes the
summaries (1 / chunk of them); backward reads k, v and the summaries'
gradients and ADDS into dK and dV, which it therefore reads and writes. phi,
mu and their gradients are a few kilobytes. The forward a ``--remat`` run
repeats is recomputation and is not required.
"""

ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2}


def required_per_step(shape):
    b, s, h, d, layers = (shape["batch"], shape["seq_len"], shape["heads"],
                          shape["head_dim"], shape["layers"])
    itemsize = max(ITEMSIZE[t] for t in shape["activation_dtypes"])
    rows = b * h * s * d * itemsize                 # one of k, v, dK, dV
    sums = rows // shape["eva_chunk"]               # one of ks, vs, their gradients
    nbytes = (2 * rows + 2 * sums) + (6 * rows + 2 * sums)
    return 0, layers * nbytes
