"""What the selective scans of a hybrid LM's state-space layers have to do in
one training step: the recurrence ``h_t = exp(delta_t A) h_{t-1} + (delta_t
u_t) B_t^T``, ``y_t = h_t C_t + D u_t`` over ``scan_layers`` layers of
``d_inner`` channels and ``d_state`` states, forward once and backward once.

FLOPs, forward, per token: for each (channel, state) pair delta * A, its
exponential (one), a * h, (delta u) * B, their sum, h * C and its add into y:
seven; per channel delta * u and D * u with its add: three. The backward is
counted as twice the forward (``reference/phi4_mini_flash.py``
``SCAN_MACS_PER_STATE`` says the same). Every one of them is elementwise:
they run on the vector unit, whose peak ``peaks.json`` does not hold, so
against the MXU's peak they are next to nothing and the bound that comes out
is the memory's.

Bytes, each array once a pass at the narrowest float dtype it is moved in: u
and y (forward), u, dy and du (backward) in the activations' dtype; delta
(forward, backward) and d delta in float32, which is what the softplus hands
the scan; B and C and their gradients ``[tokens, d_state]`` float32, three
passes each; A and D with their gradients; and the chunk-boundary states the
program's schedule keeps (``shape["scan_kept_bytes"]`` a layer), written by
the forward and read by the backward. The forward a ``--remat`` run repeats is
recomputation and is not required, nor are the states a backward step forms
again inside its chunk, nor the copies into and out of the kernels' layout.
"""

ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2}
FLOPS_PER_STATE, FLOPS_PER_CHANNEL = 7, 3


def required_per_step(shape):
    tokens = shape["batch"] * shape["seq_len"]
    di, n, layers = shape["d_inner"], shape["d_state"], shape["scan_layers"]
    act = min(ITEMSIZE[t] for t in shape["activation_dtypes"])
    flops = 3 * tokens * di * (FLOPS_PER_STATE * n + FLOPS_PER_CHANNEL)
    nbytes = tokens * di * (5 * act + 3 * 4) \
        + 2 * 3 * tokens * n * 4 \
        + 3 * (di * n + di) * 4 \
        + 2 * shape["scan_kept_bytes"]
    return layers * flops, layers * nbytes
