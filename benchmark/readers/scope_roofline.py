"""Reader: a layer's share of its roofline by the program's own scope, in
percent.

The least time the chip could take for what one scope of the step has to do
(``kernel_costs/<cost>.py`` over ``peaks.json``, as ``readers/roofline.py``
reckons it) over the device time ``readers/device_scopes.py`` reads under the
scope ``scope`` in one step, every part of it (forward, backward, recomputed
forward). It reads the same work whether XLA ops or a Pallas call implement
it: the scope is where the program opened it, not a kernel's name. None
without a trace, at a commit whose program lacks the scope, or where nothing
ran under it."""


def read(run, scope, cost):
    ms = run.files.module("readers", "device_scopes.py").read(
        run, scope, "step_ms")
    if not ms:
        return None
    flops, nbytes = run.files.module("kernel_costs", cost + ".py") \
        .required_per_step(run.shape)
    t_flops = flops / run.peak["bf16_flops_per_s"]
    t_bytes = nbytes / run.peak["hbm_bytes_per_s"]
    run.say(f"ROOFLINE {cost}: {flops:.4g} FLOPs -> {t_flops * 1e3:.4f} ms, "
            f"{nbytes:.4g} bytes -> {t_bytes * 1e3:.4f} ms, bound by "
            f"{'compute' if t_flops >= t_bytes else 'memory'}; the scope "
            f"{scope} took {ms:.4f} ms a step")
    return 100.0 * max(t_flops, t_bytes) / (ms * 1e-3)
