"""Reader: a kernel's share of its roofline, in percent.

The least time the chip could take for the kernel's calls in one step — the
larger of required FLOPs over the peak and required bytes over the memory
bandwidth, both from ``kernel_costs/<cost>.py`` and ``peaks.json`` — over the
device time the trace shows for the ops matching ``pattern`` in one step.
Says on an earlier line which of the two bounds it."""


def read(run, pattern, cost):
    steady = run.steady()
    if not steady:
        return None
    chip, window, periods = steady
    t = chip.matching_seconds(pattern, window) / periods
    if t <= 0:
        return None
    flops, nbytes = run.files.module("kernel_costs", cost + ".py") \
        .required_per_step(run.shape)
    t_flops = flops / run.peak["bf16_flops_per_s"]
    t_bytes = nbytes / run.peak["hbm_bytes_per_s"]
    run.say(f"ROOFLINE {cost}: {flops:.4g} FLOPs -> {t_flops * 1e3:.4f} ms, "
            f"{nbytes:.4g} bytes -> {t_bytes * 1e3:.4f} ms, bound by "
            f"{'compute' if t_flops >= t_bytes else 'memory'}; kernels took "
            f"{t * 1e3:.4f} ms a step")
    return 100.0 * max(t_flops, t_bytes) / t
