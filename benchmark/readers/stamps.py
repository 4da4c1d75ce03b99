"""Reader over the harness's own step stamps (host clock).

``stat``: ``first_step_s`` (trainer build to the end of step 1), ``p90_ms`` of
the window's step durations, or ``host_ms``: the step as the end-to-end rate
times it (``harness.steady_step_s``) minus the device-busy time per step of
the traced steps, which is what the trainer's host loop adds to a step.
"""


def read(run, stat):
    if stat == "first_step_s":
        return run.first_step_s
    d = sorted(run.durations)
    if not d:
        return None
    if stat == "p90_ms":
        # nearest rank; the sample count goes on an earlier line
        run.say(f"STEPS {len(d)} step durations in the window, "
                f"{len(d) - int(0.9 * len(d))} at or beyond the p90")
        return 1e3 * d[min(len(d) - 1, int(0.9 * len(d)))]
    if stat == "host_ms":
        steady = run.steady()
        if not steady:
            return None
        chip, window, periods = steady
        return 1e3 * (run.step_s - chip.busy_s(window) / periods)
    raise ValueError(f"unknown stat {stat!r}")
