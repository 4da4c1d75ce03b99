"""Reader: JAX backend compilations (cache hits included) that the harness
saw between the end of warm-up and the end of the window. Must be 0."""


def read(run):
    return run.compiles_in_window
