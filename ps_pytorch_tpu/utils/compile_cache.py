"""Where the persistent XLA compile cache lives.

Every entry point calls :func:`enable_compile_cache` before its first
compile. The directory is part of what JAX keys a cached executable on, so
it must not move between runs: it is ``JAX_COMPILATION_CACHE_DIR`` when the
caller's environment sets one (JAX reads that itself; nothing is set here),
and otherwise ``<checkout>/.jax_cache`` with the checkout located from this
file — never from the cwd, a temp name, a pid or the time. JAX's default
thresholds (store a program that took >= 1 s to compile, any size) already
keep every training step; only sub-second programs recompile.

The key of a cached executable holds its ops' metadata (JAX leaves it out by
default): the step's device scopes (``telemetry/trace.py:device_scope``) live
there and nowhere else, and an executable cached by a program that named
fewer layers computes the same and profiles as that program did. The price
is a recompile when a source line moves under a compiled function.
"""

import os

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """-> the cache directory in use."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
