#!/usr/bin/env python3
"""What a wrong or a coarser EvaByte looks like, planted in the comparison that
decides ``correct``.

``CONTROLS`` names each mistake once: a mechanism of the EVA layer left out or
misplaced, and the precision below the one the configuration states. A
control says how it is made, from these keys:

    ref             attributes of the REFERENCE replaced by name (the functions
                    ``reference/evabyte_6_5b.py`` keeps apart for this)
    ref_variables   f(variables, config) on what the reference reads

Every mistake is planted on the reference's side: the program is compared as
it runs. ``planted`` hands ``harness._reference_check`` a driver and a
reference with one control in them. ``LOSS_CONTROLS`` are the mistakes that
are the loss's to make (which byte a head predicts, which heads count): no
comparison of logits can see them, on the chip or anywhere, and
``tests/test_arch_evabyte.py`` holds them by the reference's ``loss``.
That file runs every control at a tiny float32 size on the CPU, where the
tolerance is reduction order, phi and mu are drawn at unit scale and each of
them is far over it. The command line reads ``CONTROLS`` where ``correct`` is
decided, at the cell's size on the chip, through ``harness._reference_check``
itself:

    python3 benchmark/controls/evabyte_6_5b.py --seeds 11,12 --control-seeds 11 \\
        [--controls all|none|precision|a,b] [--out chiprun_out/x.json]

One JSON line a reading (``control`` ``as_run`` is the program as it runs),
the list of them in ``--out``. One trainer serves every reading: its weights
are the first seed's, and a reading's seed draws the noise on the vector
leaves and the tokens.
"""

import contextlib
import types

CELL = "evabyte_s16384_1chip"


def _rounded(dtype):
    """Every parameter rounded to ``dtype``. The barrier keeps the rounding
    under ``jit`` on the chip: the v5e's compiler is allowed excess precision
    and took ``float32 -> float8 -> float32`` out as a no-op (PR 31)."""
    def f(variables, config):
        import jax
        return jax.tree.map(
            lambda a: jax.lax.optimization_barrier(
                a.astype(dtype)).astype(a.dtype), variables)
    return f


def _own_windows_chunks_too(n, j, config):
    """Every chunk that closed before the query, its own window's among them:
    those tokens are then counted twice."""
    return (j + 1) * config["chunk_size"] <= n


def _later_windows_chunks_too(n, j, config):
    """Every chunk outside the query's own window: the layer reads the future."""
    w = config["window_size"]
    return j // (w // config["chunk_size"]) != n // w


def _band(n, m, config):
    """A sliding window of W keys, not the block of the diagonal."""
    return (m <= n) & (n - m < config["window_size"])


def _two_softmaxes(scores_tokens, scores_summaries, v, vs):
    """A softmax over the tokens and one over the summaries, averaged where a
    query sees summaries: two normalisers for the one."""
    import jax
    import jax.numpy as jnp
    sees = jnp.any(jnp.isfinite(scores_summaries), axis=-1, keepdims=True)
    tokens = jax.nn.softmax(scores_tokens, axis=-1) @ v
    summaries = jax.nn.softmax(
        jnp.where(sees, scores_summaries, 0.0), axis=-1) @ vs
    return jnp.where(sees, 0.5 * (tokens + summaries), tokens)


def _unscaled_pool_scores(k, phi, config):
    return k @ phi


def _uniform_pool_scores(k, phi, config):
    return 0.0 * (k @ phi)


CONTROLS = {
    "own_windows_chunks_counted_twice": {
        "ref": dict(sees_summary=_own_windows_chunks_too)},
    "later_windows_chunks_read": {
        "ref": dict(sees_summary=_later_windows_chunks_too)},
    "band_for_the_block": {"ref": dict(sees_token=_band)},
    "two_softmaxes_averaged": {"ref": dict(attend=_two_softmaxes)},
    "mu_left_out": {"ref": dict(pooled_key=lambda ks, mu: ks)},
    "phi_ignored": {"ref": dict(pool_scores=_uniform_pool_scores)},
    "pooling_scale_left_out": {"ref": dict(pool_scores=_unscaled_pool_scores)},
    "summaries_from_unrotated_keys": {
        "ref": dict(summary_keys=lambda rotated, plain: plain)},
    "chunk_twice_as_long": {
        "ref": dict(chunk_of=lambda config: 2 * config["chunk_size"])},
    "norm_scale_w_for_1_plus_w": {"ref": dict(norm_scale=lambda w: w)},
    # the precision below the stated one (bfloat16 activations on float32
    # parameters, float32 softmax statistics): every parameter in float8_e4m3fn
    "parameters_in_float8": {"ref_variables": _rounded("float8_e4m3fn")},
}
PRECISION_CONTROLS = ("parameters_in_float8",)
# the loss's own mistakes: no logit moves (module docstring)
LOSS_CONTROLS = {
    "head_i_predicts_byte_t_plus_i": dict(target_offset=lambda head: head),
    "later_heads_left_out_of_the_loss": dict(
        heads_in_loss=lambda config: range(1)),
}


def _with(inner, **over):
    """A module's or a namespace's attributes with some replaced."""
    return types.SimpleNamespace(**{**vars(inner), **over})


@contextlib.contextmanager
def planted(control, driver, reference, config):
    """-> (driver, reference) as ``harness._reference_check`` takes them, with
    ``control`` in them; the reference's attributes are put back on the way
    out."""
    same = lambda v, c: v
    ref = control.get("ref_variables", same)
    over = control.get("ref", {})
    kept = {k: getattr(reference, k) for k in over}
    for k, v in over.items():
        setattr(reference, k, v)
    try:
        yield driver, _with(reference, forward=lambda v, x, c:
                            reference.forward(ref(v, c), x, c))
    finally:
        for k, v in kept.items():
            setattr(reference, k, v)


def main(argv=None) -> int:
    import argparse
    import json
    import os
    import sys
    import time

    bench_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    checkout = os.path.dirname(bench_dir)
    for p in (checkout, bench_dir):
        if p not in sys.path:
            sys.path.insert(0, p)
    os.chdir(checkout)      # as benchmark/run.py does
    import harness

    ints = lambda s: [int(x) for x in s.split(",") if x]
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=ints, required=True,
                    help="read the program as it runs on each")
    ap.add_argument("--control-seeds", type=ints, default=[],
                    help="read every chosen control on each")
    ap.add_argument("--controls", default="all")
    ap.add_argument("--out", default="chiprun_out/evabyte_6_5b_controls.json")
    args = ap.parse_args(argv)

    files = harness.Files()
    bench = harness.load_json(os.path.join(harness.CHECKOUT, "BENCHMARK.json"))
    cell = harness.find_cell(bench, CELL)
    config = files.json("configs", cell["config"] + ".json")
    traffic = files.json("traffic", cell["traffic"] + ".json")
    driver = files.module("drivers", config["driver"] + ".py")
    reference = files.module("reference", cell["config"] + ".py")
    names = {"all": sorted(CONTROLS), "none": [],
             "precision": list(PRECISION_CONTROLS)}.get(
        args.controls, args.controls.split(","))

    argv = (list(config["program_args"]) + list(traffic["args"])
            + list(driver.FIXED_ARGS)
            + ["--seed", str(args.seeds[0]), "--max-steps", "1", "--train-dir",
               os.path.join(harness.RUNS_DIR, "controls", "train_dir")])
    trainer = driver.build(argv)
    readings = []

    def read(name, seed):
        t0 = time.monotonic()
        with planted(CONTROLS.get(name, {}), driver, reference, config) \
                as (d, r):
            check = harness._reference_check(d, r, trainer, config, seed)
        readings.append({"control": name, "seed": seed, **check,
                         "seconds": round(time.monotonic() - t0, 1)})
        print(json.dumps(readings[-1]), flush=True)

    for seed in args.seeds:
        read("as_run", seed)
    for seed in args.control_seeds:
        for name in names:
            read(name, seed)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(readings, f, indent=1)
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
