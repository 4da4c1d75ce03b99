#!/usr/bin/env python3
"""What a wrong or a coarser Phi-4-mini-flash looks like, planted in the
comparison that decides ``correct``.

``CONTROLS`` names each mistake once: a mechanism of the stack left out or
misplaced, and the precisions below the one the configuration states. A
control says how it is made, from these keys:

    ref             attributes of the REFERENCE replaced by name (the functions
                    ``reference/phi4_mini_flash.py`` keeps apart for this, and
                    ``STATE_BITS``)
    ref_variables   f(variables, config) on what the reference reads

Every mistake is planted on the reference's side: the program is compared as
it runs. ``planted`` hands ``harness._reference_check`` a driver and a
reference with one control in them. ``tests/test_phi4flash.py`` runs every
control at a tiny float32 size on the CPU, where the tolerance is reduction
order and each of them is far over it. The command line reads them where
``correct`` is decided, at the cell's size on the chip, through
``harness._reference_check`` itself:

    python3 benchmark/controls/phi4_mini_flash.py --seeds 11,12 --control-seeds 11 \\
        [--controls all|none|a,b] [--embed-std 0.02,0.05] [--out chiprun_out/x.json]

One JSON line a reading (``control`` ``as_run`` is the program as it runs),
the list of them in ``--out``. One trainer serves every reading: its weights
are the first seed's, and a reading's seed draws the noise on the vector
leaves and the tokens. ``--embed-std`` reads at other scales of the tied
embedding than the arch's (the leaf times ``std / ARCHS["phi4flash"].
embed_std``: the initialiser is ``normal(std)``), which is how the scale was
chosen.
"""

import contextlib
import types

ARCH = "phi4flash"
CELL = "phi4flash_s8192_1chip"


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


def _halves(x):
    """The pairs (j, j + heads / 2) for (2j, 2j + 1)."""
    half = x.shape[1] // 2
    return x[:, :half], x[:, half:]


def _conv_not_causal(u, weight, bias):
    """The taps laid over t .. t + K - 1: the layer reads the future."""
    import jax.numpy as jnp
    taps, s = weight.shape[0], u.shape[0]
    padded = jnp.pad(u, ((0, taps - 1), (0, 0)))
    return bias + sum(weight[k] * padded[k:k + s] for k in range(taps))


def _own_kv(handed, a, config, params):
    """A cross layer that projects ITS OWN input with the handing layer's Wk
    and Wv, where it should read the K and V that layer made."""
    bp = params[f"block_{config['num_hidden_layers'] // 2 + 1}"]
    hd = config["hidden_size"] // config["num_attention_heads"]
    heads = lambda x: x.reshape(x.shape[0], -1, hd)
    return heads(a @ bp["Dense_1"]["kernel"]), heads(a @ bp["Dense_2"]["kernel"])


def _silu(z):
    import jax
    return jax.nn.silu(z)


def _window(keys):
    """A window layer's window replaced (None: ignored)."""
    def window_of(config, layer):
        n, half = config["num_hidden_layers"], config["num_hidden_layers"] // 2
        assert n % 4 == 0
        return keys(config) if layer < half and layer % 2 else None
    return window_of


def _lambda_init_of_layer(shift):
    import math
    return lambda layer: 0.8 - 0.6 * math.exp(-0.3 * (layer + shift))


CONTROLS = {
    "lambda_left_out": {"ref": dict(diff_lambda=lambda bp, layer: 1.0)},
    "lambda_init_of_another_layer": {
        "ref": dict(lambda_init=_lambda_init_of_layer(2))},
    "window_ignored": {"ref": dict(window_of=_window(lambda c: None))},
    "window_of_511": {
        "ref": dict(window_of=_window(lambda c: c["sliding_window"] - 1))},
    "window_of_513": {
        "ref": dict(window_of=_window(lambda c: c["sliding_window"] + 1))},
    "memory_taken_after_the_gate": {
        "ref": dict(memory_of=lambda m, z, skip_u: m * _silu(z))},
    "skip_dropped_from_the_memory": {
        "ref": dict(memory_of=lambda m, z, skip_u: m - skip_u)},
    "cross_layers_read_their_own_kv": {"ref": dict(cross_kv=_own_kv)},
    "conv_not_causal": {"ref": dict(causal_conv=_conv_not_causal)},
    "pairs_j_and_j_plus_half": {"ref": dict(pairs=_halves)},
    "softplus_left_out": {"ref": dict(delta_of=lambda dt: dt)},
    # the precisions below the stated one (bfloat16 activations on float32
    # parameters, a float32 scan state): every parameter in float8_e4m3fn,
    # and the scan's state in bfloat16 (7 mantissa bits, rounded every token)
    "parameters_in_float8": {"ref_variables": _rounded("float8_e4m3fn")},
    "scan_state_in_bfloat16": {"ref": dict(STATE_BITS=7)},
}
PRECISION_CONTROLS = ("parameters_in_float8", "scan_state_in_bfloat16")


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


def _embedding_times(driver, factor):
    def variables(trainer):
        v = driver.variables(trainer)
        p = dict(v["params"])
        p["tok_embed"] = {"embedding": p["tok_embed"]["embedding"] * factor}
        return {**v, "params": p}
    return _with(driver, variables=variables)


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
    ap.add_argument("--embed-std", default="")
    ap.add_argument("--out", default="chiprun_out/phi4_mini_flash_controls.json")
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

    from ps_pytorch_tpu.models.transformer import ARCHS
    stds = [float(s) for s in args.embed_std.split(",") if s] \
        or [ARCHS[ARCH].embed_std]
    argv = (list(config["program_args"]) + list(traffic["args"])
            + list(driver.FIXED_ARGS)
            + ["--seed", str(args.seeds[0]), "--max-steps", "1", "--train-dir",
               os.path.join(harness.RUNS_DIR, "controls", "train_dir")])
    trainer = driver.build(argv)
    readings = []

    def read(name, std, seed):
        scaled = _embedding_times(driver, std / ARCHS[ARCH].embed_std)
        t0 = time.monotonic()
        with planted(CONTROLS.get(name, {}), scaled, reference, config) \
                as (d, r):
            check = harness._reference_check(d, r, trainer, config, seed)
        readings.append({"control": name, "embed_std": std, "seed": seed,
                         **check, "seconds": round(time.monotonic() - t0, 1)})
        print(json.dumps(readings[-1]), flush=True)

    for std in stds:
        for seed in args.seeds:
            read("as_run", std, seed)
        for seed in args.control_seeds:
            for name in names:
                read(name, std, seed)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(readings, f, indent=1)
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
