#!/usr/bin/env python3
"""What a wrong or a coarser Trinity-Mini looks like, planted in the
comparison that decides ``correct``.

``CONTROLS`` names each mistake once: a mechanism of the block left out or
misplaced, and the precisions below the one the configuration states. A
control says how it is made, from these keys:

    arch            fields of ``ARCHS["trinity"]`` replaced (the program's side)
    model           fields of the program's model replaced
    variables       f(variables, config) on what the PROGRAM reads
    ref_variables   f(variables, config) on what the REFERENCE reads
    ref_route       the reference's ``route`` replaced

``planted`` hands ``harness._reference_check`` a driver and a reference with
one control in them. ``tests/test_trinity.py`` runs every control at a tiny
float32 size on the CPU, where the tolerance is reduction order and each of
them is far over it. The command line reads them where ``correct`` is decided,
at the cell's size on the chip, through ``harness._reference_check`` itself:

    python3 benchmark/controls/trinity_mini.py --seeds 11,12 --control-seeds 11 \
        [--controls all|none|a,b] [--embed-std 0.5,0.25] [--out chiprun_out/x.json]

One JSON line a reading (``control`` ``as_run`` is the program as it runs),
the list of them in ``--out``. One trainer serves every reading: its weights
are the first seed's, and a reading's seed draws the noise on the vector leaves
and the tokens (a run of the cell draws its weights from its seed too). ``--embed-std`` reads at other embedding
scales than the arch's (the leaf times ``std / ARCHS["trinity"].embed_std``:
the initialiser is ``normal(std)``), which is how the scale was chosen;
``<CELL>.json`` beside this file holds the readings the configuration's
limit was set from.
"""

import contextlib
import types

ARCH = "trinity"
CELL = "trinity_mini_s8192_1chip"


def _blocks(tree):
    return {k: v for k, v in tree.items() if k.startswith("block_")}


def _rounded(dtype, blocks_only=False):
    """Every parameter and the bias rounded to ``dtype``; ``blocks_only``
    leaves the embedding, the final norm and the head as they are. The
    barrier keeps the rounding under ``jit`` on the chip: the v5e's compiler
    is allowed excess precision and took ``float32 -> float8 -> float32``
    out as a no-op (PR 31: the control read what the program as run read)."""
    def f(variables, config):
        import jax

        def rnd(tree):
            return jax.tree.map(
                lambda a: jax.lax.optimization_barrier(
                    a.astype(dtype)).astype(a.dtype), tree)
        if not blocks_only:
            return rnd(variables)
        return {k: {**v, **rnd(_blocks(v))} for k, v in variables.items()}
    return f


def _qk_scales_tiled(variables, config):
    """A norm over all of q's (k's) features needs a scale that long: each
    head's scale repeated, so that only the statistics differ."""
    import jax.numpy as jnp

    p = dict(variables["params"])
    for name, block in _blocks(p).items():
        p[name] = {**block,
                   "q_norm": {"scale": jnp.tile(
                       block["q_norm"]["scale"],
                       config["num_attention_heads"])},
                   "k_norm": {"scale": jnp.tile(
                       block["k_norm"]["scale"],
                       config["num_key_value_heads"])}}
    return {**variables, "params": p}


def _first_layer_with_experts(variables, config):
    """Block 0 given block 1's experts, shared expert and bias beside its
    dense feed-forward: what a stack with no dense layer would read."""
    p = dict(variables["params"])
    p["block_0"] = {**p["block_0"], "moe": p["block_1"]["moe"],
                    "shared": p["block_1"]["shared"]}
    state = dict(variables["moe_state"])
    state["block_0"] = state["block_1"]
    return {"params": p, "moe_state": state}


def _heads_regrouped(variables, config):
    """The query heads renumbered (q and gate columns, o rows) so that the
    head the program calls h reads key/value head h % kv_heads, not
    h // group: summed over heads nothing else changes."""
    import numpy as np

    heads, kv, hd = (config["num_attention_heads"],
                     config["num_key_value_heads"], config["head_dim"])
    group = heads // kv
    h = np.arange(heads)
    perm = np.argsort(group * (h % kv) + h // kv)   # new head j holds old perm[j]
    p = dict(variables["params"])
    for name, block in _blocks(p).items():
        d = block["Dense_0"]["kernel"].shape[0]
        cols = lambda w: w.reshape(d, heads, hd)[:, perm].reshape(d, -1)
        p[name] = {**block,
                   "Dense_0": {"kernel": cols(block["Dense_0"]["kernel"])},
                   "gate": {"kernel": cols(block["gate"]["kernel"])},
                   "Dense_3": {"kernel": block["Dense_3"]["kernel"].reshape(
                       heads, hd, d)[perm].reshape(-1, d)}}
    return {**variables, "params": p}


def _weighing_by_the_bias_too(m, router, bias, config):
    """The reference's ``route`` with the mistake: score + bias weighs."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(m @ router) + bias
    kth = jax.lax.top_k(s, config["num_experts_per_tok"])[0][:, -1:]
    w = jnp.where(s >= kth, s, 0.0)
    return s, config["route_scale"] * w / jnp.sum(w, axis=-1, keepdims=True)


CONTROLS = {
    "gate_missing": {"arch": dict(attn_gate=False)},
    "qk_norm_over_d_not_a_head": {
        "arch": dict(head_qk_norm=False, qk_norm=True),
        "variables": _qk_scales_tiled},
    "rope_on_the_global_layer": {"arch": dict(rope_layers=())},
    "rope_missing_on_a_window_layer": {"arch": dict(rope_layers=(1, 0, 1, 0))},
    "window_ignored": {"arch": dict(window_layers=())},
    "global_layer_one_place_early": {
        "arch": dict(window_layers=(1, 1, 0, 1), rope_layers=(1, 1, 0, 1))},
    "kv_head_h_mod_kv": {"ref_variables": _heads_regrouped},
    "norm_on_the_input_only": {"arch": dict(post_norm=False)},
    "softmax_for_sigmoid": {"arch": dict(router_score="softmax")},
    "bias_weighs_too": {"ref_route": _weighing_by_the_bias_too},
    "bias_left_out_of_the_choice": {"arch": dict(router_bias_rate=0.0)},
    "gates_not_normalised": {"arch": dict(gate_norm=False)},
    "gates_not_scaled": {"arch": dict(route_scale=1.0)},
    "shared_expert_missing": {"arch": dict(shared_experts=0)},
    "first_layer_routed_not_dense": {
        "model": dict(dense_layers=0), "variables": _first_layer_with_experts},
    "embedding_not_multiplied": {"arch": dict(embed_scale=False)},
    # the precisions below the stated one (bfloat16 activations on float32
    # parameters): every parameter in float8_e4m3fn, and the blocks' alone
    "parameters_in_float8": {"ref_variables": _rounded("float8_e4m3fn")},
    "block_parameters_in_float8": {
        "ref_variables": _rounded("float8_e4m3fn", blocks_only=True)},
}


def _with(inner, **over):
    """A module's or a namespace's attributes with some replaced."""
    return types.SimpleNamespace(**{**vars(inner), **over})


@contextlib.contextmanager
def planted(control, driver, reference, config):
    """-> (driver, reference) as ``harness._reference_check`` takes them,
    with ``control`` in them; the arch's row and the reference's ``route``
    are put back on the way out."""
    from ps_pytorch_tpu.models import transformer

    same = lambda v, c: v
    prog, ref = control.get("variables", same), control.get("ref_variables",
                                                            same)

    def system_forward(trainer, variables, x):
        model = trainer.model.clone(**control.get("model", {}))
        return driver.system_forward(types.SimpleNamespace(model=model),
                                     prog(variables, config), x)

    row, route = transformer.ARCHS[ARCH], reference.route
    transformer.ARCHS[ARCH] = row._replace(**control.get("arch", {}))
    reference.route = control.get("ref_route", route)
    try:
        yield (_with(driver, system_forward=system_forward),
               _with(reference, forward=lambda v, x, c: reference.forward(
                   ref(v, c), x, c)))
    finally:
        transformer.ARCHS[ARCH], reference.route = row, route


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
    ap.add_argument("--out", default="chiprun_out/trinity_mini_controls.json")
    args = ap.parse_args(argv)

    files = harness.Files()
    bench = harness.load_json(os.path.join(harness.CHECKOUT, "BENCHMARK.json"))
    cell = harness.find_cell(bench, CELL)
    config = files.json("configs", cell["config"] + ".json")
    traffic = files.json("traffic", cell["traffic"] + ".json")
    driver = files.module("drivers", config["driver"] + ".py")
    reference = files.module("reference", cell["config"] + ".py")
    names = {"all": sorted(CONTROLS), "none": []}.get(
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
