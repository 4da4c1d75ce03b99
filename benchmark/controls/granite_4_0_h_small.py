#!/usr/bin/env python3
"""What a wrong or a coarser Granite-4.0-H-Small looks like, planted in the
comparison that decides ``correct``.

``CONTROLS`` names each mistake once: a multiplier or a mechanism of the stack
left out or misplaced, and the precisions below the one the configuration
states. A control says how it is made, from these keys:

    ref             attributes of the REFERENCE replaced by name (the functions
                    ``reference/granite_4_0_h_small.py`` keeps apart for this,
                    and ``STATE_BITS``)
    ref_variables   f(variables, config) on what the reference reads

Every mistake is planted on the reference's side: the program is compared as
it runs. ``planted`` hands ``harness._reference_check`` a driver and a
reference with one control in them. ``tests/test_arch_granite4h.py`` runs
every control at a tiny float32 size on the CPU, where the tolerance is
reduction order and each of them is far over it. The command line reads them
where ``correct`` is decided, at the cell's size on the chip, through
``harness._reference_check`` itself:

    python3 benchmark/controls/granite_4_0_h_small.py --seeds 11,12 --control-seeds 11 \\
        [--controls all|none|precision|a,b] [--out chiprun_out/x.json]

One JSON line a reading (``control`` ``as_run`` is the program as it runs),
the list of them in ``--out``. One trainer serves every reading: its weights
are the first seed's, and a reading's seed draws the noise on the vector
leaves and the tokens.
"""

import contextlib
import types

CELL = "granite4h_small_tp8_1chip"


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


def _norm_then_gate(y, z, scale, eps, heads):
    """The norm first, then the gate: Qwen3-Next's order (norm_before_gate)."""
    import jax
    import jax.numpy as jnp
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    return y * scale * jax.nn.silu(z)


def _norm_in_groups(groups):
    """The gated norm over each of ``groups`` groups of the channels (None:
    over each head) where the model has ONE group over all of them."""
    def f(y, z, scale, eps, heads):
        import jax
        import jax.numpy as jnp
        s = y.shape[0]
        g = (y * jax.nn.silu(z)).reshape(s, groups or heads, -1)
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
        return g.reshape(s, -1) * scale
    return f


def _b_c_a_head(t, heads):
    """Each head its own B and C (head h's the group's with every other
    state halved, from state h % 2 on), where every head reads the one
    group's as it is."""
    import jax.numpy as jnp
    states = jnp.arange(t.shape[1])
    return jnp.stack([t * jnp.where((states + h) % 2 == 0, 1.0, 0.5)
                      for h in range(heads)], axis=1)


def _rope(q, k, theta=10000.0):
    """Rotary position embedding (rotate-half, the configuration's carried
    rope_theta) where the model has no position encoding."""
    import jax.numpy as jnp

    def turn(x):                                    # [heads, S, hd]
        half = x.shape[-1] // 2
        inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
        ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None]
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               axis=-1)
    return turn(q), turn(k)


def _gates_of_all(t, chosen):
    """The softmax over ALL the outputs, kept where chosen: the ten gates do
    not sum to 1."""
    import jax
    import jax.numpy as jnp
    return jnp.where(chosen, jax.nn.softmax(t, axis=-1), 0.0)


def _untied_head(p):
    """A head of its own: the embedding's rows one place on."""
    import jax.numpy as jnp
    return jnp.roll(p["tok_embed"]["embedding"], 1, axis=0)


CONTROLS = {
    # the four multipliers
    "embedding_multiplier_left_out": {
        "ref": dict(embedding_multiplier=lambda config: 1.0)},
    "attention_scale_by_head_size": {
        "ref": dict(attention_multiplier=lambda config, hd: hd ** -0.5)},
    "residual_multiplier_left_out": {
        "ref": dict(residual_multiplier=lambda config: 1.0)},
    "logits_scaling_left_out": {
        "ref": dict(logits_scaling=lambda config: 1.0)},
    # the Mamba-2 mixer in one group
    "norm_before_the_gate": {"ref": dict(gated_norm=_norm_then_gate)},
    "norm_over_a_head": {"ref": dict(gated_norm=_norm_in_groups(None))},
    "norm_over_eight_groups": {"ref": dict(gated_norm=_norm_in_groups(8))},
    "b_c_a_head_not_shared": {"ref": dict(b_c_of_head=_b_c_a_head)},
    # attention, the expert half, the head
    "rope_applied": {"ref": dict(positions_on=_rope)},
    "gates_not_renormalised": {"ref": dict(chosen_gates=_gates_of_all)},
    "shared_expert_left_out": {
        "ref": dict(shared_expert=lambda bp, m: 0.0 * m)},
    "head_not_tied": {"ref": dict(head_table=_untied_head)},
    # the precisions below the stated one (bfloat16 activations on float32
    # parameters, a float32 state): every parameter in float8_e4m3fn, and the
    # recurrence's state in bfloat16 (7 mantissa bits) at every chunk boundary
    "parameters_in_float8": {"ref_variables": _rounded("float8_e4m3fn")},
    "state_in_bfloat16_a_chunk": {"ref": dict(STATE_BITS=7)},
}
PRECISION_CONTROLS = ("parameters_in_float8", "state_in_bfloat16_a_chunk")


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
    ap.add_argument("--out",
                    default="chiprun_out/granite_4_0_h_small_controls.json")
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
