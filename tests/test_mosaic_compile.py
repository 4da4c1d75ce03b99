"""The OLMoE cell's grouped matmuls, the SmallThinker, Trinity, Phi-4-flash,
Qwen3-Next and Nemotron-3-Nano cells' flash kernels, the Phi-4-flash cell's
selective scan, the Qwen3-Next cell's gated delta rule and mixer chains and
the Nemotron-3-Nano cell's state-space-dual kernels and the EvaByte cell's EVA
core and pooling compile under
Mosaic for a described v5e (no chip): what the
Pallas interpreter cannot show — VMEM over the limit, a
slice off the tiling, a DMA the compiler refuses; and, under ``-m slow``, the
long-context cells' whole train steps, for the bytes the compiler plans on the
device; and the Granite-4.0-H-Small cell's kernels at ONE chip's share of
every layer (4 query heads on one K/V head, 16 Mamba-2 heads in one group at
chunks of 256, a gated norm one group 1,024 wide, 9 experts of 4096 x 768).
The kernel-shape compiles are tier-1's: no chip run says which kernel
or which tile. The seven whole-step compiles (one parametrised test over
``STEP_CELLS``; 260-350 s together at PR 46) are marked ``slow`` since PR 42: that the cell's step fits the chip is what the
driver measures on a v5e in that very cell on every PR (``peak_hbm``; a step
that does not fit fails the cell). Run them when a PR moves a step's plan.
One file, one fixture: only the worker that runs it loads the TPU compiler
(on-chip-measurement guide, section 2)."""

import importlib
import json
import math
import os
import re
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ps_pytorch_tpu.ops import grouped_matmul
from ps_pytorch_tpu.ops.grouped_matmul import (
    VMEM_LIMIT_BYTES, WEIGHTS_VMEM_BYTES, _tiles, gmm,
)

# olmoe_s4096_1chip: 4096 tokens x top-8 rows, 64 experts of 2048 x 1024
M, D, F, E = 32768, 2048, 1024, 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip cannot be read back from the
    # persistent cache without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("rows", ["bfloat16", "float32"])
def test_expert_ffn_forward_and_backward_compile_at_the_cells_shape(
        one_chip, monkeypatch, rows):
    """silu(x Wgate) * (x Wup) Wdown over ragged groups and its gradients:
    nine kernels (fwd, dlhs, drhs at both weight shapes), float32 weights
    under ``rows``, as ``models/moe.DroplessMoE`` calls them."""
    monkeypatch.setattr(grouped_matmul, "interpret_default", lambda: False)

    def loss(xs, wg, wu, wd, gs):
        h = jax.nn.silu(gmm(xs, wg, gs)) * gmm(xs, wu, gs)
        return jnp.sum(gmm(h, wd, gs).astype(jnp.float32))

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        arg((M, D), rows), arg((E, D, F), jnp.float32),
        arg((E, D, F), jnp.float32), arg((E, F, D), jnp.float32),
        arg((E,), jnp.int32)).compile()
    text = compiled.as_text()
    for name in ("moe_gmm_fwd", "moe_gmm_dlhs", "moe_gmm_drhs"):
        assert name in text
    grads = compiled.output_shardings     # one per argument differentiated
    assert len(grads) == 4


# (batch, query heads, key/value heads, S, the window layers' window)
FLASH_CELLS = {"smallthinker_s16384_1chip": (1, 28, 4, 16384, 4096),
               "trinity_mini_s8192_1chip": (2, 32, 4, 8192, 2048)}


@pytest.mark.parametrize("windowed", [False, True])
@pytest.mark.parametrize("cell", sorted(FLASH_CELLS))
def test_flash_grouped_query_window_compiles_at_the_cells_shape(one_chip,
                                                                 cell,
                                                                 windowed):
    """28 query heads over 4 key/value heads of 128 at S=16384, and 2 x 32
    over 2 x 4 at S=8192, bfloat16: the forward (a K/V head whole, no kv
    axis) and the one backward kernel (one query head's rows a step, the
    group along the grid), with and without the window; dK and dV come back
    at the key/value heads, dQ as two float32 partials or as itself."""
    from ps_pytorch_tpu.ops.flash_attention import (
        flash_attention, flash_schedule,
    )
    b, h, h_kv, s, window = FLASH_CELLS[cell]
    window = window if windowed else None

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, window=window,
                                       interpret=False).astype(jnp.float32))

    def arg(heads):
        return jax.ShapeDtypeStruct((b, heads, s, 128), jnp.bfloat16,
                                    sharding=one_chip)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        arg(h), arg(h_kv), arg(h_kv)).compile()
    text = compiled.as_text()
    names = ("flash_win_fwd", "flash_win_bwd_dkv") if window else \
        ("flash_fwd", "flash_bwd_dkv")
    for name in names:      # a bare kernel's call is named jvp_<name>_
        assert f"{name}_" in text
    assert ("flash_win_" in text) == bool(window)
    assert f"bf16[{b * h_kv},{s},128]" in text
    assert f"bf16[{b * h},{s},128]" in text
    sc = flash_schedule(b * h, s, 128, 2, True, window=window,
                        bh_kv=b * h_kv)
    assert sc.grid[2] == 1 and sc.bwd_g == 1
    assert (f"f32[{sc.dq_partials},{b * h},{s},128]" in text) \
        == (sc.dq_partials > 1)


# The Pallas modules whose calls pick the interpreter where no chip is attached.
KERNEL_MODULES = ("flash_attention", "selective_scan", "gated_delta_rule",
                  "gdn_mix", "ssd", "ssm_mix", "eva_attention")


def whole_step(one_chip, monkeypatch, config, traffic):
    """The jitted train step as ``LMTrainer`` builds it from a cell's own
    flags (``benchmark/configs/<config>.json``, ``benchmark/traffic/
    <traffic>.json``), compiled for the described chip from shapes alone ->
    (the cell's ``TrainConfig``, the parameters' shapes, the compiled text,
    the bytes ``memory_analysis()`` plans on the device: arguments + outputs
    - aliased + temporaries)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from ps_pytorch_tpu.config import config_from_args
    from ps_pytorch_tpu.optim.schedules import build_schedule
    from ps_pytorch_tpu.optim.sgd import sgd
    from ps_pytorch_tpu.parallel import ep, sp
    from ps_pytorch_tpu.runtime.lm_eval import build_lm_model
    for name in KERNEL_MODULES:
        monkeypatch.setattr(
            importlib.import_module("ps_pytorch_tpu.ops." + name),
            "_interpret_default", lambda: False)
    monkeypatch.setattr(grouped_matmul, "interpret_default", lambda: False)
    monkeypatch.setattr(importlib.import_module("ps_pytorch_tpu.ops.moe_rows"),
                        "interpret_default", lambda: False)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           config + ".json")) as f:
        argv = json.load(f)["program_args"]
    with open(os.path.join(root, "benchmark", "traffic",
                           traffic + ".json")) as f:
        argv = argv + json.load(f)["args"]
    cfg = config_from_args(argv)
    tx = sgd(lr=build_schedule(cfg), momentum=cfg.momentum,
             weight_decay=cfg.weight_decay, nesterov=cfg.nesterov)
    shape = (cfg.batch_size, cfg.lm_seq_len)
    devices = np.array(list(one_chip.device_set))
    if cfg.lm_parallelism == "ep":
        mesh = Mesh(devices.reshape(1, 1), ("data", "model"))
        model = build_lm_model(cfg, attention_impl="flash", ep_axis="data")
        shapes = jax.eval_shape(partial(
            ep.create_ep_train_state, model, tx, mesh, shape),
            jax.random.key(0))
        specs, token_spec = ep.ep_state_specs(shapes, "data"), P("data", None)
        step = ep.make_ep_train_step(model, tx, mesh, shapes,
                                     remat=cfg.remat, donate=cfg.donate)
    else:
        mesh = Mesh(devices, ("data",))
        model = build_lm_model(cfg, attention_impl="flash", axis_name="data")
        shapes = jax.eval_shape(partial(
            sp.create_lm_train_state, model, tx, mesh, shape),
            jax.random.key(0))
        specs, token_spec = jax.tree.map(lambda a: P(), shapes), P(None, "data")
        step = sp.make_sp_train_step(model, tx, mesh, remat=cfg.remat,
                                     donate=cfg.donate)
    state = jax.tree.map(
        lambda a, spec: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, spec)),
        shapes, specs)
    tokens = jax.ShapeDtypeStruct(shape, jnp.int32,
                                  sharding=NamedSharding(mesh, token_spec))
    compiled = step.lower(state, tokens).compile()
    m = compiled.memory_analysis()
    planned = (m.argument_size_in_bytes + m.output_size_in_bytes
               - m.alias_size_in_bytes + m.temp_size_in_bytes)
    print(f"PLANNED {config} {planned / 2 ** 30:.3f} GiB; arguments "
          f"{m.argument_size_in_bytes / 2 ** 30:.3f}, temporaries "
          f"{m.temp_size_in_bytes / 2 ** 30:.3f}")
    return cfg, shapes.params, compiled.as_text(), planned


class StepCell(NamedTuple):
    """One --remat cell's whole step, as PR 46 found it by this same compile."""
    config: str
    traffic: str
    experts: tuple      # (router outputs, experts held) of the cell's flags
    n_params: int
    calls: dict         # kernel -> its calls in the compiled step (below)
    absent: str         # a name the step must not hold
    planned: int        # bytes the compiler plans on the device


# A kernel's calls are the instructions named <kernel>.N. A flash forward
# runs ONCE a layer (a rematerialised block keeps its output and log-sum-exp,
# models/remat.py: were the names lost it would run twice), one call a window
# layer and one the global layer in SmallThinker and Trinity, a call a head of
# a differential pair in the hybrid; every other forward kernel runs
# twice a layer (the recomputed forward makes its residuals again), every
# backward kernel once; the grouped matmuls are a call an expert matmul;
# ``moe_rows_sum`` four a routed layer that holds a share (the main part's
# combine and its take's transpose, and the same two under the overflow
# ``cond``; PR 52, whose step plans 0.1-0.5 GB less in all five such cells:
# no float32 ``[rows, d]`` product, no zeros, no float32 ``[T, d]`` pair).
# A plan moves by a few hundred KB with the heap's packing; PERF.md section 4
# has the history (PR 46 is the first since PR 34 to raise one: a
# rematerialised block keeps more; PR 50 raises the hybrid's by 1.2 GiB
# though its layers keep less: its first schedule now fits XLA's limit and
# stands, where the parent's was made again under a tighter one). The Granite
# step is at S=8192, rule (b) of its configuration: at S=16384 it plans 16.71
# GB, 4.06 GiB of it the overflow path's two float32 buffers (PR 51).
STEP_CELLS = {
    "smallthinker_s16384_1chip": StepCell(
        "smallthinker_21b_a3b", "s16384_1chip", (64, 16), 559_290_880,
        {"flash_fwd": 1, "flash_bwd_dkv": 1, "flash_win_fwd": 3,
         "flash_win_bwd_dkv": 3, "moe_gmm_fwd": 48, "moe_gmm_dlhs": 24,
         "moe_gmm_drhs": 24, "moe_rows_sum": 16}, "ssm_", 8_810_382_336),
    "trinity_mini_s8192_1chip": StepCell(
        "trinity_mini", "s8192_1chip", (128, 16), 705_473_792,
        {"flash_fwd": 1, "flash_bwd_dkv": 1, "flash_win_fwd": 4,
         "flash_win_bwd_dkv": 4, "moe_gmm_fwd": 48, "moe_gmm_dlhs": 24,
         "moe_gmm_drhs": 24, "moe_rows_sum": 16}, "ssm_", 13_179_736_576),
    "phi4flash_s8192_1chip": StepCell(
        "phi4_mini_flash", "s8192_reasoning_1chip", (8, 0), 915_283_456,
        {"flash_fwd": 4, "flash_bwd_dkv": 4, "flash_win_fwd": 4,
         "flash_win_bwd_dkv": 4, "ssm_scan_fwd": 6, "ssm_scan_bwd": 3},
        "moe_gmm_", 15_383_074_304),
    "qwen3next_s16384_1chip": StepCell(
        "qwen3_next_80b_a3b", "s16384_hybrid_1chip", (512, 64), 1_028_320_320,
        {"flash_fwd": 1, "flash_bwd_dkv": 1, "gdr_solve": 6, "gdr_fwd": 6,
         "gdr_bwd": 3, "moe_gmm_fwd": 48, "moe_gmm_dlhs": 24,
         "moe_gmm_drhs": 24, "moe_rows_sum": 16, "gdn_conv_fwd_q": 6, "gdn_conv_fwd_k": 6,
         "gdn_conv_fwd_v": 6, "gdn_conv_bwd_q": 3, "gdn_conv_bwd_k": 3,
         "gdn_conv_bwd_v": 3, "gdn_norm_fwd": 6, "gdn_norm_bwd": 3},
        "flash_win_", 14_384_855_552),
    "nemotron3nano_s16384_1chip": StepCell(
        "nemotron3_nano_30b_a3b", "s16384_ssd_1chip", (128, 16), 986_254_336,
        {"flash_fwd": 1, "flash_bwd_dkv": 1, "ssd_fwd": 8, "ssd_bwd": 4,
         "moe_gmm_fwd": 32, "moe_gmm_dlhs": 16, "moe_gmm_drhs": 16,
         "moe_rows_sum": 16, "ssm_conv_fwd_x": 8, "ssm_conv_fwd_b": 8, "ssm_conv_fwd_c": 8,
         "ssm_conv_bwd_x": 4, "ssm_conv_bwd_b": 4, "ssm_conv_bwd_c": 4,
         "ssm_norm_fwd": 8, "ssm_norm_bwd": 4}, "flash_win_",
        14_328_864_768),
    "evabyte_s16384_1chip": StepCell(
        "evabyte_6_5b", "s16384_bytes_1chip", (8, 0), 821_366_784,
        {"eva_pool_fwd": 4, "eva_fwd": 4, "eva_bwd": 4, "eva_pool_bwd": 4},
        "flash_", 12_189_031_936),
    "granite4h_small_tp8_1chip": StepCell(
        "granite_4_0_h_small", "tp8_share_1chip", (72, 9), 1_055_938_224,
        {"flash_fwd": 1, "flash_bwd_dkv": 1, "ssd_fwd": 18, "ssd_bwd": 9,
         "moe_gmm_fwd": 120, "moe_gmm_dlhs": 60, "moe_gmm_drhs": 60,
         "moe_rows_sum": 40, "ssm_conv_fwd_x": 18, "ssm_conv_fwd_b": 18, "ssm_conv_fwd_c": 18,
         "ssm_conv_bwd_x": 9, "ssm_conv_bwd_b": 9, "ssm_conv_bwd_c": 9,
         "ssm_norm_fwd": 18, "ssm_norm_bwd": 9}, "flash_win_",
        14_134_619_136),
}
HEAP_PACKING_BYTES = 2 ** 20


@pytest.mark.slow    # a whole step at the cell's size: see the module's docstring
@pytest.mark.parametrize("cell", sorted(STEP_CELLS))
def test_the_cells_whole_step_fits_by_the_rule(one_chip, monkeypatch, cell):
    """The jitted train step as ``LMTrainer`` builds it from the cell's own
    flags, compiled for the described chip from shapes alone: it is the
    cell's step (the experts held, the parameters, a plan within a tenth of
    the one found), every kernel the cell runs is in it as a Pallas call, as
    many times as ``STEP_CELLS`` says, the configuration's rule (``cut.rule``:
    under 14.5 GiB by ``memory_analysis()``) holds at no more planned memory
    than the table's figure, and the compiler rematerialises nothing by
    itself (an instruction named ``.remat``: in the Qwen3-Next step three
    copies of the 2048 -> 12,288 projection until PR 41, the sign of a plan
    too full)."""
    import numpy as np
    c = STEP_CELLS[cell]
    cfg, params, text, planned = whole_step(one_chip, monkeypatch, c.config,
                                            c.traffic)
    assert cfg.remat
    assert (cfg.lm_experts, cfg.lm_experts_held) == c.experts
    assert sum(int(np.prod(a.shape))
               for a in jax.tree.leaves(params)) == c.n_params
    calls = {name: len(re.findall(rf"%{name}\.\d+ = ", text))
             for name in c.calls}
    assert calls == c.calls
    for name in c.calls:
        assert text.count(f"/{name}/pallas_call") > 0, name
    assert c.absent not in text
    assert ".remat" not in text
    assert 0.9 * c.planned < planned <= c.planned + HEAP_PACKING_BYTES
    assert planned < 14.5 * 2 ** 30


# phi4flash_s8192_1chip: a call holds one head of each differential pair, 20
# query heads over 10 key/value heads of 64, under the window of 512 and
# without; the value is the pair's two value heads side by side, 128 wide
# (since PR 50; 64 wide, a call each, until then: the form any other arch of
# 64-wide heads under a group of 2 would run)
@pytest.mark.parametrize("dv", [64, 128])
@pytest.mark.parametrize("window", [None, 512])
def test_flash_at_head_dim_64_two_heads_a_kv_head_compiles(one_chip, window,
                                                           dv):
    from ps_pytorch_tpu.ops.flash_attention import (
        flash_attention, flash_schedule,
    )
    b, h, h_kv, s, d = 1, 20, 10, 8192, 64

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, window=window,
                                       interpret=False).astype(jnp.float32))

    def arg(heads, width=d):
        return jax.ShapeDtypeStruct((b, heads, s, width), jnp.bfloat16,
                                    sharding=one_chip)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        arg(h), arg(h_kv), arg(h_kv, dv)).compile().as_text()
    names = ("flash_win_fwd", "flash_win_bwd_dkv") if window else \
        ("flash_fwd", "flash_bwd_dkv")
    for name in names:
        assert f"{name}_" in text
    # the output and dV as wide as the value, dQ and dK as the keys
    for shape in (f"bf16[{h},{s},{dv}]", f"bf16[{h_kv},{s},{dv}]",
                  f"bf16[1,{h},{s},{d}]", f"bf16[{h_kv},{s},{d}]"):
        assert shape in text, shape
    sc = flash_schedule(b * h, s, d, 2, True, window=window, bh_kv=b * h_kv,
                        dv=dv)
    assert sc.dv == (0 if dv == d else dv)
    assert sc.group == 2 and sc.window == (window or 0)
    # the band of 512 keys is one compute tile wide: a sixteenth of the
    # causal tiles and a few more, never all of them
    if window:
        assert sc.live_tiles < sc.tiles // 4


def test_selective_scan_compiles_at_the_cells_shape(one_chip):
    """Both scan kernels at 8192 tokens x 5120 channels x 16 states, bfloat16
    ``u`` and float32 ``delta``: scalars of B and C in SMEM blocks, five-
    dimensional token blocks, the backward's chunk + 1 states of VMEM scratch."""
    from ps_pytorch_tpu.ops.selective_scan import selective_scan
    bt, s, di, n = 1, 8192, 5120, 16

    def loss(*args):
        return jnp.sum(selective_scan(*args, interpret=False)[0]
                       .astype(jnp.float32))

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(
        arg((bt, s, di), jnp.bfloat16), arg((bt, s, di)), arg((di, n)),
        arg((bt, s, n)), arg((bt, s, n)), arg((di,))).compile().as_text()
    assert "ssm_scan_fwd" in text and "ssm_scan_bwd" in text
    assert f"f32[{bt},5,64,{n},8,128]" in text      # the boundary states kept


# qwen3next_s16384_1chip: the one full layer of the period, 16 query heads on
# 2 key/value heads of 256 at S=16384
def test_flash_at_head_dim_256_eight_heads_a_kv_head_compiles(one_chip):
    """Twice the widest head so far: a K/V head whole would be 16.8 MB of K
    and V, so the forward's schedule splits the keys in two and the backward's
    dQ comes back as four float32 partials."""
    from ps_pytorch_tpu.ops.flash_attention import (
        flash_attention, flash_schedule,
    )
    b, h, h_kv, s, d = 1, 16, 2, 16384, 256

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       interpret=False).astype(jnp.float32))

    def arg(heads):
        return jax.ShapeDtypeStruct((b, heads, s, d), jnp.bfloat16,
                                    sharding=one_chip)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        arg(h), arg(h_kv), arg(h_kv)).compile().as_text()
    assert "flash_fwd_" in text and "flash_bwd_dkv_" in text
    assert "flash_win_" not in text
    sc = flash_schedule(b * h, s, d, 2, True, bh_kv=b * h_kv)
    assert sc.group == 8 and sc.bwd_g == 1
    assert sc.grid == (2, 32, 2) and sc.dq_partials == 4
    assert f"f32[{sc.dq_partials},{b * h},{s},{d}]" in text


_HLO_ITEMSIZE = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "pred": 1,
                 "s8": 1, "u8": 1}
_HLO_SHAPE = re.compile(r"\b(" + "|".join(_HLO_ITEMSIZE) + r")\[([\d,]*)\]")


def _entry_ops(text):
    """[(name, opcode, is a Mosaic call, result shapes [(dtype, dims)],
    operand + output bytes)] of the ops of a compiled module's entry
    computation that move data (parameters, constants, bitcasts, tuples and
    the ``-done`` half of an asynchronous copy left out; a ``-start`` counts
    its destination)."""
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    size = lambda shapes: sum(
        _HLO_ITEMSIZE[t] * math.prod(int(d) for d in dims.split(",") if d)
        for t, dims in shapes)
    results, ops = {}, []
    for line in entry.splitlines()[1:]:
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([a-z][\w\-]*)\((.*)",
                     line)
        if not m:
            continue
        name, result, opcode, rest = m.groups()
        shapes = _HLO_SHAPE.findall(result)
        if opcode.endswith("-start"):
            shapes = shapes[:1]
        results[name] = shapes
        if opcode in ("parameter", "constant", "bitcast", "tuple",
                      "get-tuple-element") or opcode.endswith("-done"):
            continue
        operands = re.findall(r"%([\w.\-]+)", rest.split("), ")[0])
        ops.append((name, opcode, "tpu_custom_call" in rest, shapes,
                    size(shapes) + sum(size(results.get(o, ()))
                                       for o in operands)))
    return ops


def test_gated_delta_rule_compiles_at_the_cells_shape(one_chip):
    """The three kernels of ``ops/gated_delta_rule.py`` at 16384 tokens, 16
    key and 32 value heads of 128, bfloat16 q, k, v under float32 g and beta,
    forward and backward: ``gdr_solve``'s strided reads and 128 x 128
    transposes that lay 128 chunks' systems along the lanes and back, the
    substitution's register rows; in the walks a chunk's tensors formed in
    VMEM (the masked exponentials, the columns cut from rows, the transposed
    float32 products of the inverse's derivative), the decays as SMEM
    scalars, 64-row matmul operands, the kept states.

    And what XLA is left with round them: no op outside the Mosaic calls
    hands on a float32 ``[..., 64, 64]`` a chunk or a float32 ``[32, 16384,
    128]`` row copy, and one forward and backward move under 6 GB through
    HBM by the compiled module's own shapes, kernels and all (5.05 found:
    the four calls 0.2 + 1.1 + 0.2 + 1.4, the backward solving again, and
    the copies into and out of the kernels' layout 2.2).
    Before PR 40 the same count read 17.1 GB in 208 XLA ops beside 3.7 in
    four Mosaic calls, with ``_prepare`` and ``jax.vjp`` of it in XLA
    (PERF.md section 6; about 25 GB by the whole step's 89.5 GB over three
    layers and a recomputed forward)."""
    from ps_pytorch_tpu.ops.gated_delta_rule import (
        gated_delta_rule, gdr_schedule,
    )
    b, s, hk, hv, d = 1, 16384, 16, 32, 128

    def loss(*args):
        return jnp.sum(gated_delta_rule(*args, interpret=False)[0]
                       .astype(jnp.float32))

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(5)))).lower(
        arg((b, s, hk, d), jnp.bfloat16), arg((b, s, hk, d), jnp.bfloat16),
        arg((b, s, hv, d), jnp.bfloat16), arg((b, s, hv)),
        arg((b, s, hv))).compile()
    text = compiled.as_text()
    for name in ("gdr_solve", "gdr_fwd", "gdr_bwd"):
        assert f"{name}" in text, name
    assert "gdr_tril" not in text
    sc = gdr_schedule(b, s, hv, d, d, k_heads=hk, itemsize=2)
    assert f"f32[{b * hv},{sc.chunks},{d},{d}]" in text   # the entering states
    assert sc.kept_bytes == 512 * 2 ** 20

    ops = _entry_ops(text)
    mosaic = [op for op in ops if op[2]]
    # the schedule's byte counts are the calls' own operands and results
    # (but the counter's 2 MB of state maxima, which the schedule leaves
    # out); the backward solves again here (in the cell's step, under
    # per-block remat, XLA shares the recomputed forward's solve with it)
    assert sorted(op[4] for op in mosaic) == [
        sc.solve_bytes, sc.solve_bytes,
        sc.fwd_bytes + b * hv * d * d * 4, sc.bwd_bytes]
    chunk_wide = re.compile(r"(^|,)64,64$")
    for name, opcode, is_mosaic, shapes, _ in ops:
        if not is_mosaic:
            for dtype, dims in shapes:
                assert not (dtype == "f32" and (
                    chunk_wide.search(dims)
                    or dims == f"{b * hv},{s},{d}")), (name, opcode, dims)
    assert sum(op[4] for op in ops) < 6e9


def _mixer_chains_compiled(one_chip, conv, norm):
    """One forward and one backward of the mixer's two elementwise chains at
    the cell's shape (``B`` 1, ``S`` 16384, 16 key and 32 value heads of 128,
    4 taps, bfloat16), compiled for the described chip with every cotangent
    an argument; q, k, v, ``o`` and their cotangents cross the module's
    boundary head-major, as ``ops/gated_delta_rule.py`` takes and hands them
    (its ``heads_first``)."""
    b, s, hk, hv, d, taps = 1, 16384, 16, 32, 128, 4
    to_rule = lambda a: jnp.moveaxis(a, 2, 1)       # [B, S, H, d] -> [B, H, S, d]
    from_rule = lambda a: jnp.moveaxis(a, 1, 2)

    def both(qkv, w, o, z, scale, dq, dk, dv, dout):
        out, pull = jax.vjp(conv, qkv, w)
        gated, pull_norm = jax.vjp(
            lambda o, z, scale: norm(from_rule(o), z, scale), o, z, scale)
        return (tuple(map(to_rule, out)) + pull(tuple(map(from_rule,
                                                          (dq, dk, dv))))
                + (gated,) + pull_norm(dout))

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    c = (2 * hk + hv) * d
    return jax.jit(both).lower(
        arg((b, s, c)), arg((taps, c), jnp.float32), arg((b, hv, s, d)),
        arg((b, s, hv * d)), arg((d,), jnp.float32), arg((b, hk, s, d)),
        arg((b, hk, s, d)), arg((b, hv, s, d)),
        arg((b, s, hv * d))).compile().as_text()


def test_gdn_mix_compiles_at_the_cells_shape(one_chip):
    """The eight calls of ``ops/gdn_mix.py`` (the convolution chain's three
    segments each way, the gated norm each way) under Mosaic: blocks of 2048
    tokens of one head with the 16 rows of the tile before and after, the
    loop over 512 rows at a row offset known only as a multiple of eight, the
    sublane shifts of the taps, the lane reductions a head, the weight's and
    the scale's gradients summed in their output blocks across the grid.

    And what XLA is left with: no op outside the Mosaic calls hands on a
    float32 array a sequence long (a ``[16384, 8192]`` or ``[16384, 4096]``
    row copy, a shifted product), and one forward and backward of both
    chains move under 3.2 GB through HBM: the calls' blocks 2.43 GB by
    ``mix_schedule`` (the module's operand shapes would count ``qkv`` whole
    for every call that reads a quarter of it; the floor is 2.42) and XLA's
    ops by the module's own shapes 0.54, all of it ``d qkv``'s three segments
    written side by side, which in the cell's step the matmuls that read
    ``[dq | dk | dv | dz]`` fuse. The chain as ``jax.numpy`` ops on
    ``causal_conv1d`` and ``nn.RMSNorm`` (``tests/test_gdn_mix.py``; what
    ``models/gdn.py`` held until PR 41) reads 12.7 GB in 55 ops by this same
    harness."""
    from ps_pytorch_tpu.ops import gdn_mix
    b, s, hk, hv, d, taps = 1, 16384, 16, 32, 128, 4
    text = _mixer_chains_compiled(
        one_chip,
        lambda qkv, w: gdn_mix.conv_silu_l2norm(
            qkv, w, key_heads=hk, value_heads=hv, key_dim=d, value_dim=d,
            interpret=False),
        lambda o, z, scale: gdn_mix.gated_rms_norm(o, z, scale, eps=1e-6,
                                                   interpret=False))
    ops = _entry_ops(text)
    mosaic = [op for op in ops if op[2]]
    assert sorted(op[0].split(".")[0].split("jvp_")[-1].strip("_")
                  for op in mosaic) == sorted(
        [f"gdn_conv_{way}_{seg}" for way in ("fwd", "bwd") for seg in "qkv"]
        + ["gdn_norm_fwd", "gdn_norm_bwd"])
    # q, k, v leave the kernels as the delta rule reads them: no copy between
    assert f"bf16[{b},{hk},{s},{d}]" in text
    for name, opcode, is_mosaic, shapes, _ in ops:
        if not is_mosaic:
            for dtype, dims in shapes:
                assert not (dtype == "f32" and math.prod(
                    int(n) for n in dims.split(",") if n) >= s * 128), \
                    (name, opcode, dims)
    sc = gdn_mix.mix_schedule(b, s, hk, hv, d, taps, itemsize=2)
    in_kernels = (sc.conv_fwd_bytes + sc.conv_bwd_bytes + sc.norm_fwd_bytes
                  + sc.norm_bwd_bytes)
    assert 2.42e9 < in_kernels < 2.5e9
    assert in_kernels + sum(op[4] for op in ops if not op[2]) < 3.2e9


def test_ungated_expert_ffn_at_width_1856_compiles_at_the_cells_shape(
        one_chip, monkeypatch):
    """nemotron3nano_s16384_1chip: ``relu(x Wup^T)^2 Wdown`` over ragged
    groups and its gradients, 18,432 bfloat16 rows on 16 experts' float32
    weights, BOTH stored [16, 1856, 2688]: the six calls of ``gmm_t`` and
    ``gmm``. 1856 is no multiple of 128: it is a whole tile (two float32
    weight buffers of [1856, 2688] and their bfloat16 copy are 50 MB of the
    64 MiB limit), and no weight array is sliced along it by a DMA."""
    from ps_pytorch_tpu.ops.grouped_matmul import gmm_t
    monkeypatch.setattr(grouped_matmul, "interpret_default", lambda: False)
    rows, d, f, held = 18432, 2688, 1856, 16

    def loss(xs, wu, wd, gs):
        h = jnp.square(jax.nn.relu(gmm_t(xs, wu, gs)))
        return jnp.sum(gmm(h, wd, gs).astype(jnp.float32))

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        arg((rows, d), jnp.bfloat16), arg((held, f, d), jnp.float32),
        arg((held, f, d), jnp.float32), arg((held,), jnp.int32)).compile()
    text = compiled.as_text()
    for name in ("moe_gmm_fwd", "moe_gmm_dlhs", "moe_gmm_drhs"):
        assert text.count(f"{name}") >= 2, name
    assert _tiles(rows, d, f, jnp.bfloat16, jnp.float32) == (256, 896, f)
    assert 2 * d * f * 4 + d * f * 2 < VMEM_LIMIT_BYTES


# nemotron3nano_s16384_1chip: the one attention layer, 32 query heads on 2
# key/value heads of 128 at S=16384: 16 query heads a K/V head
def test_flash_at_sixteen_query_heads_a_kv_head_compiles(one_chip):
    """Twice the widest group so far (8 in Trinity and Qwen3-Next): the
    forward holds a K/V head whole and walks its 16 query heads, the backward
    puts the group along its grid; dK and dV come back at the 2 K/V heads."""
    from ps_pytorch_tpu.ops.flash_attention import (
        flash_attention, flash_schedule,
    )
    b, h, h_kv, s, d = 1, 32, 2, 16384, 128

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       interpret=False).astype(jnp.float32))

    def arg(heads):
        return jax.ShapeDtypeStruct((b, heads, s, d), jnp.bfloat16,
                                    sharding=one_chip)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        arg(h), arg(h_kv), arg(h_kv)).compile()
    text = compiled.as_text()
    for name in ("flash_fwd", "flash_bwd_dkv"):
        assert f"{name}_" in text
    assert "flash_win_" not in text
    assert f"bf16[{b * h_kv},{s},{d}]" in text
    sched = flash_schedule(b * h, s, d, 2, True, bh_kv=b * h_kv)
    assert (sched.group, sched.g, sched.window) == (16, 16, 0)


# ... and its four Mamba-2 layers: 64 heads of 64 with a [64, 128] state, B and
# C in 8 groups, chunks of 128
SSD_CELL = dict(b=1, s=16384, heads=64, p=64, groups=8, n=128)


def test_ssd_compiles_at_the_cells_shape(one_chip):
    """``ssd_fwd`` and ``ssd_bwd`` at the cell's shape, bfloat16 rows with a
    float32 dt: two heads share a slab's 128 lanes, eight a grid step; no XLA
    op round them hands on a float32 tensor a chunk wide ([.., 128, 128]
    masks or scores) or a copy of x, B or C in another layout, and one
    forward and backward move under 3.5 GB (the inputs, y, the gradients and
    the kept states, 268 MB of them)."""
    from ps_pytorch_tpu.ops.ssd import ssd, ssd_schedule
    c = SSD_CELL
    b, s, heads, p, groups, n = (c[k] for k in ("b", "s", "heads", "p",
                                                  "groups", "n"))

    def loss(x, dt, a, bb, cc, d):
        y, top = ssd(x, dt, a, bb, cc, d, interpret=False)
        return jnp.sum(y.astype(jnp.float32)) + top

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    f32 = jnp.float32
    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(
        arg((b, s, heads, p)), arg((b, s, heads), f32), arg((heads,), f32),
        arg((b, s, groups, n)), arg((b, s, groups, n)),
        arg((heads,), f32)).compile()
    text = compiled.as_text()
    sched = ssd_schedule(b, s, heads, p, n, groups)
    assert (sched.chunk, sched.chunks, sched.heads_a_step,
            sched.heads_a_slab) == (128, 128, 8, 2)
    assert sched.kept_bytes == 128 * 64 * 64 * 128 * 4      # 268 MB a layer
    ops = _entry_ops(text)
    mosaic = [op for op in ops if op[2]]
    assert ["ssd_fwd" in mosaic[0][0], "ssd_bwd" in mosaic[1][0],
            len(mosaic)] == [True, True, 2]
    # the schedule's byte counts are the calls' own operands and results (but
    # the counter's 2 MB of state maxima, which the schedule leaves out)
    assert [op[4] for op in mosaic] == [
        sched.fwd_bytes + b * heads * p * n * 4, sched.bwd_bytes]
    for name, opcode, is_mosaic, shapes, _ in ops:
        if not is_mosaic:       # no mask or score a chunk wide, no float32 row
            for dtype, dims in shapes:
                assert not (dtype == "f32" and (
                    dims.endswith(",128,128,128")
                    or dims.startswith(f"{b},{s},"))), (name, opcode, dims)
    assert sum(op[4] for op in ops) < 3.5e9
    assert compiled.memory_analysis().temp_size_in_bytes < 0.8 * 2 ** 30


def test_ssm_mix_compiles_at_the_cells_shape(one_chip):
    """The eight calls of ``ops/ssm_mix.py`` at the cell's shape (16384
    tokens, 6144 channels of ``xBC``, 8 groups of 512, 4 taps and a bias,
    bfloat16) under Mosaic: the convolution's kernels (``ops/gdn_mix.py``'s,
    here with the bias's row and outputs that leave token-major in blocks of
    one lane tile) over the segments x, B and C each way, and the gate with
    its group norm each way, a block 512 tokens of one group's four lane
    tiles, the scale's gradient summed in its output block.

    And what XLA is left with: no op outside the Mosaic calls hands on a
    float32 array a sequence long (a ``[16384, 6144]`` or ``[16384, 4096]``
    row copy, a shifted product), x, B and C leave the kernels as ``ssd``
    reads them, the norm's calls move exactly the schedule's bytes by their
    own operands, and one forward and backward of both chains move under 2.6
    GB: the calls' blocks 2.08 by ``ssm_mix_schedule`` (the floor is 2.07)
    and XLA's ops, by the module's own shapes, ``d xBC``'s three segments
    written side by side (0.4) and the parameters' rows."""
    from ps_pytorch_tpu.ops import ssm_mix
    b, s, d_inner, bc, groups, taps = 1, 16384, 4096, 1024, 8, 4
    c = d_inner + 2 * bc

    def both(xbc, w, bias, y, z, scale, dx, db, dc, dout):
        out, pull = jax.vjp(
            lambda *a: ssm_mix.conv_bias_silu(
                *a, widths=(d_inner, bc, bc), interpret=False), xbc, w, bias)
        normed, pull_norm = jax.vjp(
            lambda *a: ssm_mix.gated_group_norm(
                *a, groups=groups, eps=1e-5, interpret=False), y, z, scale)
        return out + pull((dx, db, dc)) + (normed,) + pull_norm(dout)

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows = lambda width: arg((b, s, width))
    text = jax.jit(both).lower(
        rows(c), arg((taps, c), jnp.float32), arg((c,), jnp.float32),
        rows(d_inner), rows(d_inner), arg((d_inner,), jnp.float32),
        rows(d_inner), rows(bc), rows(bc), rows(d_inner)).compile().as_text()
    ops = _entry_ops(text)
    mosaic = {op[0].split(".")[0].split("jvp_")[-1].strip("_"): op
              for op in ops if op[2]}
    assert sorted(mosaic) == sorted(
        [f"ssm_conv_{way}_{seg}" for way in ("fwd", "bwd") for seg in "xbc"]
        + ["ssm_norm_fwd", "ssm_norm_bwd"])
    sc = ssm_mix.ssm_mix_schedule(b, s, d_inner, bc, groups, taps, itemsize=2)
    assert mosaic["ssm_norm_fwd"][4] == sc.norm_fwd_bytes
    assert mosaic["ssm_norm_bwd"][4] == sc.norm_bwd_bytes
    # token-major, in the rows' dtype: a reshape away from what ssd reads
    assert mosaic["ssm_conv_fwd_x"][3] == [("bf16", f"{b},1,{s},{d_inner}")]
    assert mosaic["ssm_conv_fwd_b"][3] == [("bf16", f"{b},1,{s},{bc}")]
    for name, opcode, is_mosaic, shapes, _ in ops:
        for dtype, dims in shapes:
            assert not (dtype == "f32" and math.prod(
                int(n) for n in dims.split(",") if n) >= s * 128), \
                (name, opcode, dims)
    in_kernels = sum(sc[-4:])
    assert 2.07e9 < in_kernels < 2.07e9 * 1.01
    assert in_kernels + sum(op[4] for op in ops if not op[2]) < 2.6e9


def test_eva_attention_compiles_at_the_cells_shape(one_chip):
    """evabyte_s16384_1chip: 32 heads of 128 at S=16384, bfloat16, windows of
    2048 in chunks of 16: the pooling pair (512 tokens a step, the chunks'
    softmax as a [32, 512] matrix), the core's forward (a q tile of 512, its
    window's K/V and the head's 1024 summaries whole) and the one backward (a
    window a step, the summaries' float32 gradients resident a head) compile
    for a described v5e. What the calls move is the schedule's count, the
    core's backward hands the pooling's backward float32 ``[32, 1024, 128]``
    sums and no XLA op round the kernels holds a float32 ``[32, 16384, 128]``:
    dK and dV go from the core into the pooling's backward in bfloat16 and are
    added to there, in place."""
    from ps_pytorch_tpu.ops.eva_attention import eva_attention, eva_schedule
    b, h, s, d, window, chunk = 1, 32, 16384, 128, 2048, 16

    def loss(q, k, v, phi, mu):
        o, top = eva_attention(q, k, v, phi, mu, window=window, chunk=chunk,
                               interpret=False)
        return jnp.sum(o.astype(jnp.float32)) + top

    arg = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    rows = arg((b, h, s, d), jnp.bfloat16)
    vec = arg((h, d), jnp.float32)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        rows, rows, rows, vec, vec).compile()
    ops = _entry_ops(compiled.as_text())
    mosaic = {op[0].split(".")[0].split("jvp_")[-1].strip("_"): op
              for op in ops if op[2]}
    assert sorted(mosaic) == ["eva_bwd", "eva_fwd", "eva_pool_bwd",
                              "eva_pool_fwd"]
    sc = eva_schedule(b * h, s, d, 2, window, chunk)
    assert (sc.block_q, sc.block_s, sc.pool_rows) == (512, 128, 512)
    assert mosaic["eva_fwd"][4] == sc.fwd_bytes
    assert mosaic["eva_bwd"][4] == sc.bwd_bytes
    sums = ("f32", f"{b * h},{s // chunk},{d}")
    assert mosaic["eva_bwd"][3].count(sums) == 2
    # the pooling's calls also move phi, mu, the step's largest weight and
    # dphi's row a step (an [8, 128] float32 tile each, 4 MiB a call): under
    # a fiftieth of the rows
    assert sc.pool_fwd_bytes <= mosaic["eva_pool_fwd"][4] \
        < 1.02 * sc.pool_fwd_bytes
    assert sc.pool_bwd_bytes <= mosaic["eva_pool_bwd"][4] \
        < 1.02 * sc.pool_bwd_bytes
    for name, opcode, is_mosaic, shapes, _ in ops:
        for dtype, dims in shapes:
            assert not (dtype == "f32" and math.prod(
                int(n) for n in dims.split(",") if n) >= h * s * d), \
                (name, opcode, dims)
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < 2 ** 30


def test_tiles_keep_the_weight_buffers_inside_their_budget():
    """tn follows from K: two float32 [K, tn] buffers and the bfloat16 copy
    stay under WEIGHTS_VMEM_BYTES, whatever the table's target."""
    assert _tiles(M, D, F, jnp.bfloat16, jnp.float32) == (256, 2048, 1024)
    assert _tiles(M, F, D, jnp.bfloat16, jnp.float32) == (256, 1024, 2048)
    for k in (2048, 4096, 8192, 16384):
        for rows in (jnp.bfloat16, jnp.float32):
            tm, tk, tn = _tiles(M, k, 4096, rows, jnp.float32)
            cast = jnp.dtype(rows).itemsize if rows != jnp.float32 else 0
            assert tn % 128 == 0 and 4096 % tn == 0
            assert k * tn * (2 * 4 + cast) <= WEIGHTS_VMEM_BYTES \
                < VMEM_LIMIT_BYTES
    # without weights to hold (moe_gmm_drhs) the table's target stands
    assert _tiles(M, 8192, 4096, jnp.bfloat16) == (256, 2048, 2048)


# granite4h_small_tp8_1chip (S=8192): ONE chip's eighth of every layer. The attention
# layer's 4 query heads on 1 key/value head of 128 at the scores' own scale
# 1/128 ...
def test_flash_at_a_share_of_four_heads_on_one_kv_head_compiles(one_chip):
    """A share's heads: the forward holds the one K/V head whole and walks
    its 4 query heads, the backward puts them along its grid; the scale is
    the caller's (1/128, not 128 ** -0.5), which changes no schedule."""
    from ps_pytorch_tpu.ops.flash_attention import (
        flash_attention, flash_schedule,
    )
    b, h, h_kv, s, d = 1, 4, 1, 8192, 128

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, scale=1 / 128,
                                       interpret=False).astype(jnp.float32))

    def arg(heads):
        return jax.ShapeDtypeStruct((b, heads, s, d), jnp.bfloat16,
                                    sharding=one_chip)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        arg(h), arg(h_kv), arg(h_kv)).compile().as_text()
    for name in ("flash_fwd", "flash_bwd_dkv"):
        assert f"{name}_" in text
    assert f"bf16[{b * h_kv},{s},{d}]" in text
    sched = flash_schedule(b * h, s, d, 2, True, bh_kv=b * h_kv)
    assert (sched.group, sched.g, sched.window) == (4, 4, 0)


# ... its nine Mamba-2 layers' 16 of 128 heads of 64 with a [64, 128] state, B
# and C in ONE group, chunks of 256 ...
GRANITE_SSD = dict(b=1, s=8192, heads=16, p=64, groups=1, n=128, chunk=256)


def test_ssd_compiles_at_a_share_of_sixteen_heads_in_one_group(one_chip):
    """``ssd_fwd`` and ``ssd_bwd`` at the share's shape and the published
    chunk of 256: all 16 heads read the one group's B and C, two heads share
    a slab's 128 lanes; 32 chunks of the cell's S=8192, each keeping 16
    entering states of [64, 128] float32."""
    from ps_pytorch_tpu.ops.ssd import ssd, ssd_schedule
    c = GRANITE_SSD
    b, s, heads, p, groups, n, chunk = (c[k] for k in (
        "b", "s", "heads", "p", "groups", "n", "chunk"))

    def loss(x, dt, a, bb, cc, d):
        y, top = ssd(x, dt, a, bb, cc, d, chunk=chunk, interpret=False)
        return jnp.sum(y.astype(jnp.float32)) + top

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    f32 = jnp.float32
    compiled = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(
        arg((b, s, heads, p)), arg((b, s, heads), f32), arg((heads,), f32),
        arg((b, s, groups, n)), arg((b, s, groups, n)),
        arg((heads,), f32)).compile()
    sched = ssd_schedule(b, s, heads, p, n, groups, chunk=chunk)
    print("GRANITE_SSD", sched.describe())
    assert (sched.chunk, sched.chunks, sched.heads_a_slab) == (256, 32, 2)
    assert sched.kept_bytes == 32 * heads * p * n * 4       # 16.8 MB a layer
    ops = _entry_ops(compiled.as_text())
    mosaic = [op for op in ops if op[2]]
    assert ["ssd_fwd" in mosaic[0][0], "ssd_bwd" in mosaic[1][0],
            len(mosaic)] == [True, True, 2]
    assert [op[4] for op in mosaic] == [
        sched.fwd_bytes + b * heads * p * n * 4, sched.bwd_bytes]


def test_ssm_mix_compiles_at_a_share_in_one_group(one_chip):
    """The eight calls of ``ops/ssm_mix.py`` at the share's shape: 1,280
    channels of ``xBC`` (x 1,024, B and C 128 each: a lane tile a segment of
    B or C), and the gated norm over ONE group 1,024 wide, eight lane tiles a
    block (the Nemotron cell's groups are four)."""
    from ps_pytorch_tpu.ops import ssm_mix
    b, s, d_inner, bc, groups, taps = 1, 8192, 1024, 128, 1, 4
    c = d_inner + 2 * bc

    def both(xbc, w, bias, y, z, scale, dx, db, dc, dout):
        out, pull = jax.vjp(
            lambda *a: ssm_mix.conv_bias_silu(
                *a, widths=(d_inner, bc, bc), interpret=False), xbc, w, bias)
        normed, pull_norm = jax.vjp(
            lambda *a: ssm_mix.gated_group_norm(
                *a, groups=groups, eps=1e-5, interpret=False), y, z, scale)
        return out + pull((dx, db, dc)) + (normed,) + pull_norm(dout)

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows = lambda width: arg((b, s, width))
    text = jax.jit(both).lower(
        rows(c), arg((taps, c), jnp.float32), arg((c,), jnp.float32),
        rows(d_inner), rows(d_inner), arg((d_inner,), jnp.float32),
        rows(d_inner), rows(bc), rows(bc), rows(d_inner)).compile().as_text()
    ops = _entry_ops(text)
    mosaic = {op[0].split(".")[0].split("jvp_")[-1].strip("_"): op
              for op in ops if op[2]}
    assert sorted(mosaic) == sorted(
        [f"ssm_conv_{way}_{seg}" for way in ("fwd", "bwd") for seg in "xbc"]
        + ["ssm_norm_fwd", "ssm_norm_bwd"])
    sc = ssm_mix.ssm_mix_schedule(b, s, d_inner, bc, groups, taps, itemsize=2)
    print("GRANITE_SSM_MIX", sc.describe())
    assert (sc.lanes, sc.norm_lanes, sc.norm_grid[1]) == (128, 1024, 1)
    assert mosaic["ssm_norm_fwd"][4] == sc.norm_fwd_bytes
    assert mosaic["ssm_norm_bwd"][4] == sc.norm_bwd_bytes
    for name, opcode, is_mosaic, shapes, _ in ops:
        for dtype, dims in shapes:
            assert not (dtype == "f32" and math.prod(
                int(n) for n in dims.split(",") if n) >= s * 128), \
                (name, opcode, dims)


# ... and its ten expert halves: 15,360 rows sized for 10,240 at balance over
# 9 held experts of 4096 x 768
def test_expert_ffn_at_nine_held_experts_of_4096_by_768_compiles(
        one_chip, monkeypatch):
    """The gated grouped matmuls at the widest model dimension so far (4096;
    2048 in the other held cells) and 1,138 rows a group at balance."""
    monkeypatch.setattr(grouped_matmul, "interpret_default", lambda: False)
    m, d, f, e = 15360, 4096, 768, 9

    def loss(xs, wg, wu, wd, gs):
        h = jax.nn.silu(gmm(xs, wg, gs)) * gmm(xs, wu, gs)
        return jnp.sum(gmm(h, wd, gs).astype(jnp.float32))

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        arg((m, d), jnp.bfloat16), arg((e, d, f), jnp.float32),
        arg((e, d, f), jnp.float32), arg((e, f, d), jnp.float32),
        arg((e,), jnp.int32)).compile().as_text()
    for name in ("moe_gmm_fwd", "moe_gmm_dlhs", "moe_gmm_drhs"):
        assert name in text


# (tokens, top-k, held experts, d, rows the layer sizes) of the five cells
# that hold a share of their experts
ROWS_CELLS = {
    "granite4h_small_tp8_1chip": (8192, 10, 9, 4096, 15360),
    "smallthinker_s16384_1chip": (16384, 6, 16, 2560, 36864),
    "qwen3next_s16384_1chip": (16384, 10, 64, 2048, 30720),
    "nemotron3nano_s16384_1chip": (16384, 6, 16, 2688, 18432),
    "trinity_mini_s8192_1chip": (16384, 8, 16, 2048, 24576),
}


@pytest.mark.parametrize("cell", sorted(ROWS_CELLS))
def test_the_held_rows_kernel_compiles_at_the_cells_shape(one_chip,
                                                          monkeypatch, cell):
    """``ops/moe_rows.py``: a held share's take and combine, forward and
    backward, as ``DroplessMoE.part`` calls them on the main part's rows in
    bfloat16: ``moe_rows_sum`` with the gates (the combine) and with none
    (the take's transpose), its DMAs at multiples of 8 rows, d = 2688 in
    seven blocks of 384 lanes, the runs' starts and its own lists in SMEM, all under
    ``VMEM_LIMIT_BYTES``; and no scatter makes a ``[., d]`` array."""
    from ps_pytorch_tpu.ops import moe_rows
    monkeypatch.setattr(moe_rows, "interpret_default", lambda: False)
    t, k, held, d, rows = ROWS_CELLS[cell]
    sched = moe_rows.rows_schedule(rows, rows * 2 // 3, t, k, d,
                                   jnp.bfloat16, held)

    def loss(tokens, gates, idx, local, has, sizes, count):
        place, lo = moe_rows.held_places(has, sizes, sched.tokens_tile)
        plan = moe_rows.rows_plan(has, place, lo, start=0, count=count)
        xs = moe_rows.take(tokens, idx, plan, k, sched)
        y = moe_rows.combine(xs * 2, gates, jnp.ones((t, d)), local, idx,
                             plan, sched, jnp.bfloat16)
        return jnp.sum(y.astype(jnp.float32))

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        arg((t, d), jnp.bfloat16), arg((t, k), jnp.float32),
        arg((rows,), jnp.int32), arg((t, k), jnp.int32),
        arg((t, held), jnp.bool_), arg((held,), jnp.int32),
        arg((), jnp.int32)).compile().as_text()
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                          text)) >= 2 and "moe_rows_sum" in text
    made = re.findall(r"= (\w+\[[\d,]*\])\S* scatter\(", text)
    assert all("," not in shape for shape in made), made
