"""One suite for every ``--lm-arch``: an architecture is a row (``ArchCase``)
and a thin ``tests/test_arch_<name>.py`` that calls ``install(globals(),
CASE)`` and adds the cases only that architecture has.

Not collected itself. What it gives a file:

- the row type: the arch's name, its tiny preset in the reference's (the
  published config's) keys and as ``TrainConfig`` fields, the reference,
  controls and config files under ``benchmark/``, the logit tolerance with its
  reason, the ``ARCHS`` row's tiny sizes, the counters the step returns, the
  device scopes the compiled step holds, the entry points that refuse the arch;
- builders cached for the process, so that whatever is expensive is built
  once a file: ``tiny`` (model, unsettled variables, tokens), ``logits``,
  ``reference_logits``, ``reference_grads``, ``grads``, and ``step(case,
  remat)``, which is ``LMTrainer`` on the tiny ``TrainConfig`` with its step
  lowered and compiled ONCE (the texts kept for the device-scope cases, the
  executable handed to everything that runs a step, the trainer's own loop
  included), ``first_step`` and ``trained`` on top of it;
- ``install``: the cases every row runs, written once.
"""

import atexit
import contextlib
import dataclasses
import importlib.util
import io
import json
import pathlib
import re
import shutil
import sys
import tempfile
import types
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ps_pytorch_tpu.config import LM_ARCHS, TrainConfig
from ps_pytorch_tpu.models import transformer as tr_mod
from ps_pytorch_tpu.models.moe import (
    BIAS_STATS, DROPLESS_STATS, LOAD_ALL_STAT, MOE_STATE,
)
from ps_pytorch_tpu.models.transformer import ARCHS, refuse_hybrid
from ps_pytorch_tpu.telemetry.trace import DEVICE_SCOPES

REPO = pathlib.Path(__file__).resolve().parent.parent

# What every tiny trainer runs under: momentum and weight decay on, so that
# one build serves the step that descends, the one that leaves a bias alone
# and the run that resumes.
LR, MOMENTUM, WEIGHT_DECAY = 0.05, 0.9, 0.01
TRAINER = dict(batch_size=2, lr=LR, momentum=MOMENTUM,
               weight_decay=WEIGHT_DECAY, log_every=1, lm_attention="flash",
               compute_dtype="float32", lm_corpus_tokens=20_000, donate=False,
               max_steps=3, eval_freq=3)

# The device scopes (telemetry/trace.py:DEVICE_SCOPES) by what uses them.
LM_SCOPES = frozenset({"embed", "attn_proj", "attn_pos", "attn_core", "head",
                       "loss", "grad_reduce", "optimizer"})
EXPERT_SCOPES = frozenset({"moe_route", "moe_dispatch", "moe_experts"})
# What no gradient passes through has no backward twin.
NO_BACKWARD = {"grad_reduce", "optimizer", "router_bias"}
HEAVY = {"dot", "convolution", "custom-call", "scatter", "gather", "sort"}


def load(path):
    """A module under ``benchmark/`` or ``tests/`` by its file, as the harness
    loads a reference: no package, no import of its neighbours."""
    path = pathlib.Path(path)
    spec = importlib.util.spec_from_file_location(path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass(frozen=True, eq=False)
class ArchCase:
    """One architecture's tiny case."""
    arch: str               # --lm-arch
    parallelism: str        # sp | ep: the one mode the arch trains under
    config: str             # benchmark/configs/<config>.json, benchmark/reference/<config>.py
    tiny: dict              # the tiny preset: overrides of the published config, in its keys
    flags: dict             # the same preset as TrainConfig fields
    logit_tol: float
    tol_reason: str         # one line: what the tolerance is made of
    controls: bool = False  # benchmark/controls/<config>.py holds planted mistakes
    margins: tuple = (25, 5)    # tolerances a planted mistake is off by at least: in the mathematics, in a precision
    row: dict = dataclasses.field(default_factory=dict)     # the ARCHS row's tiny sizes
    share: int = 0          # which block of held experts (TrainConfig cannot say: 0 there)
    unsettle: Optional[Callable] = None     # params -> params, after every vector leaf moved
    counters: dict = dataclasses.field(default_factory=dict)   # name -> (low, high) a step returns
    scopes: frozenset = LM_SCOPES           # the device scopes of the compiled step
    remat_scopes: frozenset = frozenset()   # a block's interior, recomputed under --remat
    another_depth: int = 1  # a depth a checkpoint of this one's is refused at
    pred_heads: int = 1     # prediction heads: logits [B, S, pred_heads, V] where > 1
    refusals: tuple = ()    # (id, entry(case, tmp_path), words the message must hold)
    published_row: dict = dataclasses.field(default_factory=dict)  # ARCHS field -> published key
    shares: Optional[Callable] = None       # side -> (parts, uncut): rows with held experts
    published_arch_row: tuple = dataclasses.field(init=False, repr=False)

    @property
    def published(self):
        return _once(("published", self.arch), lambda: json.loads(
            (REPO / "benchmark" / "configs" / f"{self.config}.json").read_text()))

    @property
    def reference(self):
        return _once(("reference", self.arch), lambda: load(
            REPO / "benchmark" / "reference" / f"{self.config}.py"))

    @property
    def planted(self):
        return _once(("controls", self.arch), lambda: load(
            REPO / "benchmark" / "controls" / f"{self.config}.py"))

    @property
    def tiny_config(self):
        """The reference's config at the tiny size."""
        return dict(self.published, **self.tiny)

    @property
    def step_config(self):
        """... as ``TrainConfig`` can say it: the first block of held experts."""
        c = self.tiny_config
        return dict(c, experts_share=0) if "experts_share" in c else c

    def __post_init__(self):
        # the row as published, before any test patches the tiny one in
        object.__setattr__(self, "published_arch_row", ARCHS[self.arch])

    @property
    def tiny_row(self):
        return self.published_arch_row._replace(**self.row)

    @property
    def seq_len(self):
        return self.flags["lm_seq_len"]

    @property
    def vocab(self):
        return self.flags["lm_vocab"]

    def train_config(self, **kw):
        return TrainConfig(**{**TRAINER, "lm_arch": self.arch,
                              "lm_parallelism": self.parallelism,
                              **self.flags, **kw})

    @contextlib.contextmanager
    def patched(self):
        """The arch's sizes are its ``ARCHS`` row's, not flags: the tiny size
        takes a row with small ones (a window that closes at the tiny S, few
        linear-attention heads). Read when a model is traced."""
        before = tr_mod.ARCHS[self.arch]
        tr_mod.ARCHS[self.arch] = self.tiny_row
        try:
            yield
        finally:
            tr_mod.ARCHS[self.arch] = before


_CACHE = {}


def _once(key, build):
    if key not in _CACHE:
        _CACHE[key] = build()
    return _CACHE[key]


@contextlib.contextmanager
def one_device():
    """``LMTrainer`` takes every device there is; the tiny step is one chip's."""
    real = jax.devices
    one = real()[:1]
    jax.devices = lambda *a, **k: one
    try:
        yield
    finally:
        jax.devices = real


_SCRATCH = []


def _scratch_dir():
    if not _SCRATCH:
        _SCRATCH.append(tempfile.mkdtemp(prefix="arch_suite_"))
        atexit.register(shutil.rmtree, _SCRATCH[0], ignore_errors=True)
    return pathlib.Path(_SCRATCH[0])


def unsettled(tree, key):
    """Every vector leaf (norm scales and offsets, a bias, A_log) moved off
    its 0 or 1, so that one left out or applied in the wrong place shows."""
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, [
        a + 0.2 * jax.random.normal(k, a.shape) if a.ndim == 1 else a
        for a, k in zip(leaves, keys)])


# ---- builders -----------------------------------------------------------------

def tiny(case):
    """(model, variables, tokens): the model ``build_lm_model`` makes of the
    tiny ``TrainConfig`` (dense attention, the row's share of experts), seeded
    weights unsettled, two sequences of seeded tokens."""
    def build():
        from ps_pytorch_tpu.runtime.lm_eval import build_lm_model
        model = build_lm_model(case.train_config(), attention_impl="full")
        if case.share:
            model = model.clone(experts_share=case.share)
        tokens = jnp.asarray(np.random.default_rng(1).integers(
            0, case.vocab, (2, case.seq_len)), jnp.int32)
        with case.patched():
            variables = dict(jax.jit(model.init)(jax.random.key(0), tokens))
        variables = unsettled(variables, jax.random.key(5))
        if case.unsettle is not None:
            variables["params"] = case.unsettle(variables["params"])
        return model, variables, tokens
    return _once(("tiny", case.arch), build)


def apply_logits(model, variables, tokens):
    """The logits alone, whichever LM class."""
    out = model.apply(variables, tokens)
    return out[0] if isinstance(out, tuple) else out


def forward(case, attention="full"):
    """``tiny``'s model as one jitted function (variables, tokens) -> what
    ``apply`` returns: one compiled program an attention implementation."""
    return _once(("forward", case.arch, attention), lambda: jax.jit(
        tiny(case)[0].clone(attention_impl=attention).apply))


def logits(case, attention="full"):
    """(logits, the expert layers' statistics or None) of ``tiny``."""
    def build():
        _, variables, tokens = tiny(case)
        with case.patched():
            out = forward(case, attention)(variables, tokens)
        return out if isinstance(out, tuple) else (out, None)
    return _once(("logits", case.arch, attention), build)


def reference_forward(case, variables, tokens, reference=None, config=None):
    """The reference's logits as one compiled program (taken eagerly, its
    token-by-token scans and loops over heads and experts compile one by one)."""
    ref = reference or case.reference
    config = config or case.tiny_config
    return jax.jit(lambda v, t: ref.forward(v, t, config))(variables, tokens)


def reference_logits(case):
    return _once(("reference_logits", case.arch), lambda: reference_forward(
        case, *tiny(case)[1:]))


def reference_loss(case, variables, tokens, config=None):
    """The reference's training loss: its own ``loss`` where it has one (the
    routing terms with the coefficients the configuration states), else the
    next-token cross-entropy of its logits."""
    ref, config = case.reference, config or case.tiny_config
    if hasattr(ref, "loss"):
        return ref.loss(variables, tokens, config)
    logp = jax.nn.log_softmax(ref.forward(variables, tokens, config)[:, :-1])
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))


def reference_grads(case):
    """(loss, gradients in every parameter) of the reference at ``tiny``, at
    the share of experts a step holds."""
    def build():
        _, variables, tokens = tiny(case)
        rest = {k: v for k, v in variables.items() if k != "params"}
        return jax.jit(jax.value_and_grad(lambda p: reference_loss(
            case, {**rest, "params": p}, tokens, case.step_config)))(
                variables["params"])
    return _once(("reference_grads", case.arch), build)


def model_loss(model, variables, tokens):
    logp = jax.nn.log_softmax(
        apply_logits(model, variables, tokens).astype(jnp.float32)[:, :-1])
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))


def grads(case, remat):
    """(cross-entropy, its gradient in every parameter) of the model at
    ``tiny``, with or without per-block remat: one compiled program each."""
    def build():
        model, variables, tokens = tiny(case)
        model = model.clone(remat=remat)
        rest = {k: v for k, v in variables.items() if k != "params"}
        with case.patched():
            return jax.jit(jax.value_and_grad(lambda p: model_loss(
                model, {**rest, "params": p}, tokens)))(variables["params"])
    return _once(("grads", case.arch, remat), build)


class BuiltStep:
    """A trainer's jitted step with its one compilation. Called with arrays it
    runs that executable; traced (``forward_flops``) it is the jitted
    function, whose trace is cached."""

    def __init__(self, jitted, state, tokens):
        self.jitted = jitted
        lowered = jitted.lower(state, tokens)
        self.stablehlo_text = lowered.as_text()     # no locations: no scopes
        self.lowered_text = lowered.as_text(dialect="hlo", debug_info=True)
        self.compiled = lowered.compile()
        self.compiled_text = self.compiled.as_text()

    def __call__(self, state, tokens):
        if isinstance(tokens, jax.core.Tracer):
            return self.jitted(state, tokens)
        return self.compiled(state, tokens)


def _new_trainer(case, cfg):
    """-> (LMTrainer, its KERNELS line)."""
    from ps_pytorch_tpu.runtime.lm_trainer import LMTrainer
    out = io.StringIO()
    with case.patched(), one_device(), contextlib.redirect_stdout(out):
        trainer = LMTrainer(cfg)
    kernels = [line for line in out.getvalue().splitlines()
               if line.startswith("KERNELS")]
    return trainer, (kernels[0] if kernels else "")


def place(trainer, tokens):
    from ps_pytorch_tpu.parallel import dist
    return dist.globalize_replicated(trainer.mesh, np.asarray(tokens),
                                     spec=trainer._token_spec())


def step(case, remat):
    """The arch's whole tiny train step, built once a remat setting:
    ``LMTrainer`` on the tiny ``TrainConfig`` -> namespace(trainer, step_fn,
    kernels, cfg). ``step_fn`` is the trainer's too."""
    def build():
        train_dir = _scratch_dir() / f"{case.arch}_remat_{remat}"
        train_dir.mkdir()
        cfg = case.train_config(
            remat=remat, train_dir=str(train_dir),
            metrics_file=str(train_dir / "metrics.jsonl"))
        trainer, kernels = _new_trainer(case, cfg)
        with case.patched(), one_device():
            trainer.step_fn = BuiltStep(
                trainer.step_fn, trainer.state,
                place(trainer, np.zeros((cfg.batch_size, cfg.lm_seq_len),
                                        np.int32)))
        return types.SimpleNamespace(trainer=trainer, step_fn=trainer.step_fn,
                                     kernels=kernels, cfg=cfg)
    return _once(("step", case.arch, remat), build)


def tiny_state(case, remat):
    """The trainer's state with ``tiny``'s unsettled parameters (and bias) in
    it and a fresh optimizer state."""
    built = step(case, remat)
    _, variables, _ = tiny(case)
    state = built.trainer.state
    assert jax.tree.structure(state.params) \
        == jax.tree.structure(variables["params"])
    return state.replace(params=variables["params"],
                         batch_stats=variables.get(MOE_STATE, {}))


def first_step(case, remat):
    """(state, new state, metrics) of one step from ``tiny_state`` on
    ``tiny``'s tokens."""
    def build():
        built = step(case, remat)
        state = tiny_state(case, remat)
        with case.patched(), one_device():
            new, metrics = built.step_fn(
                state, place(built.trainer, tiny(case)[2]))
        return state, new, jax.device_get(metrics)
    return _once(("first_step", case.arch, remat), build)


def _lowered_again(case, remat, tag, modules, name, value):
    """The step of ``step(case, remat)`` built a second time with ``name``
    patched to ``value`` in ``modules`` (each binds the function by name),
    lowered and never compiled: the one extra build of a patched form."""
    built = step(case, remat)
    tokens = place(built.trainer, np.zeros(
        (built.cfg.batch_size, built.cfg.lm_seq_len), np.int32))
    with pytest.MonkeyPatch.context() as patch:
        for mod in modules:
            patch.setattr(mod, name, value)
        trainer, _ = _new_trainer(case, built.cfg.replace(
            train_dir=str(_scratch_dir() / f"{case.arch}_{tag}"),
            metrics_file=""))
        with case.patched(), one_device():
            return trainer.step_fn.lower(trainer.state, tokens)


def unscoped_lowering(case):
    """(the remat step as lowered, the same step built with ``device_scope``
    patched to nothing and lowered, that lowering with every op's name). The
    first two are texts without locations, which is all a scope adds."""
    def build():
        from ps_pytorch_tpu.models import gdn, moe, ssm
        from ps_pytorch_tpu.ops import eva_attention
        from ps_pytorch_tpu.parallel import dp, ep, sp
        lowered = _lowered_again(
            case, True, "unscoped",
            (tr_mod, moe, ssm, gdn, eva_attention, dp, sp, ep),
            "device_scope", lambda name: contextlib.nullcontext())
        return (step(case, True).step_fn.stablehlo_text, lowered.as_text(),
                lowered.as_text(dialect="hlo", debug_info=True))
    return _once(("unscoped", case.arch), build)


def unnamed_lowering(case):
    """(the step WITHOUT remat as lowered, the same step built with ``kept``
    patched to hand its argument back and lowered): texts without locations,
    each function's symbol replaced by the order it first appears in
    (``@_where_94``: JAX numbers the functions it outlines by a count that a
    name moves). The second is the form the step had before a block's
    interior carried names."""
    def build():
        from ps_pytorch_tpu.models import moe, ssm
        lowered = _lowered_again(
            case, False, "unnamed", (tr_mod, moe, ssm), "kept",
            lambda x, name: x)
        return tuple(in_order_of_appearance(r"@[\w.\-]+", text) for text in (
            step(case, False).step_fn.stablehlo_text, lowered.as_text()))
    return _once(("unnamed", case.arch), build)


def trained(case):
    """The remat trainer run to step 3 and a checkpoint, a second trainer that
    resumed from it and went on to step 6: (first, resumed, first's state at
    step 3 on the host, resumed's as restored, the six records). The second
    trainer runs the first's executable: the step is a function of the
    config, which they share."""
    def build():
        from ps_pytorch_tpu.runtime.lm_trainer import LMTrainer
        built = step(case, True)
        first = built.trainer
        with case.patched(), one_device():
            first.train()
            resumed = LMTrainer(built.cfg.replace(max_steps=6, eval_freq=0))
            resumed.step_fn = built.step_fn
            assert resumed.maybe_resume() and resumed.start_step == 3
            saved, restored = jax.device_get((first.state, resumed.state))
            resumed.train()
        records = [json.loads(line) for line in pathlib.Path(
            built.cfg.metrics_file).read_text().splitlines()]
        return first, resumed, saved, restored, records
    return _once(("trained", case.arch), build)


# ---- reading a compiled step, by the benchmark's rule -------------------------

def _reader():
    """``benchmark/readers/device_scopes.py`` (it imports its neighbour
    ``trace_reduce`` by name)."""
    bench = str(REPO / "benchmark")
    sys.path.insert(0, bench)
    try:
        return load(REPO / "benchmark" / "readers" / "device_scopes.py")
    finally:
        sys.path.remove(bench)


INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = .*?\s([a-z][\w\-]*)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')
# What differs between two compilations of one program: each op's metadata
# and the tables of source locations it points into.
METADATA = re.compile(
    r",? ?metadata=\{[^}]*\}|"
    r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n.*?\n\n",
    re.M | re.S)
# The partitioner's own annotations at a shard_map's edge: no work.
ANNOTATION = re.compile(r'custom_call_target="xla\.sdy\.\w+Shape"')


def in_order_of_appearance(pattern, text):
    """``text`` with each distinct match of ``pattern`` (a sigil and a name)
    replaced by the sigil and the order the name first appears in."""
    ids = {}
    return re.sub(pattern, lambda m: ids.setdefault(
        m.group(0), f"{m.group(0)[0]}{len(ids)}"), text)


def program(text):
    """A compiled text without its metadata, every name (``%fusion.12``: JAX
    derives some from the name stack) replaced by the order it first appears
    in: equal for two compilations of one program."""
    return in_order_of_appearance(r"%[\w.\-]+", METADATA.sub("", text))


def scope_of(op_name):
    """(scope or None, part) of a JAX name stack, by the reader's rule."""
    reader = _once("reader", _reader)
    scope, part = reader.scope_of(op_name, DEVICE_SCOPES)
    return (None if scope == reader.UNSCOPED else scope), part


def named_ops(text):
    """[(opcode, op_name)] of the instructions of an HLO text that carry an
    ``op_name``."""
    out = []
    for line in text.splitlines():
        m, name = INSTRUCTION.match(line), OP_NAME.search(line)
        if m and name and not ANNOTATION.search(line):
            out.append((m.group(1), name.group(1)))
    return out


def check_scopes(lowered, compiled, uses, remat, inner=frozenset()):
    """Every scope of ``uses`` is in the step, forward and backward (and a
    recomputed forward under remat), and no other."""
    lowered, text = ({scope_of(name) for _, name in named_ops(t)}
                     for t in (lowered, compiled))
    # The CPU compiler drops the metadata of ops it rewrites (GPT-2's
    # ``to_heads`` transposes, all there is of its ``attn_pos``): the set is
    # held on both texts together, the compiled one to the scopes every step
    # has work under.
    found = lowered | text
    assert {s for s, _ in found if s} == set(uses)
    assert {"head", "loss", "optimizer"} <= {s for s, _ in text} \
        <= set(uses) | {None}
    for scope in set(uses) - NO_BACKWARD:
        assert (scope, "forward") in found and (scope, "backward") in found, \
            scope
    assert remat == any(part == "recompute" for _, part in found)
    if remat:       # a block's interior
        assert {("attn_proj", "recompute")} \
            | {(scope, "recompute") for scope in inner} <= found


def check_heavy_ops(lowered, compiled):
    """In the compiled text an op may have lost its metadata to the CPU
    compiler (a dot it rewrote), and in the lowered text an op inside a called
    function (an interpreted kernel's body) has a name relative to the call:
    what either text names in full has to be scoped."""
    heavy = [(op, name) for op, name in named_ops(compiled) if op in HEAVY] + \
        [(op, name) for op, name in named_ops(lowered)
         if op in HEAVY and name.startswith("jit(")]
    assert len(heavy) > 10
    assert [(op, name) for op, name in heavy if scope_of(name)[0] is None] \
        == []


# ---- entry points that refuse an arch ------------------------------------------

def _tiny_checkpoint(case, tmp_path):
    from ps_pytorch_tpu.runtime import checkpoint as ckpt
    network = "MoETransformerLM" if case.parallelism == "ep" \
        else "TransformerLM"
    cfg = case.train_config(network=network, train_dir=str(tmp_path))
    ckpt.save_checkpoint(cfg.train_dir, 1, {"x": jnp.zeros((1,))},
                         config_json=cfg.to_json())
    return cfg


def by_generate(case, tmp_path):
    import generate
    cfg = _tiny_checkpoint(case, tmp_path)
    generate.main(["--train-dir", cfg.train_dir, "--prompt", "ab"])


def by_serve(case, tmp_path):
    import serve
    cfg = _tiny_checkpoint(case, tmp_path)
    serve.main(["--train-dir", cfg.train_dir, "--serve-port", "0"])


def _dense(case):
    from ps_pytorch_tpu.models.transformer import TransformerLM
    return TransformerLM(vocab_size=case.vocab, n_layers=4, n_heads=4,
                         d_model=32, arch=case.arch)


def by_tp(case, tmp_path):
    from ps_pytorch_tpu.parallel.tp import make_tp_train_step
    make_tp_train_step(_dense(case), None, None, None)


def by_pp(case, tmp_path):
    from ps_pytorch_tpu.parallel.pp import make_pp_train_step
    make_pp_train_step(_dense(case), None, None, None, num_microbatches=1)


def by_decode(case, tmp_path):
    model = tiny(case)[0].clone(n_layers=1, decode=True, decode_cache_len=8)
    with case.patched():
        model.init(jax.random.key(0), jnp.zeros((1, 1), jnp.int32))


def by_two_chips(case, tmp_path):
    import optax
    from ps_pytorch_tpu.parallel import ep
    from ps_pytorch_tpu.parallel.mesh import make_mesh
    ep.make_ep_train_step(tiny(case)[0].clone(ep_axis="data"), optax.sgd(0.1),
                          make_mesh(data=2), state=None)


def hybrid_refusals(arch, mode, entries):
    """(id, entry, words) rows for an arch ``refuse_hybrid`` writes every
    refusal of: ``entries`` is (entry, where, what it lacks)."""
    return tuple(
        (where, entry, (f"lm_arch={arch} is not built for {where}", lacks,
                        f"lm_parallelism {mode}"))
        for entry, where, lacks in entries)


# ---- the cases every row runs ---------------------------------------------------

def install(namespace, case):
    """Define the common cases in a test file's ``namespace`` for its row."""
    row_is_ep = case.parallelism == "ep"

    @pytest.fixture(autouse=True)
    def tiny_row():
        with case.patched():
            yield

    @pytest.fixture(name="tiny", scope="module")
    def tiny_fixture():
        """(model, variables, tokens) for the file's own cases."""
        return tiny(case)

    @pytest.mark.parametrize("attention", ["full", "flash"])
    def test_logits_agree_with_the_reference(attention):
        """Tolerance: ``case.tol_reason``."""
        _, _, tokens = tiny(case)
        got, stats = logits(case, attention)
        want = reference_logits(case)
        heads = (case.pred_heads,) if case.pred_heads > 1 else ()
        assert got.shape == want.shape == (*tokens.shape, *heads, case.vocab)
        assert float(jnp.abs(want).max()) > 2
        assert float(jnp.abs(got - want).max()) < case.logit_tol
        if row_is_ep:
            assert set(DROPLESS_STATS) <= set(stats)
            assert float(stats["moe_dropped"]) == 0.0

    def test_no_layer_reads_a_later_token():
        """Whatever mixes tokens (an attention mask, a convolution, a scan, a
        delta rule): a change to the last third of the tokens moves no logit
        before it."""
        _, variables, tokens = tiny(case)
        cut = 2 * case.seq_len // 3
        other = tokens.at[:, cut:].set((tokens[:, cut:] + 1) % case.vocab)
        out = forward(case)(variables, other)
        # one program ran both: equal to the bit
        a, b = logits(case)[0], out[0] if isinstance(out, tuple) else out
        np.testing.assert_array_equal(a[:, :cut], b[:, :cut])
        assert float(jnp.abs(a - b)[:, cut:].max()) > 0.1

    @pytest.mark.parametrize("remat", [False, True])
    def test_the_step_descends_the_reference_loss(remat):
        """One step of the trainer's step (``parallel/sp.py``'s or
        ``parallel/ep.py``'s, on one device, SGD with momentum and weight
        decay from a fresh optimizer state) moves every parameter by ``lr *
        (jax.grad(reference loss) + weight_decay * p)``, with and without
        per-block remat, and reports the reference's loss. Tolerance: float32
        both sides, gradients up to about 1: 2e-5 absolute is reduction order,
        and the difference of two parameters over lr rounds by 2^-22 of the
        parameter over lr."""
        state, new, m = first_step(case, remat)
        want_loss, want = reference_grads(case)
        got = jax.tree.map(lambda a, b: (a - b) / LR - WEIGHT_DECAY * a,
                           state.params, new.params)
        flat_got = jax.tree_util.tree_flatten_with_path(got)[0]
        flat_want = jax.tree.leaves(want)
        assert len(flat_got) == len(flat_want)
        for ((path, g), w, p) in zip(flat_got, flat_want,
                                     jax.tree.leaves(state.params)):
            assert float(jnp.abs(w).max()) > 0, path    # every parameter is reached
            rounding = 2.0 ** -22 * float(jnp.abs(p).max()) / LR
            np.testing.assert_allclose(
                g, w, atol=2e-5 + rounding, rtol=2e-3,
                err_msg=jax.tree_util.keystr(path))
        row = case.tiny_row
        total = float(m["loss"])
        if row_is_ep:   # the step reports the routing terms beside the loss
            total += row.aux_coef * float(m["aux"]) \
                + row.z_loss_coef * float(m.get("z_loss", 0.0))
        np.testing.assert_allclose(total, float(want_loss), rtol=1e-5)

    def test_remat_changes_no_step():
        """``--remat`` is the same step: whatever a block's forward, run
        again, hands its backward (a scan's or a delta rule's kept states, the
        sorted rows) is what the first run handed it."""
        plain, remat = first_step(case, False)[1], first_step(case, True)[1]
        for (path, a), b in zip(
                jax.tree_util.tree_flatten_with_path(plain.params)[0],
                jax.tree.leaves(remat.params)):
            np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-5,
                                       err_msg=jax.tree_util.keystr(path))

    def test_the_counters_come_back_with_the_loss():
        """What the step returns beside the loss: an expert stack's routing
        statistics (under a selection bias its two more), and what the arch's
        mixers count, each inside the range the row states; nothing else."""
        _, _, m = first_step(case, True)
        want = {"loss", *case.counters}
        if row_is_ep:
            want |= set(DROPLESS_STATS)
            if case.tiny_row.router_bias_rate:
                want |= set(BIAS_STATS)
            elif case.tiny_row.load_all_stat:   # the layers' own counts
                want |= {LOAD_ALL_STAT}
            assert float(m["moe_dropped"]) == 0.0
            # rows the grouped matmuls visit and do not multiply: some under
            # a held share (its part is sized past what it draws), else none
            tail = float(m["moe_tail_rows_share"])
            assert 0 < tail < 1 if case.flags.get("lm_experts_held") \
                else tail == 0
        assert set(m) == want
        assert all(np.isfinite(float(v)) for v in m.values())
        for name, (low, high) in case.counters.items():
            assert low < float(m[name]) < high, name

    def test_lm_trainer_trains_logs_the_counters_and_resumes():
        """``train_lm.py``'s path: three steps and a checkpoint, a second
        trainer that resumes from it bit for bit (parameters, momentum and
        whatever else the state holds) and goes on to step 6; the loss falls;
        every record and the registry carry the counters; a checkpoint of
        another depth is refused."""
        first, resumed, saved, restored, records = trained(case)
        cfg = first.cfg
        assert jax.tree.structure(saved) == jax.tree.structure(restored)
        for x, y in zip(jax.tree.leaves(saved), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(x, y)
        with one_device():
            assert int(resumed.state.step) == 6
            assert [r["step"] for r in records] == [1, 2, 3, 4, 5, 6]
            assert records[0]["compute_dtype"] == "float32"
            assert records[-1]["loss"] < records[0]["loss"]
            for r in records:
                for name, (low, high) in case.counters.items():
                    assert low < r[name] < high, name
                assert set(tr_mod.COUNTER_NAMES) & set(r) <= set(case.counters)
                if row_is_ep:
                    assert r["moe_dropped"] == 0.0 and r["aux"] > 0
            for name in case.counters:
                assert resumed.registry.get(name) == records[-1][name]
            # the check is of the two configs, before any state is read: the
            # resumed trainer stands in for one of another depth
            resumed.cfg = cfg.replace(lm_layers=case.another_depth)
            try:
                with pytest.raises(ValueError,
                                   match=f"lm_layers={cfg.lm_layers}"):
                    resumed.maybe_resume()
            finally:
                resumed.cfg = cfg

    def test_every_scope_the_arch_uses_is_in_the_compiled_step():
        fn = step(case, True).step_fn
        check_scopes(fn.lowered_text, fn.compiled_text, case.scopes, True,
                     case.remat_scopes)

    def test_a_rematerialised_block_runs_nothing_it_keeps_a_second_time():
        """What ``remat_block`` keeps by name (``KEPT_NAMES``) is not made
        again: among the recomputed ops of the lowered remat step there is no
        ``top_k`` and no sort (the route's integers are kept), no matmul under
        ``attn_proj`` (q, k, v, the gate's and the output projection's
        results are kept; the input norm stays, which ``check_scopes``
        holds), none under ``ssm_proj`` where the arch's state-space layers
        are Mamba-2 (``in_proj``'s three slices are kept), and no down
        projection where a norm follows the feed-forward half (its result is
        kept, so nothing behind the hidden rows runs again; the routed rows'
        scatter-add sits in a called function, whose ops the lowered text
        names relative to the call: the chip's trace shows it).
        Without ``--remat`` a name is nothing: the step lowers to the text it
        had before any interior was named."""
        again = [(op, name) for op, name in
                 named_ops(step(case, True).step_fn.lowered_text)
                 if scope_of(name)[1] == "recompute"]
        assert len(again) > 20
        assert [(op, name) for op, name in again
                if op == "sort" or name.rsplit("/", 1)[-1] in ("top_k", "sort")
                ] == []
        dots = [name for op, name in again if op == "dot"]
        assert dots     # whatever else a block's backward reads is made again
        scopes = {scope_of(name)[0] for name in dots}
        assert "attn_proj" not in scopes
        if case.tiny_row.ssm_heads:     # Mamba-2's sizes
            assert "ssm_proj" not in scopes
        if case.tiny_row.post_norm:
            assert [name for name in dots if "/down/" in name] == []
        named, unnamed = unnamed_lowering(case)
        assert named == unnamed

    def test_no_heavy_op_is_without_a_scope():
        fn = step(case, True).step_fn
        check_heavy_ops(fn.lowered_text, fn.compiled_text)

    def test_a_scope_changes_no_program():
        """With ``device_scope`` patched to nothing the step is lowered to the
        same program, text for text: a scope is a location, which the compiler
        is not handed as work, so the lowered texts say it and no second
        compilation is needed."""
        scoped, bare, bare_named = unscoped_lowering(case)
        assert not any(scope_of(name)[0] for _, name in named_ops(bare_named))
        assert len(named_ops(bare_named)) > 100
        assert bare == scoped

    def test_config_knows_the_arch_and_needs_no_new_field():
        """The arch is a value of ``--lm-arch`` and its tiny size is said in
        fields every arch has; what the flags cannot say is the ``ARCHS``
        row's, and the row holds the published values."""
        assert case.arch in LM_ARCHS and set(LM_ARCHS) == set(ARCHS)
        cfg = TrainConfig.from_json(case.train_config().to_json())
        assert (cfg.lm_arch, cfg.lm_parallelism) == (case.arch,
                                                     case.parallelism)
        assert all(getattr(cfg, k) == v for k, v in case.flags.items())
        if row_is_ep:       # an expert stack says so where the config is made
            with pytest.raises(ValueError):
                case.train_config(lm_parallelism="sp")
        row = case.published_arch_row
        for field, key in case.published_row.items():
            assert getattr(row, field) == case.published[key], field

    common = dict(locals())
    if case.shares is not None:
        @pytest.mark.parametrize("side", ["program", "reference"])
        def test_the_shares_add_up_to_the_uncut_layer(side):
            """One expert layer at the tiny size, every expert's weights
            seeded: the routed parts of the shares, plus whatever every share
            holds whole counted once, add up to the uncut reference layer.
            float32 sums: 5e-6 absolute is their rounding."""
            parts, uncut = case.shares(side)
            assert float(jnp.abs(uncut).max()) > 5e-3
            np.testing.assert_allclose(sum(parts), uncut, atol=5e-6)
        common["test_the_shares_add_up_to_the_uncut_layer"] = \
            test_the_shares_add_up_to_the_uncut_layer

    if case.controls:
        controls = case.planted

        @pytest.mark.parametrize("name", sorted(controls.CONTROLS))
        def test_every_planted_mistake_fails_by_a_wide_margin(name):
            """The controls are ``benchmark/controls/<config>.py``'s, which
            reads the same ones at the cell's size on the chip: the program
            (with the mistake in it, where it is the program's to make)
            against the reference (with it, where it is the reference's; for
            the precisions below the stated one, the reference computed
            coarser). Each is far over the tolerance the true comparison
            keeps, by the row's ``margins``."""
            model, variables, tokens = tiny(case)
            control = controls.CONTROLS[name]
            kept = {k: getattr(case.reference, k)
                    for k in control.get("ref", {})}
            driver = types.SimpleNamespace(
                system_forward=lambda trainer, v, x: jax.jit(
                    lambda v, x: apply_logits(trainer.model, v, x))(v, x))
            row = tr_mod.ARCHS[case.arch]
            with controls.planted(control, driver, case.reference,
                                  case.tiny_config) as (wrong, ref):
                if {"arch", "model", "variables"} & set(control):
                    got = wrong.system_forward(
                        types.SimpleNamespace(model=model), variables, tokens)
                else:       # the mistake is the reference's to make
                    got = logits(case)[0]
                want = reference_forward(case, variables, tokens, ref)
            precision = name in getattr(controls, "PRECISION_CONTROLS", ())
            margin = case.margins[precision]
            # (not within it: a wrong model's state may overflow, which is no
            # agreement either)
            assert not float(jnp.abs(got - want).max()) \
                <= margin * case.logit_tol, name
            # and the row and the reference are themselves again
            assert tr_mod.ARCHS[case.arch] == row
            assert all(getattr(case.reference, k) is v
                       for k, v in kept.items())
        common["test_every_planted_mistake_fails_by_a_wide_margin"] = \
            test_every_planted_mistake_fails_by_a_wide_margin

    if case.refusals:
        @pytest.mark.parametrize(
            "entry,words", [r[1:] for r in case.refusals],
            ids=[r[0].replace(" ", "_") for r in case.refusals])
        def test_every_other_entry_point_refuses_the_arch_by_name(
                tmp_path, capsys, entry, words):
            """Each entry point the arch is not built for is a case, and its
            one message says what is missing."""
            try:
                entry(case, tmp_path)
            except SystemExit as e:     # an argparse error: the message is on stderr
                assert e.code == 2
                message = capsys.readouterr().err
            except (ValueError, NotImplementedError) as e:
                message = str(e)
            else:
                pytest.fail(f"{entry.__name__} did not refuse "
                            f"lm_arch={case.arch}")
            assert "Traceback" not in message
            for word in words:
                assert word in message, (word, message)
            if tr_mod._state_kind(case.tiny_row):   # ... and no other arch
                refuse_hybrid("gpt2", words[0].rsplit(" for ", 1)[1])
        common["test_every_other_entry_point_refuses_the_arch_by_name"] = \
            test_every_other_entry_point_refuses_the_arch_by_name

    for name, value in common.items():
        if name.startswith("test_") or name in ("tiny_row", "tiny_fixture"):
            namespace[name] = value
