"""Standalone polling evaluator.

Reproduces the reference's evaluator contract
(``distributed_evaluator.py:74-114``): a separate process watches the
checkpoint directory for ``model_step_<k>``, loads each new checkpoint, and
reports loss / Prec@1 / Prec@5 on the test set. Differences: atomic
checkpoints mean no torn reads; the model/config are read from the checkpoint
itself (no flag duplication); and the reference's latent crash at
``distributed_evaluator.py:145`` (undefined ``worker_fc_nn``) has no
equivalent here.
"""

import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from ps_pytorch_tpu.config import TrainConfig
from ps_pytorch_tpu.data import prepare_data
from ps_pytorch_tpu.models import build_model
from ps_pytorch_tpu.optim import build_optimizer
from ps_pytorch_tpu.parallel import create_train_state, make_eval_step, make_mesh
from ps_pytorch_tpu.parallel.dp import replica0_batch_stats
from ps_pytorch_tpu.runtime import checkpoint as ckpt

EVAL_LINE = "EVAL step {step} loss {loss:.6f} prec1 {prec1:.4f} prec5 {prec5:.4f}"
EVAL_LM_LINE = "EVAL_LM step {step} loss {loss:.6f} perplexity {perplexity:.3f}"
_LM_NETWORKS = ("TransformerLM", "MoETransformerLM")


def accumulate_eval(eval_fn, params, bstats, batches, max_batches=None) -> dict:
    """Shared eval accumulation (trainer/multislice/evaluator): run
    ``eval_fn(params, bstats, x, y)`` over ``batches`` and reduce to
    loss / prec1 / prec5 / count."""
    tot = {"sum_loss": 0.0, "top1": 0, "top5": 0, "count": 0}
    for i, (x, y) in enumerate(batches):
        if max_batches is not None and i >= max_batches:
            break
        m = eval_fn(params, bstats, jnp.asarray(x), jnp.asarray(y))
        tot["sum_loss"] += float(m["sum_loss"])
        for k in ("top1", "top5", "count"):
            tot[k] += int(m[k])
    n = max(tot["count"], 1)
    return {"loss": tot["sum_loss"] / n, "prec1": tot["top1"] / n,
            "prec5": tot["top5"] / n, "count": tot["count"]}


class Evaluator:
    def __init__(self, train_dir: str, poll_s: float = 10.0,
                 printer: Callable = print, download: bool = False):
        self.train_dir = train_dir
        self.poll_s = poll_s
        self.printer = printer
        self.download = download
        self._built_for: Optional[str] = None
        self._lm = False

    def _build(self, config_json: str):
        cfg = TrainConfig.from_json(config_json)
        self.cfg = cfg
        self._lm = cfg.network in _LM_NETWORKS
        if self._lm:
            self._build_lm(cfg)
            self._built_for = config_json
            return
        self.model = build_model(cfg.network, cfg.num_classes, cfg.compute_dtype,
                                 conv_impl=cfg.conv_impl)
        # Template state for deserialization; single-device mesh is fine here.
        mesh = make_mesh(data=1)
        from ps_pytorch_tpu.data.datasets import sample_shape
        self.template = create_train_state(
            self.model, build_optimizer(cfg), mesh,
            (1,) + sample_shape(cfg.dataset), jax.random.key(0))
        _, self.test_loader = prepare_data(cfg, download=self.download)
        from ps_pytorch_tpu.data.augment import input_norm_for
        self.eval_fn = make_eval_step(self.model, input_norm_for(cfg))
        self._built_for = config_json

    def _build_lm(self, cfg: TrainConfig):
        """LM checkpoints (train_lm.py): held-out next-token loss /
        perplexity. The checkpoint's config is self-describing (model
        family in ``network``, resolved ``lm_model_axis`` for pp).

        sp checkpoints evaluate through the SHARDED ring-attention forward
        over this host's devices when the sequence shards evenly — the
        unsharded fallback materializes [S, S] attention, the OOM the sp
        mode exists to avoid, so it is only used when ring sharding is
        impossible (one device, or indivisible sequence)."""
        from ps_pytorch_tpu.data.text import TokenLoader, lm_streams
        from ps_pytorch_tpu.runtime.lm_eval import build_lm_oracle, lm_geometry

        self._lm_sp_eval = None
        n = len(jax.devices())
        if (cfg.lm_parallelism == "sp" and n > 1
                and cfg.lm_seq_len % n == 0):
            import numpy as np
            from jax.sharding import Mesh
            from ps_pytorch_tpu.models.transformer import TransformerLM
            from ps_pytorch_tpu.parallel.sp import make_sp_eval_fn
            mesh = Mesh(np.array(jax.devices()), ("data",))
            ring = TransformerLM(attention_impl="ring", axis_name="data",
                                 **lm_geometry(cfg))
            self._lm_sp_eval = (make_sp_eval_fn(ring, mesh), mesh)
        loss_fn, to_tree = build_lm_oracle(cfg)
        # Template state for deserialization: same model family + same
        # optimizer construction as LMTrainer, so the tree matches
        # (shared with generate.py via lm_eval.build_lm_template).
        from ps_pytorch_tpu.runtime.lm_eval import build_lm_template
        self.template = build_lm_template(cfg)
        _, val = lm_streams(cfg)
        self._lm_val = TokenLoader(val, cfg.batch_size, cfg.lm_seq_len,
                                   seed=0, shuffle=False)
        self._lm_to_tree = to_tree
        self._lm_loss = loss_fn

    def _evaluate_lm_step(self, step: int) -> dict:
        from ps_pytorch_tpu.parallel import dist
        from ps_pytorch_tpu.runtime.lm_eval import perplexity

        state, _, _ = ckpt.load_checkpoint(self.train_dir, step,
                                           self.template)
        params = self._lm_to_tree(state.params)
        losses = []
        for t in self._lm_val.epoch(0):
            if self._lm_sp_eval is not None:
                from jax.sharding import PartitionSpec as P
                eval_fn, mesh = self._lm_sp_eval
                tok = dist.globalize_replicated(mesh, t,
                                                spec=P(None, "data"))
                losses.append(float(eval_fn(params, tok)))
            else:
                losses.append(float(self._lm_loss(params, jnp.asarray(t),
                                                  state.batch_stats)))
        loss = sum(losses) / max(len(losses), 1)
        result = {"step": step, "loss": loss, "perplexity": perplexity(loss)}
        self.printer(EVAL_LM_LINE.format(**result))
        return result

    def evaluate_step(self, step: int) -> dict:
        path = ckpt.checkpoint_path(self.train_dir, step)
        with open(f"{path}/config.json") as f:
            config_json = f.read()
        if config_json != self._built_for:
            self._build(config_json)
        if self._lm:
            return self._evaluate_lm_step(step)
        state, meta, _ = ckpt.load_checkpoint(self.train_dir, step, self.template)
        result = accumulate_eval(self.eval_fn, state.params,
                                 replica0_batch_stats(state),
                                 self.test_loader.epoch(0))
        result = {"step": step, "loss": result["loss"],
                  "prec1": result["prec1"], "prec5": result["prec5"]}
        self.printer(EVAL_LINE.format(**result))
        return result

    def run(self, stop_after: Optional[int] = None,
            idle_timeout_s: Optional[float] = None) -> list:
        """Poll-evaluate loop (reference ``:79-88``): wake every poll_s,
        evaluate any checkpoint newer than the last one seen."""
        done = -1
        results = []
        idle = 0.0
        while True:
            latest = ckpt.latest_step(self.train_dir)
            if latest is not None and latest > done:
                # Evaluate every committed step between done and latest.
                steps = sorted(s for s in self._all_steps() if s > done)
                for s in steps:
                    results.append(self.evaluate_step(s))
                done = latest
                idle = 0.0
                if stop_after is not None and done >= stop_after:
                    return results
            else:
                time.sleep(self.poll_s)
                idle += self.poll_s
                if idle_timeout_s is not None and idle >= idle_timeout_s:
                    return results

    def _all_steps(self):
        import os, re
        pat = re.compile(r"^model_step_(\d+)$")
        if not os.path.isdir(self.train_dir):
            return []
        return [int(m.group(1)) for n in os.listdir(self.train_dir)
                if (m := pat.match(n))]
