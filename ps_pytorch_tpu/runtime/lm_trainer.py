"""LM trainer — long-context training through the standard runtime contract.

Drives a transformer LM under the parallelism selected by
``--lm-parallelism`` while reusing the framework's standard machinery
(TrainConfig, MetricsLogger STEP schema, atomic checkpoints with resume,
held-out next-token-loss oracle):

- ``sp`` (default): sequence sharded over the mesh, ring attention
  (``parallel/sp.py``) — the long-context mode.
- ``tp``: Megatron-style tensor parallelism over the 'model' axis,
  composed with DP over 'data' (``parallel/tp.py``).
- ``pp``: GPipe pipeline over the 'model' axis with ``--lm-microbatches``
  (``parallel/pp.py``).
- ``ep``: an MoE model with experts sharded over 'data' (``models/moe.py``
  + ``parallel/ep.py``); also how an MoE model is run on one chip.
  ``--lm-arch`` picks capacity routing (gpt2) or dropless (olmoe,
  smallthinker, trinity, qwen3next, nemotronh, granite4h; ``--lm-experts-held`` trains one chip's
  share of the experts, ``--lm-mixer-shares`` one chip's share of the mixers'
  heads and of the shared expert, ``--lm-dense-layers`` starts the stack with
  dense layers).

The reference has no LM surface at all — this is the §5.7 long-context
capability expressed as a first-class entry point (``train_lm.py``), not
just library code.
"""

import json
import os
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ps_pytorch_tpu import resilience
from ps_pytorch_tpu.config import TrainConfig
from ps_pytorch_tpu.data.text import TokenLoader
from ps_pytorch_tpu.models.transformer import (
    ARCHS, ATTENTION_KINDS, refuse_hybrid,
)
from ps_pytorch_tpu.ops._backend import announce_kernels
from ps_pytorch_tpu.ops.flash_attention import flash_schedule
from ps_pytorch_tpu.ops.selective_scan import scan_schedule
from ps_pytorch_tpu.optim import build_schedule
from ps_pytorch_tpu.optim.sgd import sgd
from ps_pytorch_tpu.parallel import dist
from ps_pytorch_tpu.parallel.sp import (
    create_lm_train_state, make_sp_eval_fn, make_sp_train_step,
)
from ps_pytorch_tpu.runtime import checkpoint as ckpt
from ps_pytorch_tpu.runtime.lm_eval import build_lm_model
from ps_pytorch_tpu.runtime.metrics import MetricsLogger
from ps_pytorch_tpu.runtime.step_queue import QueuedSteps
from ps_pytorch_tpu.telemetry import (
    FlightRecorder, HealthMonitor, MetricsExporter, ProfileWindow, Registry,
    Tracer, declare_resilience_metrics,
    declare_training_metrics, derive_step_record,
    device_memory_record, host_rss_bytes, set_default_tracer,
    set_device_memory_gauges, startup_line,
)
from ps_pytorch_tpu.utils.compile_cache import (
    COMPILE_COUNTERS, count_compiles,
)
from ps_pytorch_tpu.utils.flops import forward_flops, peak_flops_bf16


class LMTrainer:
    def __init__(self, cfg: TrainConfig):
        # The tracer exists before anything is built, as in the CNN Trainer:
        # the constructor is one tree under the span ``setup``
        # (telemetry/trace.py), and a compile anywhere in it is counted in
        # the span that caused it (utils/compile_cache.py, into the registry
        # too).
        count_compiles()
        self.registry = declare_training_metrics(Registry())
        self.tracer = Tracer(registry=self.registry,
                             counters=COMPILE_COUNTERS)
        self._prev_tracer = set_default_tracer(self.tracer)
        try:
            with self.tracer.setup_span():
                self._build(cfg)
        except BaseException:
            set_default_tracer(self._prev_tracer)
            raise

    def _build(self, cfg: TrainConfig) -> None:
        """The constructor's work, each piece under the child of ``setup``
        that names it (the table of ``PERF.md`` §3)."""
        span = self.tracer.span
        self.cfg = cfg
        with span("backend_init") as found:
            # TPU start where the caller has not paid it already
            devices = jax.devices()
            found["devices"] = len(devices)
            self.tracer.pid = jax.process_index()
        self.mode = cfg.lm_parallelism
        with span("model_build"):
            deg = self._build_model(cfg, devices)
        shape = (cfg.batch_size, cfg.lm_seq_len)
        self.eval_fn = None     # tp/pp/ep: oracle eval (see evaluate())
        if self.mode == "sp":
            create, make = create_lm_train_state, make_sp_train_step
        elif self.mode == "tp":
            from ps_pytorch_tpu.parallel.tp import (
                create_tp_train_state as create, make_tp_train_step as make,
            )
        elif self.mode == "pp":
            from ps_pytorch_tpu.parallel.pp import (
                create_pp_train_state as create, make_pp_train_step as make,
            )
        else:
            from ps_pytorch_tpu.parallel.ep import (
                create_ep_train_state as create, make_ep_train_step as make,
            )
        with span("state_init") as made:
            # tracing, compiling and running the initialiser; optimizer state
            # (pp stacks the blocks by stage: its initialiser takes the degree)
            stages = (deg,) if self.mode == "pp" else ()
            self.state = create(self.model, self.tx, self.mesh, *stages,
                                shape, jax.random.key(cfg.seed))
            made["params"] = sum(
                leaf.size for leaf in jax.tree.leaves(self.state.params))
            made["bytes"] = sum(
                leaf.nbytes for leaf in jax.tree.leaves(self.state))
        with span("step_build"):
            if self.mode == "sp":
                self.step_fn = make(self.model, self.tx, self.mesh,
                                    remat=cfg.remat, donate=cfg.donate)
                self.eval_fn = make_sp_eval_fn(self.model, self.mesh)
            else:
                more = {"num_microbatches": cfg.lm_microbatches} \
                    if self.mode == "pp" else {}
                self.step_fn = make(self.model, self.tx, self.mesh,
                                    self.state, remat=cfg.remat,
                                    donate=cfg.donate, **more)

        # Checkpoints are self-describing: record the model family and the
        # RESOLVED mesh degree (lm_model_axis=0 means "all devices", which
        # the standalone evaluator cannot know) into the config that
        # save_checkpoint embeds.
        resolved = {"network": ("MoETransformerLM" if self.mode == "ep"
                                else "TransformerLM")}
        if self.mode in ("tp", "pp"):
            resolved["lm_model_axis"] = deg
        self.cfg = cfg = cfg.replace(**resolved)

        with span("data_build") as built:
            from ps_pytorch_tpu.data.text import lm_streams
            train_stream, self.val_tokens = lm_streams(cfg)
            self.train_loader = TokenLoader(train_stream, cfg.batch_size,
                                            cfg.lm_seq_len, seed=cfg.seed)
            built["bytes"] = train_stream.nbytes + self.val_tokens.nbytes
        with span("ops_plane_build"):
            self._build_ops_plane(cfg, devices)

    def _build_model(self, cfg: TrainConfig, devices) -> Optional[int]:
        """The optimizer, the mesh of ``--lm-parallelism``, the model with
        the attention it resolves to, and the ``KERNELS`` line of the
        schedules behind it. -> the model axis' degree under tp / pp."""
        n = len(devices)
        deg = None
        self.tx = sgd(lr=build_schedule(cfg), momentum=cfg.momentum,
                      weight_decay=cfg.weight_decay, nesterov=cfg.nesterov)

        # Resolve the attention kernel (--lm-attention). "flash" (the fused
        # Pallas kernel, ops/flash_attention.py) is sequence-LOCAL: legal
        # whenever this rank holds the whole sequence (sp on one device,
        # tp/pp/ep always). sp over >1 device shards the sequence, so the
        # cross-shard exchange must be ring attention.
        local_impl = "flash" if cfg.lm_attention == "flash" else "full"

        if self.mode == "sp":
            # Sequence sharded over 'data', ring attention across shards.
            self.mesh = Mesh(np.array(devices), ("data",))
            if n > 1:
                refuse_hybrid(cfg.lm_arch, "ring attention")
                if cfg.lm_attention != "auto":
                    raise ValueError(
                        f"lm_attention={cfg.lm_attention!r} is "
                        f"sequence-local; sp over {n} devices shards the "
                        "sequence and requires ring attention (use "
                        "lm_attention=auto)")
                impl = "ring"
            else:
                impl = local_impl
            if cfg.lm_seq_len % n:
                raise ValueError(f"lm_seq_len {cfg.lm_seq_len} not "
                                 f"divisible by {n} devices (sequence "
                                 f"sharding)")
            self.model = build_lm_model(cfg, attention_impl=impl,
                                        axis_name="data")
        elif self.mode in ("tp", "pp"):
            from ps_pytorch_tpu.parallel.mesh import make_mesh
            deg = cfg.lm_model_axis or n
            if n % deg:
                raise ValueError(f"{n} devices not divisible by "
                                 f"lm_model_axis={deg}")
            self.mesh = make_mesh(data=n // deg, model=deg,
                                  devices=devices)
            if self.mode == "tp" and local_impl != "full":
                # TP partitions the step with GSPMD; a pallas_call carries
                # no partitioning rule, so XLA cannot shard the fused
                # kernel over the head axis. PP runs per-stage inside
                # shard_map (device-local), where flash is fine.
                raise ValueError("lm_attention='flash' is not supported "
                                 "under tp (GSPMD cannot partition the "
                                 "fused kernel over heads); use full")
            if self.mode == "pp" and cfg.lm_layers % deg:
                raise ValueError(f"lm_layers={cfg.lm_layers} not "
                                 f"divisible into {deg} stages")
            self.model = build_lm_model(cfg, attention_impl=local_impl)
        elif self.mode == "ep":
            from ps_pytorch_tpu.parallel.mesh import make_mesh
            self.mesh = make_mesh(data=n, model=1, devices=devices)
            self.model = build_lm_model(cfg, attention_impl=local_impl,
                                        ep_axis="data")
        else:  # unreachable: TrainConfig.__post_init__ validates
            raise ValueError(self.mode)
        kernels = []
        arch = ARCHS[cfg.lm_arch]
        calls = cfg.lm_microbatches if self.mode == "pp" else 1
        rows = max(cfg.batch_size // (self.mesh.shape["data"] * calls), 1)
        # the schedules are of the heads HELD (all of them but under ep with
        # --lm-mixer-shares)
        shares = getattr(self.model, "mixer_shares", 1)
        self.mixer_held_share = (cfg.lm_heads // shares) / cfg.lm_heads
        if self.model.attention_impl == "flash":
            # the schedule is static per shape: the line is its record, one
            # for each kind of attention layer the arch mixes (window or
            # not); under differential heads a call holds one head of each
            # pair and, as its value, the pair's two value heads side by side
            per_call = 2 if arch.diff_attn else 1
            hd = cfg.lm_head_dim or cfg.lm_d_model // cfg.lm_heads
            scheds = {flash_schedule(
                rows * cfg.lm_heads // (per_call * shares), cfg.lm_seq_len,
                hd, jnp.dtype(self.model.dtype).itemsize, True,
                window=arch.layer_window(i, cfg.lm_layers),
                bh_kv=rows * (cfg.lm_kv_heads or cfg.lm_heads)
                // (per_call * shares),
                dv=per_call * hd)
                for i in range(cfg.lm_layers)
                if arch.layer_kind(i, cfg.lm_layers) in ATTENTION_KINDS}
            kernels += [f"flash_attention[{sched.describe()}]"
                        for sched in sorted(scheds, key=lambda sc: sc.window)]
        if "eva" in arch.mixer_layers and self.model.attention_impl == "flash":
            from ps_pytorch_tpu.ops.eva_attention import eva_schedule
            kernels.append("eva_attention[" + eva_schedule(
                rows * cfg.lm_heads, cfg.lm_seq_len,
                cfg.lm_head_dim or cfg.lm_d_model // cfg.lm_heads,
                jnp.dtype(self.model.dtype).itemsize, arch.eva_window,
                arch.eva_chunk).describe() + "]")
        if arch.hybrid:
            kernels.append("selective_scan[" + scan_schedule(
                rows, cfg.lm_seq_len, arch.ssm_expand * cfg.lm_d_model,
                arch.ssm_state).describe() + "]")
        if "gdn" in arch.mixer_layers:
            from ps_pytorch_tpu.ops.gated_delta_rule import gdr_schedule
            kernels.append("gated_delta_rule[" + gdr_schedule(
                rows, cfg.lm_seq_len, arch.gdn_value_heads, arch.gdn_key_dim,
                arch.gdn_value_dim, k_heads=arch.gdn_key_heads,
                itemsize=jnp.dtype(self.model.dtype).itemsize).describe()
                + "]")
            from ps_pytorch_tpu.ops.gdn_mix import mix_schedule
            kernels.append("gdn_mix[" + mix_schedule(
                rows, cfg.lm_seq_len, arch.gdn_key_heads,
                arch.gdn_value_heads, arch.gdn_key_dim, arch.gdn_conv,
                itemsize=jnp.dtype(self.model.dtype).itemsize).describe()
                + "]")
        if "M" in arch.layer_pattern or "mamba2" in arch.mixer_layers:
            from ps_pytorch_tpu.ops.ssd import ssd_schedule
            heads = arch.ssm_heads // shares
            kernels.append("ssd[" + ssd_schedule(
                rows, cfg.lm_seq_len, heads, arch.ssm_head_dim,
                arch.ssm_state, arch.ssm_groups, chunk=arch.ssm_chunk,
                itemsize=jnp.dtype(self.model.dtype).itemsize).describe()
                + "]")
            from ps_pytorch_tpu.ops.ssm_mix import ssm_mix_schedule
            kernels.append("ssm_mix[" + ssm_mix_schedule(
                rows, cfg.lm_seq_len, heads * arch.ssm_head_dim,
                arch.ssm_groups * arch.ssm_state, arch.ssm_groups,
                arch.ssm_conv,
                itemsize=jnp.dtype(self.model.dtype).itemsize).describe()
                + "]")
        if arch.dropless:
            from ps_pytorch_tpu.models.moe import held_rows
            from ps_pytorch_tpu.ops.grouped_matmul import gmm_schedule
            held = cfg.lm_experts_held or cfg.lm_experts
            assignments = rows * cfg.lm_seq_len * cfg.lm_moe_top_k
            sized = held_rows(assignments, held, cfg.lm_experts)
            kernels.append("grouped_matmul[" + gmm_schedule(
                sized, assignments * held // cfg.lm_experts, cfg.lm_d_model,
                cfg.lm_ffn_dim or 4 * cfg.lm_d_model, held,
                jnp.dtype(self.model.dtype).itemsize).describe() + "]")
            if sized < assignments:     # a held share: its rows' own kernel
                from ps_pytorch_tpu.ops.moe_rows import rows_schedule
                kernels.append("moe_rows[" + rows_schedule(
                    sized, assignments * held // cfg.lm_experts,
                    rows * cfg.lm_seq_len, cfg.lm_moe_top_k, cfg.lm_d_model,
                    self.model.dtype, held).describe() + "]")
        # What the run really computes in is read from the built model, not
        # from the flag: the line here, the first JSONL record and the gauge.
        self.compute_dtype = jnp.dtype(self.model.dtype)
        announce_kernels(kernels, dtype=self.compute_dtype)
        return deg

    def _build_ops_plane(self, cfg: TrainConfig, devices) -> None:
        """Metrics logger, profiler window, the MFU's inputs, fault injector,
        health monitor, flight recorder, exporter: the same telemetry surface
        as the CNN Trainer (schema parity — the analyze tooling must read
        vision and LM runs identically)."""
        self.metrics = MetricsLogger(cfg.metrics_file, cfg.log_every,
                                     process_index=jax.process_index(),
                                     num_processes=jax.process_count())
        # --profile-dir / --profile-steps: the same window as the CNN Trainer.
        self._profile = ProfileWindow(cfg.profile_dir, cfg.profile_steps)
        self._flops_per_step: Optional[int] = None
        self._n_chips = len(devices)
        self._peak_per_chip = peak_flops_bf16(devices[0].device_kind)
        self.start_step = 0
        # Fault plane (same spec/grammar as the CNN trainer): step-keyed
        # crashes + post-commit checkpoint corruption for resilience drills.
        self.injector = None
        if cfg.fault_spec:
            self.injector = resilience.FaultInjector(
                cfg.fault_spec, process_index=jax.process_index())
        # Live ops plane, same surfaces as the CNN Trainer. The LM step
        # metrics carry loss only (no in-graph grad norm yet), so the
        # watchdogs see loss at log cadence plus wall-clock stall.
        self.health: Optional[HealthMonitor] = None
        if cfg.health_spec:
            self.health = HealthMonitor(cfg.health_spec,
                                        registry=self.registry)
        self.flightrec: Optional[FlightRecorder] = None
        flight_path = cfg.flight_file or (
            os.path.join(cfg.train_dir, "flightrec.json")
            if (cfg.health_spec or cfg.metrics_port > 0) else "")
        if flight_path:
            if jax.process_index() > 0:
                flight_path = f"{flight_path}.p{jax.process_index()}"
            self.flightrec = FlightRecorder(flight_path, tracer=self.tracer,
                                            registry=self.registry)
        self.registry.set("compute_dtype", self.compute_dtype.itemsize)
        self.exporter: Optional[MetricsExporter] = None
        if cfg.metrics_port > 0:
            collect = []
            if self.injector is not None:
                declare_resilience_metrics(self.registry)
                collect.append(self._pump_resilience_metrics)
            self.exporter = MetricsExporter(
                self.registry,
                port=cfg.metrics_port + jax.process_index(),
                health_fn=self._health_status,
                collect=collect).start()

    def _pump_resilience_metrics(self) -> None:
        """Refresh resilience counters from the live fault-injector snapshot
        (delta-inc: Registry counters are monotonic, the snapshot is the
        source of truth). Runs as a MetricsExporter collect hook."""
        if self.injector is None:
            return
        for name, value in self.injector.snapshot().items():
            try:
                delta = value - self.registry.get(name)
            except KeyError:
                continue            # snapshot key with no declared metric
            if delta > 0:
                self.registry.inc(name, delta)

    def _health_status(self) -> dict:
        body = self.health.status() if self.health is not None else {"ok": True}
        body["process_index"] = jax.process_index()
        # Uniform /healthz identity contract with the elastic trainers: the
        # LM path is pure SPMD (no election), so leadership is static.
        body["leader"] = jax.process_index() == 0
        body["role"] = "leader" if body["leader"] else "follower"
        return body

    def _ops_step(self, step: int, *, loss=None, step_time=None,
                  data_time=None, dispatch_ahead: int = 0) -> None:
        """One step's worth of live-ops bookkeeping. ``loss`` is the PREVIOUS
        step's — already on the host via the loop's one sync, so this adds no
        device round-trip (as in runtime/trainer.py)."""
        r = self.registry
        r.inc("train_steps")
        r.inc("dispatch_ahead_steps", dispatch_ahead)
        r.set("train_step", step)
        if loss is not None:
            r.set("train_loss", loss)
        if step_time is not None and step_time > 0:
            r.set("train_step_time_s", step_time)
            r.observe("train_step_latency_s", step_time)
            r.set("train_examples_per_sec", self.cfg.batch_size / step_time)
        if data_time is not None:
            r.set("train_data_time_s", data_time)
        set_device_memory_gauges(r, device_memory_record())
        r.set("host_rss_bytes", host_rss_bytes())
        if self.flightrec is not None:
            self.flightrec.record_step(step, loss=loss, step_time=step_time,
                                       data_time=data_time)
        self._watch(step, loss=loss, step_time=step_time)

    def _watch(self, step: int, **values) -> None:
        """The health watchdogs on one step's values."""
        if self.health is None:
            return
        for ev in self.health.observe_step(step, **values):
            if self.flightrec is not None:
                self.flightrec.record_health(ev)
            print(f"HEALTH {ev.detector} ({ev.action}): {ev.message}")

    def _halt_for_health(self, step: int) -> None:
        """The checkpoint-and-halt action: commit an emergency checkpoint,
        dump the flight recorder, leave the loop (caller breaks)."""
        ev = self.health.halt_event
        with self.tracer.span("checkpoint", step=step):
            self._checkpoint(step)
        if self.flightrec is not None:
            self.flightrec.dump(f"watchdog:{ev.detector}")
        print(f"HEALTH halt at step {step}: {ev.message}")

    # ---- checkpoint/resume (same on-disk contract as the CNN Trainer) ----
    def _checkpoint(self, step: int) -> None:
        # The gather is COLLECTIVE (tp/pp/ep shard params over devices that
        # can span hosts, and process_allgather needs every host), so it
        # runs on all processes; only the leader writes — concurrent
        # writers to a shared train_dir would race (trainer.py does the
        # same).
        host_state = dist.all_replicated(self.mesh, self.state)
        if jax.process_index() != 0:
            return
        ckpt.save_checkpoint(self.cfg.train_dir, step, host_state,
                             config_json=self.cfg.to_json(),
                             compress=self.cfg.compress_grad,
                             codec_level=self.cfg.codec_level)
        if self.injector is not None:
            self.injector.after_checkpoint(self.cfg.train_dir, step)
        if self.cfg.ckpt_keep > 0:
            ckpt.prune_checkpoints(self.cfg.train_dir, self.cfg.ckpt_keep)

    def _check_saved_config(self, config_json) -> None:
        """Refuse a checkpoint whose recorded model differs from this run's."""
        try:
            saved = json.loads(config_json)
        except (TypeError, ValueError):
            saved = {}
        # lm_model_axis matters for pp: blocks are stacked per stage, and a
        # different stage count would restore without shape validation and
        # silently drop layers inside the step's per-stage slicing. A saved
        # value of 0 predates resolved recording ("all devices at save
        # time") and cannot be compared — skip rather than spuriously
        # reject.
        for k in ("lm_arch", "lm_vocab", "lm_d_model", "lm_layers",
                  "lm_heads", "lm_kv_heads", "lm_head_dim", "lm_ffn_dim",
                  "lm_dense_layers", "lm_dense_ffn_dim",
                  "lm_parallelism", "lm_experts", "lm_experts_held",
                  "lm_mixer_shares", "lm_model_axis", "lm_moe_top_k"):
            if k == "lm_model_axis" and saved.get(k) == 0:
                continue
            if k in saved and saved[k] != getattr(self.cfg, k):
                raise ValueError(
                    f"checkpoint in {self.cfg.train_dir} was written with "
                    f"{k}={saved[k]} but this run uses "
                    f"{getattr(self.cfg, k)} — wrong train_dir, or pass "
                    f"--no-resume / a fresh --train-dir")

    def maybe_resume(self) -> bool:
        with self.tracer.span("resume") as found:
            restored = self._restore_latest()
            if restored:
                found["restored_step"] = self.start_step
        return restored

    def _restore_latest(self) -> bool:
        if ckpt.latest_step(self.cfg.train_dir) is None:
            return False
        # A checkpoint of another model (a CNN's, another arch's) would fail
        # deep inside deserialization with a msgpack key error; check the
        # newest checkpoint's recorded config first and fail with an
        # actionable message instead.
        try:
            with open(os.path.join(ckpt.checkpoint_path(
                    self.cfg.train_dir, ckpt.latest_step(self.cfg.train_dir)),
                    "config.json")) as f:
                self._check_saved_config(f.read())
        except OSError:
            pass
        # Collective gather for the restore template, mirroring
        # _checkpoint: tp/pp/ep shard state across hosts, where a plain
        # device_get raises on non-addressable shards.
        template = dist.all_replicated(self.mesh, self.state)
        try:
            # Valid-latest restore: manifest-failing (corrupt) checkpoints
            # are skipped back to the previous committed step.
            got = ckpt.load_latest_valid(self.cfg.train_dir, template)
        except Exception as e:
            # Most likely a non-LM (CNN) checkpoint sharing the default
            # ./train_dir — surface that instead of a msgpack key error.
            raise ValueError(
                f"could not restore a checkpoint from {self.cfg.train_dir} "
                f"into the LM state (a train.py checkpoint in the same "
                f"train_dir? use a separate --train-dir or "
                f"--no-resume): {type(e).__name__}: {e}") from e
        if got is None:
            return False
        state, meta, config_json, _ = got
        # The checkpoint actually restored may be an older one than the
        # newest (a corrupt latest is skipped): hold its config to the same.
        self._check_saved_config(config_json)
        # Re-place every leaf with the sharding the live state was built
        # with (stage/expert-sharded for pp/ep, TP-sharded kernels, or
        # plain replication) — a bare device_put would leave host-local
        # arrays that cannot feed a multi-host shard_map step.
        self.state = jax.tree.map(
            lambda h, live: jax.device_put(h, live.sharding),
            state, self.state)
        self.start_step = int(meta["step"])
        print(f"RESUME lm at step {self.start_step}")
        return True

    def train(self):
        """Run to ``max_steps``, under whichever ``--lm-parallelism``.

        The loop has one rule, ``Trainer.train``'s: nothing between two
        dispatches waits for the device, so one step is always queued behind
        the running one and the host's work (loader, put, dispatch,
        bookkeeping) hides under the device's. Its one wait, ``device_sync``,
        reads the PREVIOUS step's scalars (one ``device_get`` of all of them)
        with this step already queued. What trails by a step, therefore: a
        logged step's record (JSONL and STEP line) is written once the next
        step is queued, or before what drains the device anyway (a
        checkpoint, the last step, a halt, an exception on its way out); the
        registry's ``train_loss``, the flight recorder and the health
        watchdogs see step n-1's loss in iteration n, so a halt is noticed one
        step late and its checkpoint is of the state one step later (the last
        step's loss is checked after the loop). The bookkeeping is
        runtime/step_queue.py's, shared with ``Trainer``."""
        cfg = self.cfg
        if cfg.resume:
            self.maybe_resume()
        step = self.start_step
        halted = False
        tracer = self.tracer
        # only this run's first record says what it computes in and holds its
        # set-up (the STARTUP line)
        once = {"compute_dtype": self.compute_dtype.name, "setup": None}
        first_step = step + 1

        def write_record(step, own, *, step_time, data_time, dispatch_ahead,
                         epoch):
            # The ep step's routing statistics (aux; a dropless arch's
            # z_loss, expert_load_max_over_mean, moe_dropped,
            # moe_held_share, moe_tail_rows_share; under a selection bias
            # moe_bias_abs_max and moe_load_all_max_over_mean, the second also
            # under an arch with load_all_stat) and what the
            # model counted (a
            # hybrid arch's ssm_state_abs_max and diff_lambda_max and an EVA
            # arch's eva_pool_weight_max and next_token_loss_head0 under sp,
            # a linear-attention arch's gdn_state_abs_max and a Mamba-2
            # arch's ssd_state_abs_max under ep) come
            # with the loss.
            loss = own.pop("loss")
            if self.mixer_held_share < 1:
                # heads held over heads, from the model's own sizes: no
                # device op, and no field where every head is held
                own["mixer_held_share"] = self.mixer_held_share
            derived = derive_step_record(
                step_time_s=step_time, data_time_s=data_time,
                examples=cfg.batch_size,
                tokens=cfg.batch_size * cfg.lm_seq_len,
                flops_per_step=self._flops_per_step,
                peak_flops_per_chip=self._peak_per_chip,
                n_chips=self._n_chips)
            if once:
                once["setup"] = tracer.startup_summary(first_step)
                print(startup_line(once["setup"]))
            self.metrics.log_step(
                step, epoch, loss=loss, acc=0.0, participating=1.0,
                step_time=step_time, data_time=data_time,
                dispatch_ahead=dispatch_ahead,
                compiles=tracer.counted_through(step, "programs"),
                phases=tracer.step_summary(step), **own, **derived, **once)
            once.clear()
            for k, v in own.items():
                self.registry.set(k, v)

        # A record holds, and the watchdogs see, every scalar the step
        # returns: one read of the whole dict.
        queued = QueuedSteps(tracer, step, write_record)
        t_sync = time.monotonic()
        try:
            while step < cfg.max_steps:
                step += 1
                self._profile.on_step(step)
                # The iteration's root span, as in runtime/trainer.py: the
                # phases below are its children, its self time is what no
                # span explains; the finally closes it on any other exit.
                tracer.begin_step(step)
                if self.injector is not None:
                    self.injector.maybe_crash(step)
                t0 = time.monotonic()
                with tracer.span("data_wait"):
                    tokens = self.train_loader.next_batch()
                t_data = time.monotonic() - t0
                # Every process generates the identical shared-seed batch; the
                # globalize places each host's shard (multi-process safe — a
                # host-local committed array can't feed a multi-host
                # shard_map). SP shards the SEQUENCE axis; tp/pp/ep shard the
                # batch axis.
                with tracer.span("batch_put", bytes=tokens.nbytes):
                    tok_g = dist.globalize_replicated(
                        self.mesh, tokens, spec=self._token_spec())
                if self._flops_per_step is None:
                    with tracer.span("flops_trace"):
                        self._flops_per_step = forward_flops(
                            self.step_fn, self.state, tok_g)
                with tracer.span("host_dispatch"):
                    self.state, m = self.step_fn(self.state, tok_g)
                    ahead = queued.ahead()
                last = step == cfg.max_steps
                if step % cfg.log_every == 0 or last:
                    queued.log_later(step, m, data_time=t_data,
                                     dispatch_ahead=ahead,
                                     epoch=self.train_loader._epoch)
                # The loop's one wait: the previous step's scalars, EVERY
                # step (with log_every > 1 too: a read that waits for nothing
                # the chip is not already doing), so the host never runs more
                # than one step ahead and the wall time between two syncs is
                # a true per-step duration (dispatch time alone reads as an
                # MFU above 1 on a chip).
                prev = queued.sync(m)
                now = time.monotonic()
                t_step, t_sync = now - t_sync, now
                with tracer.span("ops_step"):
                    self._ops_step(step, loss=prev.get("loss"),
                                   step_time=t_step, data_time=t_data,
                                   dispatch_ahead=ahead)
                if self.health is not None and self.health.should_halt:
                    queued.log_through(step)
                    self._halt_for_health(step)
                    halted = True
                    break
                # The previous step's record: its metrics finished under
                # device_sync above, so the read waits for nothing. Where the
                # device is drained anyway (a checkpoint, the last step) this
                # step's record goes with it, in one read.
                saves = cfg.eval_freq > 0 and step % cfg.eval_freq == 0
                queued.log_through(step if saves or last else step - 1)
                if saves:
                    with tracer.span("checkpoint"):
                        self._checkpoint(step)
                    queued.restart_clock(step)
                    t_sync = time.monotonic()
                tracer.end_step()
            tracer.end_step()       # an iteration left by break
            jax.block_until_ready(self.state.params)
            final = queued.last_watched() \
                if self.health is not None and not halted else {}
            if final:
                # The loop's sync trails by one step: check the LAST step's
                # loss too, so a NaN on the final step still trips.
                self._watch(step, loss=final["loss"])
                if self.health.should_halt:
                    self._halt_for_health(step)
                    halted = True
            if not halted and cfg.eval_freq > 0 and step % cfg.eval_freq != 0:
                with self.tracer.span("checkpoint", step=step):
                    self._checkpoint(step)
        except BaseException as e:
            queued.log_on_the_way_out()
            if self.flightrec is not None:
                self.flightrec.record_event(
                    "exception", {"type": type(e).__name__, "message": str(e)})
                self.flightrec.dump(f"crash:{type(e).__name__}")
            raise
        finally:
            tracer.end_step()       # an iteration left by an exception
            self._profile.close()
            if self.exporter is not None:
                self.exporter.stop()
            self.metrics.close()
            if cfg.trace_file:
                path = cfg.trace_file
                if jax.process_index() > 0:
                    path = f"{path}.p{jax.process_index()}"
                self.tracer.write_chrome_trace(path)
            set_default_tracer(self._prev_tracer)
        return self.state

    def _token_spec(self) -> P:
        return P(None, "data") if self.mode == "sp" else P("data", None)

    def _oracle_eval_fn(self):
        """Grad-free eval for tp/pp/ep: gather params to their logical tree
        and run the plain (unsharded) model — fine at checkpoint cadence.
        SP keeps its sharded ring eval (a full-attention clone at the global
        sequence length is exactly the OOM that mode exists to avoid).

        The loss itself comes from the SHARED oracle (runtime/lm_eval.py)
        so the standalone evaluator's EVAL_LM can never diverge from this.
        One trainer-only refinement for ep: live training knows the data
        axis, so the oracle model regains per-device capacity grouping
        (exact vs the sharded forward; the standalone evaluator documents
        the one-group approximation instead)."""
        from ps_pytorch_tpu.runtime.lm_eval import build_lm_oracle
        loss_fn, to_tree = build_lm_oracle(self.cfg)
        if self.mode == "ep":
            import optax
            oracle = self.model.clone(ep_axis=None,
                                      n_groups=self.mesh.shape["data"],
                                      n_local_experts=None)

            from ps_pytorch_tpu.models.moe import lm_variables
            moe_state = dist.all_replicated(self.mesh, self.state.batch_stats)

            @jax.jit
            def loss_fn(params, tokens):  # noqa: F811 — ep refinement
                logits, _ = oracle.apply(lm_variables(params, moe_state),
                                         tokens)
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits[:, :-1].astype(jnp.float32), tokens[:, 1:]).mean()

        # all_replicated, not device_get: tp/pp/ep leaves are sharded over
        # devices that can span hosts.
        params = to_tree(dist.all_replicated(self.mesh, self.state.params))
        return lambda tokens: float(loss_fn(params, tokens))

    def evaluate(self, max_batches: Optional[int] = None) -> dict:
        """Held-out next-token loss + perplexity (the LM analogue of the
        evaluator's Prec@1 oracle). SP evaluates through the SAME sharded
        ring-attention forward as training; tp/pp/ep evaluate via the
        unsharded oracle forward on gathered params."""
        cfg = self.cfg
        val = TokenLoader(self.val_tokens, cfg.batch_size, cfg.lm_seq_len,
                          seed=0, shuffle=False)
        oracle = None if self.mode == "sp" else self._oracle_eval_fn()
        losses = []
        for i, tokens in enumerate(val.epoch(0)):
            if max_batches is not None and i >= max_batches:
                break
            if oracle is not None:
                losses.append(oracle(jnp.asarray(tokens)))
                continue
            tok_g = dist.globalize_replicated(self.mesh, tokens,
                                              spec=self._token_spec())
            losses.append(float(self.eval_fn(self.state.params, tok_g)))
        loss = float(np.mean(losses)) if losses else float("nan")
        return {"loss": loss, "perplexity": float(np.exp(min(loss, 30.0))),
                "batches": len(losses)}
