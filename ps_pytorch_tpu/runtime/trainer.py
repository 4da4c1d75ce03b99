"""The training driver — role-merged replacement for the reference's
master/worker pair.

One Trainer per host drives the jitted SPMD step; there is no separate
parameter-server process. What the reference split across
``SyncReplicasMaster_NN.start()`` (``sync_replicas_master_nn.py:133-197``) and
``DistributedWorker.train()`` (``distributed_worker.py:104-180``) — step
announce, weight broadcast, gradient ship, aggregate, update, checkpoint,
per-phase timing logs — collapses here into: next batch -> step_fn (forward,
backward, masked psum, update, all on-device) -> telemetry -> occasional
checkpoint. The Coordinator supplies the per-step participation mask
(backup-worker/deadline policies) and step control.
"""

import os
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ps_pytorch_tpu import resilience
from ps_pytorch_tpu.config import TrainConfig
from ps_pytorch_tpu.data import prepare_data
from ps_pytorch_tpu.models import build_model
from ps_pytorch_tpu.ops._backend import announce_kernels, cnn_kernels
from ps_pytorch_tpu.optim import build_optimizer
from ps_pytorch_tpu.parallel import (
    create_train_state, make_eval_step, make_train_step, make_mesh,
)
from ps_pytorch_tpu.parallel import dist
from ps_pytorch_tpu.parallel.dp import (
    fetch_replicated, place_state, replica0_batch_stats,
)
from ps_pytorch_tpu.parallel.mesh import local_data_shard
from ps_pytorch_tpu.runtime import checkpoint as ckpt
from ps_pytorch_tpu.runtime.coordinator import Coordinator
from ps_pytorch_tpu.runtime.metrics import MetricsLogger
from ps_pytorch_tpu.runtime.step_queue import QueuedSteps
from ps_pytorch_tpu.telemetry import (
    FlightRecorder, HealthMonitor, MetricsExporter, ProfileWindow, Registry,
    TelemetryAggregator, Tracer,
    declare_kvrep_metrics, declare_resilience_metrics,
    declare_training_metrics,
    derive_step_record, device_memory_record, host_rss_bytes,
    set_default_tracer, set_device_memory_gauges, startup_line,
)
from ps_pytorch_tpu.utils.compile_cache import (
    COMPILE_COUNTERS, count_compiles,
)
from ps_pytorch_tpu.utils.flops import forward_flops, peak_flops_bf16

from ps_pytorch_tpu.data.datasets import sample_shape


def host_prng_key(seed: int) -> np.ndarray:
    """``np.asarray(jax.random.PRNGKey(seed))`` without a device program:
    the legacy threefry key is the seed's high and low 32 bits, and with
    ``jax_enable_x64`` off the seed is first cut to 32 bits, so the high
    word is 0 (tests/test_trainer_pipeline.py pins both settings)."""
    high = (seed >> 32) & 0xFFFFFFFF if jax.config.jax_enable_x64 else 0
    return np.array([high, seed & 0xFFFFFFFF], np.uint32)


class Trainer:
    def __init__(self, cfg: TrainConfig, mesh=None, coordinator: Optional[Coordinator] = None,
                 download: bool = False, injector=None):
        # The tracer exists before anything is built: the constructor is one
        # tree under the span ``setup`` (telemetry/trace.py), and a compile
        # anywhere in it is counted in the span that caused it
        # (utils/compile_cache.py, into the registry too). Installed as the
        # ambient default, so the library layers' span() calls (data build,
        # checkpoint restore and writes, coordinator rounds, KV transport)
        # land on this host's timeline as well.
        count_compiles()
        self.registry = declare_training_metrics(Registry())
        self.tracer = Tracer(registry=self.registry,
                             counters=COMPILE_COUNTERS)
        # The previous default is restored when train() exits so a trainer
        # never leaks its tracer into unrelated code running afterwards.
        self._prev_tracer = set_default_tracer(self.tracer)
        try:
            with self.tracer.setup_span():
                self._build(cfg, mesh, coordinator, download, injector)
        except BaseException:
            set_default_tracer(self._prev_tracer)
            raise

    def _build(self, cfg, mesh, coordinator, download, injector) -> None:
        """The constructor's work, each piece under the child of ``setup``
        that names it (the table of ``PERF.md`` §3)."""
        span = self.tracer.span
        self.cfg = cfg
        with span("backend_init") as found:
            # TPU start where the caller has not paid it already
            found["devices"] = len(jax.devices())
            self.tracer.pid = jax.process_index()
        with span("model_build"):
            self.mesh = mesh if mesh is not None else make_mesh(
                data=cfg.data_axis, model=cfg.model_axis)
            self.n_data = self.mesh.shape["data"]
            self.model = build_model(cfg.network, cfg.num_classes,
                                     cfg.compute_dtype, conv_impl=cfg.conv_impl)
            self.tx = build_optimizer(cfg)
            announce_kernels(cnn_kernels(cfg))
        with span("data_build") as built:
            host_id, num_hosts = local_data_shard()
            self.train_loader, self.test_loader = prepare_data(
                cfg, host_id=host_id, num_hosts=num_hosts, download=download)
            built["bytes"] = sum(
                a.nbytes for loader in (self.train_loader, self.test_loader)
                for a in (loader.x, loader.y, loader._padded) if a is not None)
        sample = (1,) + sample_shape(cfg.dataset)
        from ps_pytorch_tpu.data.augment import input_norm_for
        input_norm = input_norm_for(cfg)
        # Live ops plane: the watchdogs exist BEFORE the step builds,
        # because the nonfinite skip action is an in-graph gate
        # (make_train_step's skip_nonfinite) decided by the health spec.
        self.health: Optional[HealthMonitor] = None
        if cfg.health_spec:
            self.health = HealthMonitor(cfg.health_spec,
                                        registry=self.registry)
        skip_nonfinite = self.health.skip_nonfinite if self.health else False
        if cfg.shard_update:
            from ps_pytorch_tpu.parallel.zero import (
                create_zero_train_state, make_zero_train_step, zero_state_specs,
            )
            create, make, self._state_specs = (
                create_zero_train_state, make_zero_train_step, zero_state_specs)
        else:
            from ps_pytorch_tpu.parallel.dp import state_specs
            create, make, self._state_specs = (
                create_train_state, make_train_step, state_specs)
        with span("state_init") as made:
            # tracing, compiling and running the initialiser; optimizer state
            self.state = create(self.model, self.tx, self.mesh, sample,
                                jax.random.key(cfg.seed))
            made["params"] = sum(
                leaf.size for leaf in jax.tree.leaves(self.state.params))
            made["bytes"] = sum(
                leaf.nbytes for leaf in jax.tree.leaves(self.state))
        with span("step_build"):
            self.step_fn = make(self.model, self.tx, self.mesh, self.state,
                                sync_batchnorm=cfg.sync_batchnorm,
                                remat=cfg.remat, donate=cfg.donate,
                                input_norm=input_norm,
                                skip_nonfinite=skip_nonfinite)
            self.eval_fn = make_eval_step(self.model, input_norm)
        with span("control_plane_build"):
            self._build_control_plane(cfg, coordinator, injector)
        with span("ops_plane_build"):
            self._build_ops_plane(cfg)
        self.start_step = 0
        if cfg.resume:
            self._maybe_resume()

    def _build_control_plane(self, cfg, coordinator, injector) -> None:
        """Fault injector, the coordination store and its shims, election and
        membership, the coordinator, heartbeat and liveness, the preemption
        guard."""
        # Fault plane: an injector passed in (the auto-resume loop threads
        # ONE across restarts so once-only faults stay fired) wins over one
        # built from --fault-spec.
        self.injector = injector
        if self.injector is None and cfg.fault_spec:
            self.injector = resilience.FaultInjector(
                cfg.fault_spec, process_index=jax.process_index())
        self._retrier = None
        self._kvrep = None
        if coordinator is None:
            kv = None
            if cfg.kv_replicas:
                # Quorum-replicated coordination plane (runtime/kvrep.py):
                # the election, membership, masks, and lease all ride N
                # independent backends; losing any minority of them is a
                # survived hiccup instead of a dead control plane.
                from ps_pytorch_tpu.runtime.kvrep import build_replicated_kv
                kv = self._kvrep = build_replicated_kv(
                    cfg, process_index=jax.process_index(),
                    injector=self.injector)
            elif dist.is_multiprocess():
                from ps_pytorch_tpu.runtime.coordinator import DistributedKV
                kv = DistributedKV()  # control plane over the coordination service
            elif (self.injector is not None and self.injector.has_kv_faults) \
                    or cfg.kv_retry_attempts > 1 or cfg.elastic:
                # Single-process: materialize the store here so the
                # resilience shims (fault plane inside, retry plane
                # outside) wrap the SAME kv the Coordinator uses.
                from ps_pytorch_tpu.runtime.coordinator import KVStore
                kv = KVStore()
            if kv is not None:
                kv, _, self._retrier = resilience.wrap_kv_with(
                    kv, cfg, self.injector)
            # Elastic control plane (elastic/): leadership is a LEASE, not
            # an address. The initial leader is --elastic-leader (keep it
            # off process 0 on a real fleet: process 0 hosts the
            # coordination service, so killing it kills the KV itself);
            # any follower can be promoted mid-run, so everyone gets the
            # election object and a liveness factory.
            leader = jax.process_index() == 0
            election = membership = liveness_factory = None
            if cfg.elastic:
                from ps_pytorch_tpu import elastic as elx
                n_proc = max(jax.process_count(), 1)
                pid = jax.process_index()
                initial = cfg.elastic_leader % n_proc
                leader = pid == initial
                hb_timeout = cfg.heartbeat_timeout_s or \
                    3 * (cfg.heartbeat_interval_s or cfg.leader_lease_s)
                election = elx.LeaderElection(
                    kv, "run", pid, n_proc,
                    interval_s=cfg.leader_lease_s, preferred=initial)
                membership = elx.MembershipRegistry(
                    kv, "run", n_proc, self.n_data, timeout_s=hb_timeout)
                liveness_factory = lambda: resilience.LivenessMonitor(  # noqa: E731
                    kv, "run", self.n_data, timeout_s=hb_timeout)
                if leader:
                    election.claim_initial()
            coordinator = Coordinator(
                self.n_data, mode=cfg.mode, num_aggregate=cfg.num_aggregate,
                kill_threshold=cfg.kill_threshold, kv=kv,
                leader=leader,
                lease_interval_s=cfg.leader_lease_s,
                election=election, membership=membership,
                liveness_factory=liveness_factory)
        self.coordinator = coordinator
        # Data-axis replica indices whose devices live on this host (for
        # duration telemetry feeding the kofn/deadline policies).
        self._local_replicas = [
            i for i, row in enumerate(self.mesh.devices)
            if row.flat[0].process_index == jax.process_index()]
        # Liveness: this host beats for its replicas; the leader folds
        # missed beats into the participation mask (crashed != slow).
        # With --elastic the MemberAnnouncer owns the beat (same hb/ keys,
        # plus the join announcement the membership registry folds in).
        self.heartbeat = None
        self.announcer = None
        if cfg.elastic and self.coordinator.election is not None:
            from ps_pytorch_tpu import elastic as elx
            self.announcer = elx.MemberAnnouncer(
                self.coordinator.kv, self.coordinator.run_id,
                jax.process_index(), self._local_replicas,
                interval_s=cfg.heartbeat_interval_s or cfg.leader_lease_s)
            self.announcer.join()
            self.heartbeat = self.announcer.heartbeat
            from ps_pytorch_tpu.telemetry import declare_elastic_metrics
            declare_elastic_metrics(self.registry)
            self._elastic_drained = {"coord": 0, "member": 0, "elect": 0}
            self._last_member_epoch = 0
        elif cfg.heartbeat_interval_s > 0:
            self.heartbeat = resilience.Heartbeat(
                self.coordinator.kv, self.coordinator.run_id,
                self._local_replicas, interval_s=cfg.heartbeat_interval_s)
        if self.heartbeat is not None and self.coordinator.leader and \
                self.coordinator.liveness is None:
            self.coordinator.liveness = resilience.LivenessMonitor(
                self.coordinator.kv, self.coordinator.run_id,
                self.n_data,
                timeout_s=(cfg.heartbeat_timeout_s
                           or 3 * (cfg.heartbeat_interval_s
                                   or cfg.leader_lease_s)))
        # SIGTERM/preemption: the handler only flags; the loop writes an
        # emergency checkpoint at the next step boundary.
        self._preempt = resilience.PreemptionGuard()

    def _build_ops_plane(self, cfg) -> None:
        """Metrics logger, flight recorder, exporter, the MFU's inputs, the
        cross-host timeline, the profiler window."""
        self.metrics = MetricsLogger(cfg.metrics_file, cfg.log_every,
                                     process_index=jax.process_index(),
                                     num_processes=jax.process_count())
        # Flight recorder: armed whenever any ops-plane surface is on; its
        # rings cost O(capacity) and only dump() touches the disk.
        self.flightrec: Optional[FlightRecorder] = None
        flight_path = cfg.flight_file or (
            os.path.join(cfg.train_dir, "flightrec.json")
            if (cfg.health_spec or cfg.metrics_port > 0) else "")
        if flight_path:
            if jax.process_index() > 0:
                flight_path = f"{flight_path}.p{jax.process_index()}"
            self.flightrec = FlightRecorder(flight_path, tracer=self.tracer,
                                            registry=self.registry)
        # /metrics + /healthz exporter; each process binds its own port so
        # a scraper sees every host of a multi-process run.
        self.exporter: Optional[MetricsExporter] = None
        if cfg.metrics_port > 0:
            collect = [self._update_memory_gauges]
            if self.injector is not None or self._retrier is not None:
                # Resilience counters reach the SCRAPE endpoint, not just
                # the JSONL: refresh them from the live fault/retry
                # snapshots on every render.
                declare_resilience_metrics(self.registry)
                collect.append(self._pump_resilience_metrics)
            if self._kvrep is not None:
                # Replication-plane health on the SAME scrape endpoint:
                # quorum failures, ejections, rejoins, and the live
                # healthy-backend gauge.
                declare_kvrep_metrics(self.registry)
                collect.append(self._pump_kvrep_metrics)
            self.exporter = MetricsExporter(
                self.registry,
                port=cfg.metrics_port + jax.process_index(),
                health_fn=self._health_status,
                collect=collect).start()
        # MFU inputs: per-step FLOPs are traced lazily at step 1 (the step
        # must exist first); the chips' peak is a device_kind lookup (None
        # off-TPU -> mfu reported as null, never a fiction).
        self._flops_per_step: Optional[int] = None
        self._n_chips = int(self.mesh.devices.size)
        self._peak_per_chip = peak_flops_bf16(
            self.mesh.devices.flat[0].device_kind)
        # Cross-host step telemetry over the control-plane KV: every process
        # publishes per-step durations + phase summaries; the leader drains
        # them into ONE merged per-replica timeline JSONL.
        timeline = cfg.timeline_file or (
            f"{cfg.metrics_file}.timeline"
            if dist.is_multiprocess() and cfg.metrics_file else "")
        self._telemetry: Optional[TelemetryAggregator] = None
        if timeline:
            self._telemetry = TelemetryAggregator(
                self.coordinator.kv, jax.process_index(),
                jax.process_count(), run_id=self.coordinator.run_id)
            if jax.process_index() == 0:
                self._telemetry.open_timeline(timeline)
        # jax.profiler trace window (SURVEY §5.1: the reference's hand-rolled
        # timers + our structured lines, plus real profiler integration).
        self._profile = ProfileWindow(cfg.profile_dir, cfg.profile_steps)

    def _maybe_resume(self) -> None:
        """NEW vs the reference (which always restarts at step 1,
        ``sync_replicas_master_nn.py:18``): restore-to-train.

        Resume is VALID-latest, not latest: a checkpoint whose manifest
        hashes fail (torn write, bitrot, injected ckpt_corrupt) is skipped
        and the walk continues to the previous committed step."""
        with self.tracer.span("resume") as found:
            if ckpt.latest_step(self.cfg.train_dir) is None:
                return
            template = fetch_replicated(self.mesh, self.state) \
                if dist.is_multiprocess() else self.state
            got = ckpt.load_latest_valid(self.cfg.train_dir, template)
            if got is None:
                return
            state, meta, _, step = got
            self.state = place_state(self.mesh, state,
                                     self._state_specs(state))
            self.start_step = found["restored_step"] = int(meta["step"])
            # Replay the data stream to the restore point so a resumed run
            # sees the SAME batch sequence an uninterrupted run would
            # (bit-for-bit resume needs params AND stream position; the PRNG
            # key is already step-derived).
            self.train_loader.fast_forward(self.start_step)
        print(f"RESUME from {ckpt.checkpoint_path(self.cfg.train_dir, step)} "
              f"at step {self.start_step}")

    def _checkpoint(self, step: int) -> None:
        # Multi-process: gather 'data'-sharded BN leaves (a collective — every
        # host participates), then ONLY process 0 writes. The reference had
        # every worker overwrite the same NFS file (distributed_worker.py:
        # 175-177); replaying that on a shared filesystem races rmtree/rename
        # between hosts, so checkpoint authority stays with the leader.
        if dist.is_multiprocess():
            state = fetch_replicated(self.mesh, self.state)
            if jax.process_index() != 0:
                return
        else:
            state = self.state
        extra = None
        if self.coordinator.election is not None:
            # Stamp which leadership epoch committed these weights —
            # serving /healthz surfaces it for the checkpoints it reloads.
            extra = {"leader_epoch": self.coordinator.election.epoch,
                     "leader_pid": jax.process_index()}
        ckpt.save_checkpoint(self.cfg.train_dir, step, state,
                             config_json=self.cfg.to_json(),
                             compress=self.cfg.compress_grad,
                             codec_level=self.cfg.codec_level,
                             extra_meta=extra)
        if self.injector is not None:
            # ckpt_corrupt faults strike AFTER the atomic commit — the torn
            # artifact the manifest check must catch, not a failed write.
            self.injector.after_checkpoint(self.cfg.train_dir, step)
        if self.cfg.ckpt_keep > 0:
            ckpt.prune_checkpoints(self.cfg.train_dir, self.cfg.ckpt_keep)

    def resilience_stats(self) -> dict:
        """Flat counters from every resilience plane that is active."""
        out: dict = {}
        if self.injector is not None:
            out.update(self.injector.snapshot())
        if self._retrier is not None:
            out.update(self._retrier.snapshot())
        if self._kvrep is not None:
            out.update(self._kvrep.snapshot())
        if self.coordinator.liveness is not None:
            out.update(self.coordinator.liveness.snapshot())
        out["mask_changes"] = self.coordinator.stats.get("mask_changes", 0)
        if self.coordinator.election is not None:
            out["leader_epoch"] = self.coordinator.election.epoch
            out["elections"] = self.coordinator.stats.get("elections", 0)
        if self.coordinator.membership is not None:
            m = self.coordinator.membership.snapshot()
            out["membership_changes"] = m["membership_changes"]
            out["world_size"] = m["world_size"] or self.n_data
        return out

    def _resilience_active(self) -> bool:
        # Gate: vanilla runs keep the exact pre-resilience metrics schema;
        # counters appear only when something resilience-y is configured or
        # the retry plane actually absorbed an error.
        if self.injector is not None or self.heartbeat is not None or \
                self.cfg.elastic:
            return True
        if self._retrier is not None:
            s = self._retrier.snapshot()
            return s.get("kv_retries", 0) > 0 or s.get("kv_giveups", 0) > 0
        return False

    # ---- live ops plane ----
    def _update_memory_gauges(self) -> None:
        """HBM/RSS watermarks into the registry — called per step AND as an
        exporter collect hook, so a scrape between steps still sees fresh
        memory pressure."""
        set_device_memory_gauges(self.registry, device_memory_record())
        self.registry.set("host_rss_bytes", host_rss_bytes())

    def _pump_resilience_metrics(self) -> None:
        """Refresh resilience counters from the live fault/retry snapshots
        (delta-inc: Registry counters are monotonic, the snapshots are the
        source of truth). Runs as a MetricsExporter collect hook."""
        snap = {}
        if self.injector is not None:
            snap.update(self.injector.snapshot())
        if self._retrier is not None:
            snap.update(self._retrier.snapshot())
        for name, value in snap.items():
            try:
                delta = value - self.registry.get(name)
            except KeyError:
                continue            # snapshot key with no declared metric
            if delta > 0:
                self.registry.inc(name, delta)

    def _pump_kvrep_metrics(self) -> None:
        """kvrep_* counters/gauges from the live ReplicatedKV — same
        delta-inc discipline as the resilience pump."""
        for name, value in self._kvrep.snapshot().items():
            try:
                delta = value - self.registry.get(name)
            except KeyError:
                continue
            if delta > 0:
                self.registry.inc(name, delta)
        for name, value in self._kvrep.gauges().items():
            try:
                self.registry.set(name, value)
            except KeyError:
                continue

    def _health_status(self) -> dict:
        """/healthz body: watchdog state (stall evaluated on demand from the
        exporter thread — a wedged step loop can't self-report) + identity."""
        body = self.health.status() if self.health is not None else {"ok": True}
        body["process_index"] = jax.process_index()
        body["run_id"] = self.coordinator.run_id
        # Leader identity: static role without elections, live epoch'd
        # identity with them (who leads, which epoch, am I it).
        body["leader"] = bool(self.coordinator.leader)
        if self.coordinator.election is not None:
            body["leader_epoch"] = self.coordinator.election.epoch
            body["leader_owner"] = self.coordinator.election.owner
        return body

    def _elastic_step(self, step: int) -> None:
        """Per-step elastic bookkeeping: leader-epoch/world-size gauges,
        membership-change counter, and election/membership events into the
        flight recorder. On a membership-epoch change with --shard-update,
        the new ZeRO shard plan is recomputed and recorded — the
        rebalancing evidence for post-mortems (elastic/rebalance.py)."""
        el = self.coordinator.election
        mem = self.coordinator.membership
        if el is None:
            return
        r = self.registry
        r.set("leader_epoch", el.epoch)
        if mem is not None:
            snap = mem.snapshot()
            r.set("world_size", snap["world_size"] or self.n_data)
            delta = snap["membership_changes"] - r.get("membership_changes")
            if delta > 0:
                r.inc("membership_changes", delta)
        e_delta = self.coordinator.stats.get("elections", 0) - \
            r.get("elections")
        if e_delta > 0:
            r.inc("elections", e_delta)
        if self.flightrec is not None:
            for src, events in (("coord", self.coordinator.events),
                                ("member", mem.events if mem else []),
                                ("elect", el.events)):
                seen = self._elastic_drained[src]
                for ev in events[seen:]:
                    kind = "membership" if src == "member" else "election"
                    self.flightrec.record_event(kind, dict(ev))
                self._elastic_drained[src] = len(events)
        if mem is not None and mem.epoch != self._last_member_epoch:
            self._last_member_epoch = mem.epoch
            if self.cfg.shard_update and mem.members:
                from ps_pytorch_tpu.elastic import plan_shards
                size = sum(int(np.prod(l.shape)) for l in
                           jax.tree.leaves(self.state.params))
                plan = plan_shards(size, len(mem.members))
                print(f"REBALANCE shard plan epoch {mem.epoch}: "
                      f"{plan.n} shards x {plan.chunk} params")
                if self.flightrec is not None:
                    self.flightrec.record_event("shard_replan", {
                        "epoch": mem.epoch, "n_shards": plan.n,
                        "chunk": plan.chunk, "step": step})

    def _ops_step(self, step: int, *, loss=None, grad_norm=None,
                  nonfinite=None, step_time=None, data_time=None,
                  dispatch_ahead: int = 0) -> None:
        """One step's worth of live-ops bookkeeping: registry gauges, memory
        watermarks, flight-recorder step record, and the health watchdogs.
        loss/grad_norm/nonfinite are the PREVIOUS step's values — already on
        the host via the 1-deep pipeline's existing sync, so this adds no
        device round-trip."""
        r = self.registry
        r.inc("train_steps")
        r.inc("dispatch_ahead_steps", dispatch_ahead)
        r.set("train_step", step)
        if loss is not None:
            r.set("train_loss", loss)
        if grad_norm is not None:
            r.set("train_grad_norm", grad_norm)
        if step_time is not None and step_time > 0:
            r.set("train_step_time_s", step_time)
            r.observe("train_step_latency_s", step_time)
            r.set("train_examples_per_sec", self.cfg.batch_size / step_time)
        if data_time is not None:
            r.set("train_data_time_s", data_time)
        self._update_memory_gauges()
        if self.cfg.elastic:
            self._elastic_step(step)
        if self.flightrec is not None:
            self.flightrec.record_step(step, loss=loss, grad_norm=grad_norm,
                                       step_time=step_time,
                                       data_time=data_time)
        self._watch(step, loss=loss, grad_norm=grad_norm,
                    nonfinite=nonfinite, step_time=step_time)

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
            self.flightrec.dump(f"watchdog:{ev.detector}",
                                extra={"halt": ev.to_dict()})
        print(f"HEALTH halt at step {step}: {ev.message}")

    def train(self):
        """Run to max_steps (or epochs * steps-per-epoch, whichever is
        smaller — reference semantics: both bounds live on the CLI,
        ``distributed_nn.py:34-36``).

        The loop has one rule: nothing between two dispatches waits for the
        device, so one step is always queued behind the running one and the
        host's work (loader, put, dispatch, bookkeeping) hides under the
        device's. Its one wait, ``device_sync``, reads the PREVIOUS step's
        loss with this step already queued; a step's record is written once
        the next step is queued, and the per-step key is made on the host.
        What trails by a step, therefore: records, the watchdogs, and a
        halt's checkpoint (of the state one step later). The bookkeeping is
        runtime/step_queue.py's, shared with ``LMTrainer``."""
        cfg = self.cfg
        steps_per_epoch = max(len(self.train_loader), 1)
        epoch_budget = cfg.epochs * steps_per_epoch if cfg.epochs > 0 else cfg.max_steps
        last_step = min(cfg.max_steps, epoch_budget)
        step = self.start_step
        preempted = False
        halted = False
        tracer = self.tracer
        self._preempt.install()
        # A logged step's record (STEP line, JSONL) is written once the NEXT
        # step is queued, or where the device is drained anyway (a
        # checkpoint, the loop's last step, an exception on its way out):
        # runtime/step_queue.py, shared with LMTrainer.
        # Only this run's first record holds its set-up (the STARTUP line).
        once = {"setup": None}
        first_step = step + 1

        def write_record(step, own, *, step_time, data_time, dispatch_ahead):
            extra = derive_step_record(
                step_time_s=step_time, data_time_s=data_time,
                examples=cfg.batch_size,
                flops_per_step=self._flops_per_step,
                peak_flops_per_chip=self._peak_per_chip,
                n_chips=self._n_chips)
            if self._resilience_active():
                extra.update(self.resilience_stats())
            if once:
                once["setup"] = tracer.startup_summary(first_step)
                print(startup_line(once["setup"]))
            self.metrics.log_step(
                step, (step - 1) // steps_per_epoch,
                loss=own["loss"], acc=own["accuracy"],
                participating=own["participating"],
                step_time=step_time, data_time=data_time,
                dispatch_ahead=dispatch_ahead,
                compiles=tracer.counted_through(step, "programs"),
                phases=tracer.step_summary(step), **extra, **once)
            once.clear()

        queued = QueuedSteps(
            tracer, step, write_record,
            record=("loss", "accuracy", "participating"),
            watch=("loss", "grad_norm", "nonfinite"))

        try:
            while step < last_step:
                step += 1
                self._profile.on_step(step)
                # The iteration's root span: every phase below is its child,
                # so its self time is what no span explains. It closes at
                # the iteration's end, or in the finally on any other exit.
                tracer.begin_step(step)
                if self.injector is not None:
                    # Before any KV/device work for this step: the crash
                    # models a process dying BETWEEN steps, so the last
                    # committed checkpoint is the recovery point.
                    self.injector.maybe_crash(step)
                with tracer.span("coordinator"):
                    self.coordinator.announce_step(step)
                    if self.heartbeat is not None:
                        self.heartbeat.beat(step)
                t0 = time.monotonic()
                with tracer.span("data_wait"):
                    x, y = self.train_loader.next_batch()
                t_data = time.monotonic() - t0
                with tracer.span("coordinator"):
                    mask = self.coordinator.participation_mask(step)
                if self.injector is not None:
                    # Role-addressed kill AFTER the mask decision: the
                    # leader dies with this step's mask already published,
                    # the worst-case handoff (followers consume it, then
                    # find the lease stale at step+1 and elect).
                    self.injector.maybe_kill_leader(
                        step, is_leader=self.coordinator.leader)
                if self.injector is not None and \
                        self.injector.maybe_poison(step):
                    # grad_nan fault: NaN rides the mask into the step's
                    # psums (loss/grad-average/grad-norm all blow up) with
                    # no recompile; the all-NaN mask also fails the
                    # `msum > 0` guard so params stay clean regardless.
                    mask = np.asarray(mask, np.float32) * np.nan
                    print(f"FAULT grad_nan: poisoned mask at step {step}")
                    if self.flightrec is not None:
                        self.flightrec.record_event(
                            "fault_grad_nan", {"step": step})
                # Legacy uint32[2] key: globalizable as a plain replicated array
                # (typed key dtypes can't cross make_array_from_callback).
                with tracer.span("rng_key"):
                    key = host_prng_key(cfg.seed * 100003 + step)
                x, y = np.asarray(x), np.asarray(y)
                with tracer.span("batch_put", bytes=x.nbytes + y.nbytes):
                    xg = dist.globalize_batch(self.mesh, x)
                    yg = dist.globalize_batch(self.mesh, y)
                    mg = dist.globalize_replicated(
                        self.mesh, np.asarray(mask, np.float32))
                    kg = dist.globalize_replicated(
                        self.mesh, key, spec=jax.sharding.PartitionSpec())
                if self._flops_per_step is None:
                    # One abstract trace of the full fwd+bwd+update program
                    # (nothing executes).
                    with tracer.span("flops_trace"):
                        self._flops_per_step = forward_flops(
                            self.step_fn, self.state, xg, yg, mg, kg)
                with tracer.span("host_dispatch"):
                    # Rebinding the state releases the donated buffers'
                    # handles (0.2 ms on one chip, 0.75 ms on four): part of
                    # the dispatch, as in runtime/lm_trainer.py.
                    self.state, m = self.step_fn(self.state, xg, yg, mg, kg)
                    ahead = queued.ahead()
                if step % cfg.log_every == 0 or step == last_step:
                    queued.log_later(step, m, data_time=t_data,
                                     dispatch_ahead=ahead)
                if cfg.inject_step_delay > 0 and \
                        jax.process_index() == cfg.inject_delay_process:
                    # Fault injection (tests/ops drills): make THIS host a
                    # straggler. The reference had no fault injection at all
                    # (SURVEY §5.3); its stragglers were organic EC2 noise.
                    time.sleep(cfg.inject_step_delay)
                # The loop's one wait for the device: the PREVIOUS step's
                # loss, read with this step already queued. So the
                # per-iteration wall time is a true per-step duration —
                # reported EVERY step, so the kofn/deadline policies never act
                # on stale numbers (the reference timed every worker step,
                # distributed_worker.py:169-173) — and the watchdogs and the
                # previous step's record get their values at no further sync.
                prev = queued.sync(m)
                t_step = time.monotonic() - t0
                with tracer.span("ops_step"):
                    for r in self._local_replicas:
                        self.coordinator.report_duration(r, step, t_step)
                    self._ops_step(step, step_time=t_step, data_time=t_data,
                                   dispatch_ahead=ahead, **prev)
                if self.health is not None and self.health.should_halt:
                    queued.log_through(step)
                    self._halt_for_health(step)
                    halted = True
                    break
                if self._telemetry is not None:
                    with tracer.span("telemetry_publish"):
                        rec = {
                            "step_time": round(t_step, 6),
                            "data_time": round(t_data, 6),
                            "phases": tracer.step_summary(step)}
                        if self._resilience_active():
                            rec["resilience"] = self.resilience_stats()
                        self._telemetry.publish_step(step, rec)
                        self._telemetry.drain_to_file()  # no-op off-leader
                # The previous step's record: its metrics finished under
                # device_sync above, so these reads wait for nothing. Where
                # the device is drained anyway (a checkpoint, the last step)
                # this step's record goes with it, in one read.
                saves = cfg.eval_freq > 0 and step % cfg.eval_freq == 0
                drains = saves or step == last_step or self._preempt.triggered
                queued.log_through(step if drains else step - 1)
                if saves:
                    with tracer.span("checkpoint"):
                        self._checkpoint(step)
                    queued.restart_clock(step)
                if self._preempt.triggered:
                    # SIGTERM (preemption notice): commit an emergency
                    # checkpoint at this step boundary and leave cleanly so
                    # auto-resume (or the next scheduling) restores here.
                    queued.log_through(step)   # a notice that came this moment
                    with tracer.span("checkpoint"):
                        self._checkpoint(step)
                    print(f"PREEMPT emergency checkpoint at step {step}")
                    if self.flightrec is not None:
                        self.flightrec.dump("sigterm", extra={"step": step})
                    preempted = True
                    break
                tracer.end_step()
            tracer.end_step()       # an iteration left by break
            jax.block_until_ready(self.state.params)
            final = queued.last_watched() \
                if self.health is not None and not halted else {}
            if final:
                # The loop's sync point trails by one step: check the LAST
                # step's metrics too, so a NaN on the final step still trips.
                self._watch(step, **final)
                if self.health.should_halt and not preempted:
                    self._halt_for_health(step)
                    halted = True
            if cfg.eval_freq > 0 and step % cfg.eval_freq != 0 \
                    and not preempted and not halted:
                with self.tracer.span("checkpoint", step=step):
                    self._checkpoint(step)
        except BaseException as e:
            queued.log_on_the_way_out()
            # The flight dump happens while the exception is in flight so a
            # crash post-mortem exists even when nothing catches it upstream;
            # dump() itself never raises (it must not mask the real error).
            if self.flightrec is not None:
                self.flightrec.record_event(
                    "exception", {"type": type(e).__name__, "message": str(e)})
                self.flightrec.dump(f"crash:{type(e).__name__}")
            raise
        finally:
            self._preempt.uninstall()
            if self.exporter is not None:
                self.exporter.stop()
            # Telemetry sinks close on ANY exit — a trainer exception must
            # not leak the JSONL handle or lose the trace collected so far.
            tracer.end_step()       # an iteration left by an exception
            self._profile.close()
            self.metrics.close()
            if cfg.trace_file:
                path = cfg.trace_file
                if jax.process_index() > 0:
                    path = f"{path}.p{jax.process_index()}"
                self.tracer.write_chrome_trace(path)
            if self._telemetry is not None:
                self._telemetry.close(
                    final_step=step if jax.process_index() == 0 else None)
            set_default_tracer(self._prev_tracer)
        return self.state

    def evaluate(self, max_batches: Optional[int] = None) -> dict:
        """Top-1/top-5/loss over the test loader (reference
        ``_evaluate_model``, ``distributed_evaluator.py:90-106``)."""
        if dist.is_multiprocess():
            # Host-local copies: each host evaluates the full test set locally
            # (the reference evaluator is likewise a standalone local process).
            st = fetch_replicated(self.mesh, self.state)
            params = st.params
            bstats = jax.tree.map(lambda a: a[0], st.batch_stats)
        else:
            params = self.state.params
            bstats = replica0_batch_stats(self.state)
        from ps_pytorch_tpu.runtime.evaluator import accumulate_eval
        return accumulate_eval(self.eval_fn, params, bstats,
                               self.test_loader.epoch(0), max_batches)
