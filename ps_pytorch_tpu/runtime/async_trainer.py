"""Cross-process asynchronous (stale-gradient) training — one slice per OS
process, gradients crossing the process/DCN boundary as codec-compressed
bytes over the coordination-service KV (parallel/transport.py).

This is the multi-machine async story the reference ran (workers shipping
staleness-tagged gradients to a master across ranks,
``resnet_split.py:25-42`` + ``sync_replicas_master_nn.py:156-186``),
re-expressed TPU-natively:

- each process drives an SPMD slice over its OWN local devices (in-slice
  gradient averaging is an in-graph psum riding ICI);
- process 0 is the PS leader: it owns the optimizer state (like the
  reference master, ``optim/sgd.py:80-90`` momentum lives master-side),
  pools cross-process contributions with staleness metadata
  (parallel/async_dp.StaleGradientAggregator), applies fresh-enough updates,
  and publishes canonical weights;
- followers fetch canonical weights every ``fetch_every`` of their own
  steps, so a slow follower naturally submits stale gradients — exercising
  drop/decay exactly as the reference's timeout-kill discards identifiably
  late gradients (``resnet_split.py:617-728``).

Within one process (no jax.distributed), use runtime/multislice.py instead:
same semantics with device-group slices.
"""

import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ps_pytorch_tpu import resilience
from ps_pytorch_tpu.config import TrainConfig
from ps_pytorch_tpu.data.datasets import DataLoader, load_arrays, sample_shape
from ps_pytorch_tpu.models import build_model
from ps_pytorch_tpu.ops._backend import announce_kernels, cnn_kernels
from ps_pytorch_tpu.optim import build_optimizer
from ps_pytorch_tpu.parallel.async_dp import StaleGradientAggregator
from ps_pytorch_tpu.parallel.dp import apply_optimizer, make_eval_step
from ps_pytorch_tpu.parallel.mesh import make_mesh
from ps_pytorch_tpu.parallel.transport import KVGradientTransport
from ps_pytorch_tpu.runtime import checkpoint as ckpt
from ps_pytorch_tpu.runtime.coordinator import DistributedKV, KVStore
from ps_pytorch_tpu.runtime.metrics import MetricsLogger
from ps_pytorch_tpu.runtime.multislice import make_slice_grad_fn
from ps_pytorch_tpu.telemetry import (
    MetricsExporter, Registry, Tracer, declare_elastic_metrics,
    declare_hierarchy_metrics, declare_integrity_metrics,
    declare_kvrep_metrics, declare_resilience_metrics,
    declare_training_metrics, device_memory_record, host_rss_bytes,
    set_default_tracer, set_device_memory_gauges,
)


class AsyncTrainer:
    """PS-style async training across jax.distributed processes."""

    def __init__(self, cfg: TrainConfig, kv: Optional[KVStore] = None):
        self.cfg = cfg
        self.pid = jax.process_index()
        self.n = jax.process_count()
        self.leader = self.pid == 0
        devices = jax.local_devices()
        self.mesh = make_mesh(data=len(devices), devices=devices)
        from jax.sharding import NamedSharding, PartitionSpec as _P
        # Canonical placement for params fetched/restored from the wire:
        # replicated over THIS process's local mesh (uncommitted arrays work
        # too, but explicit placement keeps every path uniform).
        self._rep = NamedSharding(self.mesh, _P())
        self.model = build_model(cfg.network, cfg.num_classes, cfg.compute_dtype,
                                 conv_impl=cfg.conv_impl)
        self.tx = build_optimizer(cfg)
        announce_kernels(cnn_kernels(cfg))

        shape = (1,) + sample_shape(cfg.dataset)
        variables = self.model.init(jax.random.key(cfg.seed),
                                    jnp.zeros(shape, jnp.float32), train=False)
        # Same seed everywhere -> every process starts from identical weights
        # (the reference broadcasts initial weights; here the bcast is free).
        # Canonical params/opt state/BN stats live ON DEVICE for the whole
        # run — the wire boundary (device_get/put) is crossed only at
        # publish/fetch/submit, never per local step. The reference master
        # updated host-side numpy every step (sync_replicas_master_nn.py:
        # 204-208); keeping residency is the TPU-first inversion of that.
        self.params = variables["params"]
        self.has_bn = "batch_stats" in variables
        bs0 = variables.get("batch_stats", {})
        per = len(devices)
        self._bs = jax.tree.map(
            lambda a: jnp.tile(a[None], (per,) + (1,) * a.ndim), bs0)
        from ps_pytorch_tpu.data.augment import input_norm_for
        self._input_norm = input_norm_for(cfg)
        self.grad_fn = make_slice_grad_fn(self.model, self.mesh, self.has_bn,
                                          self._input_norm)

        # The injector is built BEFORE the KV so the per-backend fault
        # kinds (kv_backend_kill/wipe) can be threaded INSIDE the quorum
        # layer while the logical kinds still wrap outside it.
        injector = None
        if cfg.fault_spec:
            injector = resilience.FaultInjector(cfg.fault_spec,
                                                process_index=self.pid)
        self._kvrep = None
        if kv is None:
            if cfg.kv_replicas:
                # Quorum-replicated coordination plane (runtime/kvrep.py):
                # N independent backends under the same KV interface —
                # elections, membership, the wire, and the ledger all run
                # unchanged while any minority of backends dies.
                from ps_pytorch_tpu.runtime.kvrep import build_replicated_kv
                kv = self._kvrep = build_replicated_kv(
                    cfg, process_index=self.pid, injector=injector)
            else:
                kv = DistributedKV() if self.n > 1 else KVStore()
        # Resilience shims around the control plane: seeded fault injection
        # inside (when --fault-spec names kv faults), jittered-backoff
        # retries outside — the transport and aggregator see one hardened
        # KV without knowing either layer exists.
        kv, self.injector, self._retrier = resilience.wrap_kv_with(
            kv, cfg, injector)
        # --shard-wire (parallel/zero_wire.py) publishes per-shard params
        # through this same hardened KV; keep the handle.
        self._kv = kv
        self._zw_rd = None           # lazy reader-mode updater (followers)
        self._zw_ptr_version = -1    # last version whose shards are on the KV
        # Elastic control plane (--elastic): the PS-leader role becomes a
        # lease over the coordination KV instead of the pid==0 birthright.
        # The initial leader is --elastic-leader (keep it OFF process 0 in
        # multi-process runs: process 0 hosts the coordination service, so
        # killing it in a drill takes the KV down with it). Any follower
        # that sees the lease go stale campaigns; the winner promotes to
        # PS duty mid-run (_promote) and the run completes.
        self.election = None
        self.membership = None
        self.announcer = None
        self.elect_latency_s = 0.0
        if cfg.elastic:
            from ps_pytorch_tpu import elastic as elx
            initial = cfg.elastic_leader % max(self.n, 1)
            self.leader = self.pid == initial
            run_id = f"async-{cfg.seed}"
            lease_s = cfg.leader_lease_s or 1.0
            self.election = elx.LeaderElection(
                kv, run_id, self.pid, self.n, interval_s=lease_s,
                preferred=initial)
            self.announcer = elx.MemberAnnouncer(
                kv, run_id, self.pid, [self.pid],
                interval_s=cfg.heartbeat_interval_s or lease_s)
            hb_timeout = cfg.heartbeat_timeout_s or 3 * (
                cfg.heartbeat_interval_s or lease_s)
            # One "replica" per process in async mode — membership tracks
            # processes, not data shards (there is no participation mask).
            self.membership = elx.MembershipRegistry(
                kv, run_id, self.n, self.n, timeout_s=hb_timeout)
            if self.leader:
                self.election.claim_initial()
            self.announcer.join()
        # Wire format honors the same flags as the in-process aggregator
        # (--compress-grad / --grad-codec): off -> raw npy framing;
        # blosc -> C++ lossless; int8 -> on-device Pallas quantization, the
        # components then blosc-framed (4x smaller before the bytes leave
        # the chip); int8lat/topk/randk -> homomorphic payloads the leader
        # sums IN THE COMPRESSED DOMAIN (compression/codecs.py) without
        # ever materializing a per-contributor float32 tree.
        from ps_pytorch_tpu.compression.codecs import (
            HOMOMORPHIC_GRAD_CODECS, encode_leaves,
        )
        self._wire_int8 = cfg.compress_grad and cfg.grad_codec == "int8"
        self._wire_homo = cfg.compress_grad and \
            cfg.grad_codec in HOMOMORPHIC_GRAD_CODECS
        self._ef = None           # sender-side EF residuals (lazy, --ef)
        self._enc_pool = None     # encode-side bucket pool (lazy)
        chan_codec = "blosc" if cfg.compress_grad else "raw"
        if self._wire_homo:
            # Template = a zero-gradient encode: payload shapes are
            # data-independent (k from --grad-topk-frac, "v" from the leaf
            # shape), so one throwaway encode fixes the wire structure.
            leaves, treedef = jax.tree.flatten(self.params)
            grad_template = jax.tree.unflatten(
                treedef, encode_leaves(
                    cfg.grad_codec,
                    [np.zeros(np.shape(l), np.float32) for l in leaves],
                    slice_id=0, step=0, frac=cfg.grad_topk_frac))
        elif self._wire_int8:
            grad_template = jax.tree.map(
                lambda a: {"v": np.zeros(0, np.int8),
                           "s": np.zeros(0, np.float32)}, self.params)
        else:
            grad_template = self.params
        # Shape/size reference for wire decode (structure only, no storage).
        self._param_tpl = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), self.params)
        # Canonical publish carries params AND the leader's replica-0 BN
        # stats, so every process evaluates identical state (the reference
        # evaluator scores the master's checkpoint, which includes whatever
        # BN stats the checkpointing worker had).
        self._bs0 = lambda: jax.tree.map(lambda a: a[0], self._bs)
        # Under --shard-wire the canonical params travel as per-shard zw
        # keys (pipelined, GC'd per round) instead of one monolithic
        # transport publish — only the (small) BN stats keep riding the
        # transport's param channel. That asymmetry IS the wire win.
        param_template = {"bs0": self._bs0()} if cfg.shard_wire \
            else {"params": self.params, "bs0": self._bs0()}
        # Overlapped wire (--wire-bucket-mb/--wire-workers): the channels
        # sync+encode+put bucket k while bucket k+1 is still on device, so
        # publish cost hides under the tail of backward instead of landing
        # after it. 0 restores the blocking single-payload schedule.
        wire_bucket_bytes = int(cfg.wire_bucket_mb * (1 << 20))
        self._wire_overlap = wire_bucket_bytes > 0
        self._hier = cfg.sync_topology == "hier"
        # Gradient integrity (--grad-integrity, resilience/integrity.py):
        # the leader-side ledger screens pooled contributions before the
        # sum; in hier mode a second member-space ledger rides the group
        # hop (whichever process holds the group lease screens its
        # members). Wire digests (layer 1) need no ledger — transport.py
        # stamps/verifies crc32 per chunk unconditionally.
        self._integrity = None        # leader ledger over contributor ids
        self._group_integrity = None  # member-space ledger (hier group hop)
        if self._hier:
            # 2-tier multi-hop sync (parallel/hierarchy.py): members
            # publish to key-namespaced intra-group channels, the group
            # aggregator (a group-scoped elastic lease) re-encodes and
            # publishes one payload per group upward, the root (PS leader)
            # pools GROUP aggregates. Config validation already pinned
            # compress_grad + a homomorphic codec.
            from ps_pytorch_tpu.parallel.hierarchy import (
                HierarchicalKVTransport,
            )
            self._group_integrity = self._make_integrity()
            self.transport = HierarchicalKVTransport(
                kv, self.n, grad_template=grad_template,
                param_template=param_template, run_id=f"async-{cfg.seed}",
                pid=self.pid, group_size=cfg.sync_group_size,
                codec=cfg.grad_codec, staleness_limit=cfg.staleness_limit,
                topk_frac=cfg.grad_topk_frac, chan_codec=chan_codec,
                level=cfg.codec_level, bucket_bytes=wire_bucket_bytes,
                workers=cfg.wire_workers, hop_retries=cfg.hier_hop_retries,
                lease_interval_s=cfg.leader_lease_s or 1.0,
                integrity=self._group_integrity)
            print(f"HIER topology pid {self.pid}: "
                  f"{self.transport.describe()}", flush=True)
        else:
            self.transport = KVGradientTransport(
                kv, self.n, grad_template=grad_template,
                param_template=param_template, run_id=f"async-{cfg.seed}",
                level=cfg.codec_level, codec=chan_codec,
                bucket_bytes=wire_bucket_bytes, workers=cfg.wire_workers)

        # Per-slice data: this process is shard pid-of-n over the shared-seed
        # shuffle; each slice draws cfg.batch_size per step like a reference
        # worker.
        dev_norm = self._input_norm is not None
        xtr, ytr = load_arrays(cfg.dataset, cfg.data_dir, train=True,
                               seed=cfg.seed)
        self.train_loader = DataLoader(
            xtr, ytr, cfg.batch_size * self.n, cfg.dataset, train=True,
            seed=cfg.seed, host_id=self.pid, num_hosts=self.n,
            device_normalize=dev_norm)
        xte, yte = load_arrays(cfg.dataset, cfg.data_dir, train=False,
                               seed=cfg.seed)
        self.test_loader = DataLoader(xte, yte, cfg.test_batch_size,
                                      cfg.dataset, train=False, shuffle=False,
                                      seed=cfg.seed, drop_last=False,
                                      device_normalize=dev_norm)

        self.metrics = MetricsLogger(cfg.metrics_file, cfg.log_every,
                                     process_index=self.pid,
                                     num_processes=self.n)
        # Ambient tracer: the wire_publish/wire_read spans inside
        # transport.py land here, so the Chrome trace shows what each
        # process's DCN legs cost relative to its compute.
        self.tracer = Tracer(pid=self.pid)
        self._prev_tracer = set_default_tracer(self.tracer)
        # Live ops plane (lighter than the sync trainers: gauges + step
        # counter, no watchdogs — the async loop has no global loss on
        # followers to guard). Port is offset by process index so every
        # worker of a local multi-process run gets its own endpoint.
        self.registry = declare_training_metrics(Registry())
        if cfg.elastic:
            declare_elastic_metrics(self.registry)
        if self._hier:
            declare_hierarchy_metrics(self.registry)
        # Resilience counters reach the SCRAPE endpoint, not just the
        # JSONL: whenever a fault/retry plane is armed, declare the
        # contract and refresh it from the live snapshots on every render.
        collect = []
        if self.injector is not None or self._retrier is not None:
            declare_resilience_metrics(self.registry)
            collect.append(self._pump_resilience_metrics)
        if self._kvrep is not None:
            declare_kvrep_metrics(self.registry)
            collect.append(self._pump_kvrep_metrics)
        if cfg.grad_integrity:
            declare_integrity_metrics(self.registry)
            collect.append(self._pump_integrity_metrics)
        self.exporter = None
        if cfg.metrics_port > 0:
            self.exporter = MetricsExporter(
                self.registry, port=cfg.metrics_port + self.pid,
                health_fn=self._health_status, collect=collect).start()
        self.last_publish_s = 0.0
        self.version = 0        # canonical PS step (leader-owned)
        self.applied = 0
        self.dropped_stale = 0
        self._seq = 0
        if self.leader:
            self.opt_state = self.tx.init(variables["params"])
            self.aggregator = self._make_leader_aggregator()
            # out_shardings pins the updated params/opt state REPLICATED
            # over the local mesh: a bare jit would commit them to one
            # device, and the next multi-device shard_map grad_fn call
            # would fail with incompatible devices (single-device CI can't
            # see this; multislice.py handles the same hazard).
            rep = self._rep
            self._update = jax.jit(
                lambda p, o, g: apply_optimizer(self.tx, p, o, g),
                out_shardings=(rep, rep))

    def _make_integrity(self):
        """One screening ledger (--grad-integrity): compressed-domain
        validation + MAD outlier gate + strike/quarantine bookkeeping.
        Built per contributor-id space — leader pool and hier group hop
        get SEPARATE instances (slice ids vs group ids)."""
        cfg = self.cfg
        if not cfg.grad_integrity:
            return None
        from ps_pytorch_tpu.resilience.integrity import GradIntegrity
        return GradIntegrity(
            mad_threshold=cfg.integrity_mad_threshold,
            strike_limit=cfg.integrity_strike_limit,
            readmit_clean=cfg.integrity_readmit_clean,
            on_event=self._integrity_event)

    def _make_leader_aggregator(self):
        cfg = self.cfg
        self._integrity = self._make_integrity()
        if self._hier:
            # Root tier pools GROUP aggregates; K-of-N applies per tier,
            # so the member-count knob is clamped to the group count.
            from ps_pytorch_tpu.parallel.hierarchy import RootAggregator
            plan = self.transport.plan
            return RootAggregator(
                plan.n_groups, cfg.grad_codec,
                staleness_limit=cfg.staleness_limit,
                staleness_decay=cfg.staleness_decay,
                num_aggregate=min(cfg.num_aggregate, plan.n_groups),
                on_event=self._hier_event, integrity=self._integrity)
        if self._wire_homo:
            # Homomorphic wire: the pool holds PAYLOADS (submit_encoded)
            # and collect() sums them in the compressed domain. EF stays
            # sender-side — each process compensates its own encodes.
            return self._wrap_shard_wire(StaleGradientAggregator(
                self.n, staleness_limit=cfg.staleness_limit,
                staleness_decay=cfg.staleness_decay,
                num_aggregate=cfg.num_aggregate, compress=True,
                codec=cfg.grad_codec, topk_frac=cfg.grad_topk_frac,
                integrity=self._integrity))
        agg = StaleGradientAggregator(
            self.n, staleness_limit=cfg.staleness_limit,
            staleness_decay=cfg.staleness_decay,
            num_aggregate=cfg.num_aggregate,
            compress=False,  # the WIRE is compressed; the pool is local
            integrity=self._integrity)
        return self._wrap_shard_wire(agg)

    def _wrap_shard_wire(self, agg):
        """--shard-wire: wrap the leader pool in the sharded-update
        aggregator (parallel/zero_wire.py). Pooling/staleness/K-of-N/
        integrity delegate to ``agg`` untouched; the update itself runs
        host-side per bucket-edge-snapped shard and publishes per-shard
        params over the KV. Single-owner here (the leader owns every
        shard); tests/test_zero_wire.py drives the symmetric multi-owner
        topology."""
        cfg = self.cfg
        if not cfg.shard_wire:
            return agg
        from ps_pytorch_tpu.parallel.zero_wire import updater_from_config
        return updater_from_config(
            cfg, inner=agg, kv=self._kv, run_id=f"zw-{cfg.seed}",
            params=self.params, members=[0], me=0,
            n_shards=max(self.n, 2))

    def _pump_resilience_metrics(self) -> None:
        """Refresh resilience counters from the live fault/retry snapshots
        (delta-inc: Registry counters are monotonic, snapshots are the
        source of truth). Runs as a MetricsExporter collect hook, so every
        scrape sees current values without the train loop's involvement."""
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
        """Refresh kvrep_* registry metrics from the live ReplicatedKV
        snapshot (delta-inc for counters, set for the health gauges) —
        same collect-hook discipline as the resilience pump."""
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

    def _integrity_event(self, kind: str, cid: int, step: int,
                         detail: str) -> None:
        """Quarantine lifecycle callback: one parseable line per
        transition (tools/poison_drill.py greps these). Per-payload
        strikes stay silent — the counters carry them."""
        if kind == "quarantine":
            print(f"INTEGRITY quarantine contributor {cid} at version "
                  f"{step} ({detail})", flush=True)
        elif kind == "readmit":
            print(f"INTEGRITY readmit contributor {cid} at version {step}",
                  flush=True)

    def _integrity_snapshot(self) -> dict:
        """Merged counters over every ledger this process runs (leader
        pool + hier group hop) plus the transport's wire-digest
        failures."""
        snap: dict = {}
        for ledger in (self._integrity, self._group_integrity):
            if ledger is None:
                continue
            for k, v in ledger.snapshot().items():
                snap[k] = snap.get(k, 0) + v
        snap["wire_integrity_failures"] = self.transport.wire_stats()[
            "wire_integrity_failures"]
        return snap

    def _pump_integrity_metrics(self) -> None:
        """Refresh integrity_* registry metrics from the live ledger
        snapshots (same delta-inc discipline as the resilience pump)."""
        snap = self._integrity_snapshot()
        self.registry.set("integrity_quarantined",
                          float(snap.pop("integrity_quarantined", 0)))
        for name, value in snap.items():
            try:
                delta = value - self.registry.get(name)
            except KeyError:
                continue
            if delta > 0:
                self.registry.inc(name, delta)

    def _hier_telemetry(self) -> dict:
        """Delta-inc the hierarchy_* registry counters from the live
        transport/root snapshots; returns the JSONL columns."""
        st = self.transport.stats
        pairs = [("hierarchy_group_publishes", st["group_publishes"]),
                 ("hierarchy_failovers", st["failovers"])]
        hops = st["hops"]
        extra = {"hier_group_publishes": st["group_publishes"],
                 "hier_failovers": st["failovers"],
                 "hier_hop_giveups": st["hop_giveups"]}
        self.registry.set("hierarchy_groups",
                          float(self.transport.plan.n_groups))
        if self.leader:
            snap = self.aggregator.snapshot()
            hops += snap["hops"]
            self.registry.set("hierarchy_groups_healthy",
                              float(snap["groups_healthy"]))
            pairs.append(("hierarchy_degraded_steps",
                          snap["degraded_steps"]))
            extra["hier_groups_healthy"] = snap["groups_healthy"]
            extra["hier_degraded_steps"] = snap["degraded_steps"]
        pairs.append(("hierarchy_hops", hops))
        for name, value in pairs:
            delta = value - self.registry.get(name)
            if delta > 0:
                self.registry.inc(name, delta)
        return extra

    def _hier_event(self, kind: str, gid: int, step: int,
                    staleness: int) -> None:
        """Root-tier lifecycle callback: one parseable line per subtree
        transition (tools/hierarchy_drill.py greps these) + counters."""
        if kind == "partition":
            self.registry.inc("hierarchy_partitions")
            print(f"HIER partition group {gid} at version {step} "
                  f"(silent {staleness})", flush=True)
        elif kind == "regraft":
            self.registry.inc("hierarchy_regrafts")
            print(f"HIER regraft group {gid} at version {step} "
                  f"staleness {staleness}", flush=True)

    def _health_status(self) -> dict:
        body = {"ok": True, "process_index": self.pid,
                "version": self.version, "leader": bool(self.leader),
                "role": "leader" if self.leader else "follower"}
        if self.election is not None:
            body["leader_epoch"] = self.election.epoch
            body["leader_owner"] = self.election.owner
        return body

    # ---- checkpoint/resume (leader authority, sync-Trainer contract) ----
    def _as_train_state(self):
        from ps_pytorch_tpu.parallel.dp import TrainState
        return TrainState(step=jnp.asarray(self.version, jnp.int32),
                          params=self.params, opt_state=self.opt_state,
                          batch_stats=self._bs)

    def _checkpoint(self) -> None:
        extra = None
        if self.election is not None:
            # Stamp which leadership epoch committed these weights —
            # serving /healthz surfaces it for the checkpoints it reloads.
            extra = {"leader_epoch": self.election.epoch,
                     "leader_pid": self.pid}
        # The leader's own EF residual rides the checkpoint as extra state
        # (followers hold their own; a restarted follower restarts with a
        # zero residual, like a freshly relaunched reference worker).
        extra_state = {"ef": self._ef.state_dict()} \
            if (self.cfg.ef and self._ef is not None) else None
        if self.cfg.shard_wire and self.leader:
            # Sharded optimizer moments + step: without them a resumed /
            # promoted leader restarts momentum from zero and diverges
            # from the uninterrupted run.
            extra_state = dict(extra_state or {})
            extra_state["zero"] = self.aggregator.state_dict()
        ckpt.save_checkpoint(self.cfg.train_dir, self.version,
                             jax.device_get(self._as_train_state()),
                             config_json=self.cfg.to_json(),
                             compress=self.cfg.compress_grad,
                             codec_level=self.cfg.codec_level,
                             extra_meta=extra, extra_state=extra_state)
        if self.injector is not None:
            self.injector.after_checkpoint(self.cfg.train_dir, self.version)
        if self.cfg.ckpt_keep > 0:
            ckpt.prune_checkpoints(self.cfg.train_dir, self.cfg.ckpt_keep)

    def _maybe_resume(self) -> bool:
        if ckpt.latest_step(self.cfg.train_dir) is None:
            return False
        got = ckpt.load_latest_valid(
            self.cfg.train_dir, jax.device_get(self._as_train_state()))
        if got is None:
            return False
        state, meta, _, step = got
        # Checkpoints come back as host numpy; restore device residency once.
        self.params = jax.device_put(state.params, self._rep)
        self.opt_state = jax.device_put(state.opt_state, self._rep)
        self._bs = jax.device_put(state.batch_stats)
        self.version = int(meta["step"])
        extra = ckpt.load_extra_state(self.cfg.train_dir, step)
        if extra and "ef" in extra:
            from ps_pytorch_tpu.compression.codecs import ErrorFeedback
            self._ef = ErrorFeedback(clip=self.cfg.ef_clip)
            self._ef.load_state_dict(extra["ef"])
        if self.cfg.shard_wire and self.leader:
            # Bit-for-bit resume: re-anchor owned shards on the restored
            # params, then restore the sharded moments + step.
            self.aggregator.reset_params(self.params)
            if extra and "zero" in extra:
                self.aggregator.load_state_dict(extra["zero"])
            self._zw_ptr_version = -1  # republish shards at this version
        print(f"RESUME from {ckpt.checkpoint_path(self.cfg.train_dir, step)} "
              f"at step {self.version}")
        return True

    # ---- wire codecs ----
    def _encode_grads(self, grads):
        if self._wire_homo:
            from ps_pytorch_tpu.compression.codecs import (
                ErrorFeedback, encode_leaves,
            )
            if self.cfg.ef and self._ef is None:
                self._ef = ErrorFeedback(clip=self.cfg.ef_clip)
            leaves, treedef = jax.tree.flatten(grads)
            # Per-bucket streaming: encode + EF-update of bucket k runs on
            # the pool while bucket k+1 is still syncing off-device — the
            # homomorphic wire's analogue of the overlapped blosc/int8
            # schedule. Payloads are bitwise-invariant to the bucketing
            # (global flat leaf index), so overlap never changes the wire.
            payloads = encode_leaves(
                self.cfg.grad_codec, leaves, slice_id=self.pid,
                step=self._seq, frac=self.cfg.grad_topk_frac, ef=self._ef,
                bucket_bytes=(int(self.cfg.wire_bucket_mb * (1 << 20))
                              if self._wire_overlap else 0),
                pool=self._encode_pool())
            return jax.tree.unflatten(treedef, payloads)
        if not self._wire_int8:
            # Overlapped wire: hand the DEVICE arrays to the channel — it
            # blocks per BUCKET (flat-leaf order) and encodes bucket k while
            # bucket k+1 is still computing. The blocking wire keeps the one
            # batched device_get (whole tree on host before any encode).
            return grads if self._wire_overlap else jax.device_get(grads)
        from ps_pytorch_tpu.ops.quantize import quantize_int8
        key = jax.random.key(self.cfg.seed * 31 + self._seq * self.n + self.pid)
        leaves, treedef = jax.tree.flatten(grads)
        enc = []
        for i, leaf in enumerate(leaves):
            qt = quantize_int8(leaf, jax.random.fold_in(key, i))
            if self._wire_overlap:
                # Hand the quantized components to the channel as DEVICE
                # arrays: its per-bucket sync then overlaps the quantize of
                # bucket k+1 with the encode/put of bucket k, instead of
                # stalling here on the whole tree.
                enc.append({"v": qt.values, "s": qt.scales})
            else:
                enc.append({"v": np.asarray(qt.values),
                            "s": np.asarray(qt.scales)})
        return jax.tree.unflatten(treedef, enc)

    def _encode_pool(self):
        if self._enc_pool is None and self._wire_overlap \
                and self.cfg.wire_workers > 1:
            from concurrent.futures import ThreadPoolExecutor
            self._enc_pool = ThreadPoolExecutor(
                max_workers=self.cfg.wire_workers,
                thread_name_prefix="grad-enc")
        return self._enc_pool

    def _decode_grads(self, wire):
        if not self._wire_int8:
            return wire
        from ps_pytorch_tpu.ops.quantize import (
            QuantizedTensor, dequantize_int8,
        )

        def leaf(enc, tpl):
            qt = QuantizedTensor(values=jnp.asarray(enc["v"]),
                                 scales=jnp.asarray(enc["s"]),
                                 shape=tuple(tpl.shape), size=int(tpl.size))
            return np.asarray(dequantize_int8(qt))
        # Wire leaves are {"v","s"} dicts; pair them with the params
        # template for shape/size by walking the flattened orders.
        wire_leaves = jax.tree.flatten(
            wire, is_leaf=lambda x: isinstance(x, dict) and "v" in x)[0]
        tpl_leaves, treedef = jax.tree.flatten(self._param_tpl)
        return jax.tree.unflatten(
            treedef, [leaf(e, t) for e, t in zip(wire_leaves, tpl_leaves)])

    # ---- elastic role transitions ----
    def _promote(self, my_version: int) -> int:
        """Assume PS duty mid-run after winning an election: build the
        leader-only machinery this process skipped at startup, recover
        optimizer state from the latest valid checkpoint (the dead
        leader's momentum survives through its last save), fast-forward
        params to the freshest canonical publish on the KV, and announce
        the takeover with a fresh publish so followers re-anchor."""
        cfg = self.cfg
        rep = self._rep
        self.aggregator = self._make_leader_aggregator()
        self._update = jax.jit(
            lambda p, o, g: apply_optimizer(self.tx, p, o, g),
            out_shardings=(rep, rep))
        self.opt_state = self.tx.init(self.params)
        self.version = my_version
        if ckpt.latest_step(cfg.train_dir) is not None:
            got = ckpt.load_latest_valid(
                cfg.train_dir, jax.device_get(self._as_train_state()))
            if got is not None:
                state, meta, _, _ = got
                self.opt_state = jax.device_put(state.opt_state, rep)
                self._bs = jax.device_put(state.batch_stats)
                if int(meta["step"]) > self.version:
                    self.params = jax.device_put(state.params, rep)
                    self.version = int(meta["step"])
        # The KV canonical publish is usually AHEAD of any checkpoint
        # (publish_every vs eval_freq); prefer the freshest params even
        # though the momentum then lags a few steps — async staleness
        # semantics already tolerate exactly that skew.
        got = self._fetch_canonical(self.version)
        if got is not None and got[0] > self.version:
            self.version = got[0]
            self.params = jax.device_put(got[1]["params"], self._rep)
        if cfg.shard_wire:
            # The freshly built sharded updater re-anchors on the adopted
            # params; the dead leader's sharded optimizer moments survive
            # through its last checkpoint (same lag tolerance as above).
            self.aggregator.reset_params(self.params)
            step = ckpt.latest_step(cfg.train_dir)
            extra = ckpt.load_extra_state(cfg.train_dir, step) \
                if step is not None else None
            if extra and "zero" in extra:
                self.aggregator.load_state_dict(extra["zero"])
            self._zw_ptr_version = -1  # force a full shard publish below
        self.leader = True
        print(f"ELECTED async leader process {self.pid} epoch "
              f"{self.election.epoch} at version {self.version} "
              f"(election {self.elect_latency_s:.3f}s)", flush=True)
        self._publish_canonical()
        return self.version

    def _demote(self) -> None:
        self.leader = False
        print(f"DEPOSED async leader process {self.pid}: following epoch "
              f"{self.election.epoch} owner {self.election.owner}",
              flush=True)

    def _elastic_control(self, own_steps: int, my_version: int) -> int:
        """One control-plane beat per loop iteration: heartbeat, lease
        refresh (leader) or staleness check (follower), and the
        campaign/promote path when the lease goes stale. Returns the
        version this process should stamp on its next contribution."""
        from ps_pytorch_tpu.elastic.election import Deposed
        self.announcer.beat(own_steps)
        if self.leader:
            try:
                self.election.refresh(own_steps)
                self.membership.update(own_steps)
            except Deposed:
                self._demote()
            return self.version if self.leader else my_version
        if self.election.check() == "stale":
            t0 = time.monotonic()
            won = self.election.campaign()
            self.elect_latency_s = time.monotonic() - t0
            self.registry.inc("elections")
            if won:
                return self._promote(my_version)
        return my_version

    # ---- the two roles ----
    def _publish_canonical(self) -> None:
        t0 = time.monotonic()
        if self.cfg.shard_wire:
            # Params go out as per-shard zw keys; steady-state updates
            # already published them inside update_from, so only publish
            # here when the KV pointer lags (startup / resume / promote /
            # final). The transport channel keeps just the BN stats.
            if self._zw_ptr_version != self.version:
                self.aggregator.publish_full(self.version)
                self._zw_ptr_version = self.version
            payload = {"bs0": self._bs0()}
        else:
            payload = {"params": self.params, "bs0": self._bs0()}
        if not self._wire_overlap:
            payload = jax.device_get(payload)
        self.transport.publish_params(self.version, payload)
        self.last_publish_s = time.monotonic() - t0

    def _zw_reader(self):
        """Reader-mode sharded-params assembler for non-leader processes
        (owns nothing; fetch() gathers the newest consistent round)."""
        if self._zw_rd is None:
            from ps_pytorch_tpu.parallel.zero_wire import updater_from_config
            self._zw_rd = updater_from_config(
                self.cfg, inner=None, kv=self._kv,
                run_id=f"zw-{self.cfg.seed}", params=self.params,
                members=[0], me=None, n_shards=max(self.n, 2))
        return self._zw_rd

    def _fetch_canonical(self, min_version: int = -1):
        """(version, {"params", "bs0"}) from the canonical plane. Normal
        runs read the transport publish; under --shard-wire params
        assemble from the per-shard keys (pipelined) and only the BN
        stats ride the transport (their version may lag a publish_every
        window behind the params — eval-only state, same skew the
        replicated path has between publishes)."""
        if not self.cfg.shard_wire:
            got = self.transport.fetch_params()
            return None if got is None or got[0] <= min_version else got
        got = self._zw_reader().fetch(min_version)
        if got is None:
            return None
        version, params = got
        bs = self.transport.fetch_params()
        bs0 = bs[1]["bs0"] if bs is not None else self._bs0()
        return version, {"params": params, "bs0": bs0}

    def _compute_and_submit(self, version_used: int) -> dict:
        with self.tracer.span("data_wait", step=self._seq + 1):
            x, y = self.train_loader.next_batch()
        with self.tracer.span("host_dispatch", step=self._seq + 1):
            grads, m, new_bs = self.grad_fn(
                self.params, self._bs, jnp.asarray(x), jnp.asarray(y),
                jax.random.PRNGKey(self.cfg.seed * 7919
                                   + self._seq * 13 + self.pid))
        self._bs = new_bs
        self._seq += 1
        if self.injector is not None:
            # Poisoned-contributor drill (--fault-spec grad_poison): the
            # fault scales this process's OWN gradients before encode, so
            # the corruption rides the real wire and the leader's screen
            # must catch it downstream.
            scale = self.injector.poison_scale(self._seq)
            if scale is not None:
                grads = jax.tree.map(lambda g: g * scale, grads)
        self.transport.submit_grads(self.pid, self._seq, version_used,
                                    self._encode_grads(grads))
        with self.tracer.span("device_sync", step=self._seq):
            return {"loss": float(m["loss"]), "acc": float(m["accuracy"])}

    def _leader_apply(self) -> int:
        """Pool new wire contributions and apply at most one update.
        Returns number of contributions used."""
        if self._hier:
            # Root tier: the wire carries GROUP aggregates, one payload
            # tree per group with (step, wsum) meta — pool them as groups.
            for gid, step, wsum, tree in self.transport.poll_new_aggs():
                self.aggregator.submit_group(gid, step, wsum, tree)
        else:
            for s, step, wire in self.transport.poll_new_grads():
                if self._wire_homo:
                    # Payloads enter the pool AS PAYLOADS: no
                    # per-contributor float32 is ever materialized
                    # leader-side; decode happens once, after the K-of-N
                    # cutoff inside collect().
                    self.aggregator.submit_encoded(s, step, wire)
                else:
                    self.aggregator.submit(s, step, self._decode_grads(wire))
        avg, pool = self.aggregator.collect(self.version)
        used = 0
        if avg is not None and pool["used"]:
            if self.cfg.shard_wire:
                # Sharded host-side update: per-shard optimizer + pipelined
                # per-shard publish + assemble (parallel/zero_wire.py). The
                # per-shard keys ARE the canonical publish for params, so
                # _publish_canonical ships only BN stats below.
                self.params = jax.device_put(
                    self.aggregator.update_from(avg,
                                                version=self.version + 1),
                    self._rep)
                self._zw_ptr_version = self.version + 1
            else:
                # Update runs jitted with everything already
                # device-resident; only the pooled average crosses
                # host->device here.
                self.params, self.opt_state = self._update(
                    self.params, self.opt_state, avg)
            self.version += 1
            self.applied += 1
            used = len(pool["used"])
            self.aggregator.consume(pool["used"])
            # publish_every > 1 trades follower freshness for DCN publish
            # traffic (the full param tree crosses the wire per publish —
            # wire_stats records what that costs). The final state is
            # always published in train() before set_done.
            if self.applied % max(self.cfg.publish_every, 1) == 0:
                self._publish_canonical()
            if self.cfg.eval_freq > 0 and self.version % self.cfg.eval_freq == 0:
                self._checkpoint()
        self.dropped_stale += self.aggregator.drop_older_than(self.version)
        return used

    def train(self):
        cfg = self.cfg
        my_version = 0
        if self.leader:
            if cfg.resume:
                self._maybe_resume()
            # Canonical start weights (fresh or resumed) become visible to
            # followers before anyone trains.
            self._publish_canonical()
        else:
            # Block on the leader's initial publish (the reference worker's
            # first blocking step-fetch, distributed_worker.py:193-199).
            deadline = time.monotonic() + 120.0
            while True:
                got = self._fetch_canonical()
                if got is not None:
                    my_version, tree = got
                    self.params = jax.device_put(tree["params"], self._rep)
                    break
                if time.monotonic() > deadline:
                    raise TimeoutError("no initial params from leader")
                time.sleep(0.05)

        own_steps = 0
        # Safety valve for followers if the leader dies before set_done:
        # bounded loop, generous multiple of the canonical target.
        max_own = cfg.max_steps * 50 + 100
        try:
            self._train_loop(cfg, my_version, own_steps, max_own)
            if self.election is not None:
                # One parseable control-plane summary per process: the
                # chaos drill (tools/elastic_drill.py) reads epoch /
                # world-size / membership-change evidence from here.
                msnap = self.membership.snapshot()
                print(f"ELASTIC pid {self.pid} epoch {self.election.epoch} "
                      f"world {msnap['world_size']} membership_changes "
                      f"{msnap['membership_changes']} wins "
                      f"{self.election.stats['wins']}", flush=True)
            if self._hier:
                # One parseable hierarchy summary per process — the chaos
                # drill (tools/hierarchy_drill.py) reads its partition/
                # regraft/degraded evidence from here.
                st = self.transport.stats
                line = (f"HIERARCHY pid {self.pid} gid {self.transport.gid} "
                        f"aggregator {int(self.transport.is_aggregator)} "
                        f"hops {st['hops']} publishes "
                        f"{st['group_publishes']} failovers "
                        f"{st['failovers']} giveups {st['hop_giveups']}")
                if self.leader:
                    root = self.aggregator.snapshot()
                    line += (f" partitions {root['partitions']} regrafts "
                             f"{root['regrafts']} degraded_steps "
                             f"{root['degraded_steps']} groups_healthy "
                             f"{root['groups_healthy']}")
                print(line, flush=True)
            if self._integrity is not None or \
                    self._group_integrity is not None:
                # One parseable integrity summary per screening process —
                # tools/poison_drill.py reads its quarantine/readmission/
                # wire-failure evidence from here.
                s = self._integrity_snapshot()
                print(f"INTEGRITY pid {self.pid} screen_rejects "
                      f"{s.get('integrity_screen_rejects', 0)} "
                      f"outlier_rejects "
                      f"{s.get('integrity_outlier_rejects', 0)} strikes "
                      f"{s.get('integrity_strikes', 0)} quarantines "
                      f"{s.get('integrity_quarantines', 0)} readmissions "
                      f"{s.get('integrity_readmissions', 0)} wire_failures "
                      f"{s.get('wire_integrity_failures', 0)}", flush=True)
        finally:
            if self.announcer is not None:
                try:
                    # Graceful leave: the leader evicts on the announcement
                    # instead of waiting out the heartbeat timeout.
                    self.announcer.leave()
                except Exception:
                    pass  # KV may already be torn down at exit
            # Sinks close on any exit (a follower TimeoutError must not
            # leak the JSONL handle or drop the trace).
            if self.exporter is not None:
                self.exporter.stop()
            self.metrics.close()
            if cfg.trace_file:
                path = cfg.trace_file
                if self.pid > 0:
                    path = f"{path}.p{self.pid}"
                self.tracer.write_chrome_trace(path)
            set_default_tracer(self._prev_tracer)
        return self.params

    def _train_loop(self, cfg, my_version: int, own_steps: int,
                    max_own: int) -> None:
        while own_steps < max_own:
            t0 = time.monotonic()
            if self.injector is not None:
                # Keyed on this process's own step counter (the async loop
                # has no global step on followers).
                self.injector.maybe_crash(own_steps + 1)
                self.injector.maybe_kill_leader(own_steps + 1,
                                                is_leader=self.leader)
            if self.election is not None:
                my_version = self._elastic_control(own_steps, my_version)
            done = self.transport.done()
            if done is not None and (not self.leader):
                break
            if self.leader and self.version >= cfg.max_steps:
                break
            if self.leader:
                # The leader's params ARE canonical — no KV readback, and
                # its contributions carry the true current version.
                my_version = self.version
            elif own_steps % self.fetch_every == 0:
                got = self._fetch_canonical(my_version)
                if got is not None and got[0] > my_version:
                    my_version, tree = got
                    # ONE host->device transfer per fetch; the jitted grad fn
                    # then reuses the device copy every local step (feeding
                    # numpy would re-transfer the full model each call).
                    self.params = jax.device_put(tree["params"], self._rep)
            m = self._compute_and_submit(my_version)
            own_steps += 1
            if self._hier:
                # Every process pumps: the group lease stays fresh, and
                # whoever holds it drains member channels and publishes
                # the re-encoded aggregate upward (after the submit above,
                # so an aggregator pools its OWN contribution same-round).
                before = self.transport.stats["failovers"]
                self.transport.pump(my_version)
                if self.transport.stats["failovers"] > before:
                    print(f"HIER failover: process {self.pid} adopted "
                          f"aggregator role for group {self.transport.gid} "
                          f"at own step {own_steps}", flush=True)
            used = self._leader_apply() if self.leader else 0
            step_for_log = self.version if self.leader else own_steps
            self.registry.inc("train_steps")
            self.registry.observe("train_step_latency_s",
                                  time.monotonic() - t0)
            if step_for_log and step_for_log % cfg.log_every == 0:
                self.registry.set("train_step", float(step_for_log))
                self.registry.set("train_loss", float(m["loss"]))
                self.registry.set("train_step_time_s",
                                  time.monotonic() - t0)
                self.registry.set("host_rss_bytes", float(host_rss_bytes()))
                set_device_memory_gauges(self.registry,
                                         device_memory_record())
                wire = self.transport.wire_stats()
                extra = {}
                if self.election is not None:
                    self.registry.set("leader_epoch",
                                      float(self.election.epoch))
                    snap = self.membership.snapshot()
                    self.registry.set(
                        "world_size", float(snap["world_size"] or self.n))
                    delta = snap["membership_changes"] - \
                        self.registry.get("membership_changes")
                    if delta > 0:
                        self.registry.inc("membership_changes", delta)
                    extra["leader_epoch"] = self.election.epoch
                if self._hier:
                    extra.update(self._hier_telemetry())
                if self._integrity is not None or \
                        self._group_integrity is not None:
                    isnap = self._integrity_snapshot()
                    # Schema gate: vanilla runs only grow integrity
                    # columns once a screen/digest actually fired.
                    if self.injector is not None or any(isnap.values()):
                        extra.update(isnap)
                if self.injector is not None:
                    extra.update(self.injector.snapshot())
                if self._retrier is not None:
                    s = self._retrier.snapshot()
                    # Schema gate: vanilla runs only grow resilience columns
                    # once the retry plane actually absorbed an error.
                    if self.injector is not None or s["kv_retries"] or \
                            s["kv_giveups"]:
                        extra.update(s)
                self.metrics.log_step(
                    step_for_log, 0, loss=m["loss"], acc=m["acc"],
                    participating=float(used),
                    step_time=time.monotonic() - t0, data_time=0.0,
                    applied=self.applied, dropped_stale=self.dropped_stale,
                    wire_bytes_out=wire["wire_bytes_out"],
                    wire_bytes_in=wire["wire_bytes_in"],
                    publish_s=round(self.last_publish_s, 4), **extra)
        if self.leader:
            if cfg.eval_freq > 0 and self.version % cfg.eval_freq != 0:
                self._checkpoint()
            # Canonical final state visible to every process regardless of
            # publish_every (evaluate() and late followers read it).
            self._publish_canonical()
            self.transport.set_done(self.version)

    @property
    def fetch_every(self) -> int:
        return max(self.cfg.fetch_every, 1)

    def evaluate(self, max_batches: Optional[int] = None) -> dict:
        """Every process evaluates the CANONICAL state — params AND the
        leader's replica-0 BN stats from the final publish — so all FINAL
        lines agree even for BN networks. The reference evaluator likewise
        scores the master's checkpoint."""
        got = self._fetch_canonical()
        if got is not None:
            params, bs0 = got[1]["params"], got[1]["bs0"]
        else:
            params, bs0 = self.params, self._bs0()
        from ps_pytorch_tpu.runtime.evaluator import accumulate_eval
        return accumulate_eval(make_eval_step(self.model, self._input_norm),
                               params, bs0,
                               self.test_loader.epoch(0), max_batches)
