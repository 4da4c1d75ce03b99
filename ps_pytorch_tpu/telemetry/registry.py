"""Typed counters/gauges registry + the derived per-step record.

The weight-update-sharding paper's point (PAPERS.md) is that raw step time
is not the metric — utilization is. This module owns the arithmetic the
MetricsLogger v2 record carries beyond the reference's loss/time pair:

- MFU: analytic step FLOPs (utils/flops.py jaxpr traversal) divided by
  wall time and by the chips' aggregate peak (``peak_flops_bf16``). On a
  backend without a published peak (CPU) MFU is None, never a fiction.
- goodput: examples/sec (or tokens/sec for the LM surface) actually
  trained, i.e. global batch over the TRUE per-step wall time.
- data_stall_frac: the fraction of the step the host spent waiting on the
  input pipeline — the one number that says whether the loader or the chip
  is the bottleneck (PERF.md §5's ratio, now per step, per run).
- device memory: ``memory_stats()`` peak/current bytes when the backend
  reports them (memory_probe-style, inline instead of a separate drill).

The Registry itself is deliberately small: metrics must be DECLARED (name,
kind, unit, help) before use, so the set of emitted fields is a reviewable
contract rather than whatever strings the call sites happened to pass —
the same schema-discipline argument as runtime/metrics.py, applied to
counters.
"""

import threading
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

# Request-latency style default: sub-ms to minutes, roughly x2 per bucket.
DEFAULT_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                   0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0)


@dataclass(frozen=True)
class MetricSpec:
    name: str
    kind: str          # "counter" (monotonic) | "gauge" (set) | "histogram" (observe)
    unit: str = ""
    help: str = ""
    buckets: Tuple[float, ...] = ()     # histogram upper bounds, ascending

    def __post_init__(self):
        if self.kind not in ("counter", "gauge", "histogram"):
            raise ValueError(f"metric kind {self.kind!r} "
                             "(counter | gauge | histogram)")
        if self.kind == "histogram":
            bs = tuple(float(b) for b in (self.buckets or DEFAULT_BUCKETS))
            if list(bs) != sorted(bs) or len(set(bs)) != len(bs):
                raise ValueError(f"histogram {self.name!r} buckets must be "
                                 "strictly ascending")
            object.__setattr__(self, "buckets", bs)


class Registry:
    """Declared-metrics store. ``inc`` only on counters, ``set`` only on
    gauges; touching an undeclared name raises — typos surface at the call
    site, not as silently-new JSONL keys."""

    def __init__(self):
        self._specs: Dict[str, MetricSpec] = {}
        self._values: Dict[str, float] = {}
        self._hists: Dict[str, dict] = {}
        self._lock = threading.Lock()

    def counter(self, name: str, unit: str = "", help: str = "") -> str:
        return self._declare(MetricSpec(name, "counter", unit, help))

    def gauge(self, name: str, unit: str = "", help: str = "") -> str:
        return self._declare(MetricSpec(name, "gauge", unit, help))

    def histogram(self, name: str, unit: str = "", help: str = "",
                  buckets: Optional[Tuple[float, ...]] = None) -> str:
        return self._declare(
            MetricSpec(name, "histogram", unit, help, tuple(buckets or ())))

    def _declare(self, spec: MetricSpec) -> str:
        with self._lock:
            old = self._specs.get(spec.name)
            if old is not None and old != spec:
                raise ValueError(f"metric {spec.name!r} re-declared as "
                                 f"{spec.kind}, was {old.kind}")
            self._specs[spec.name] = spec
            if spec.kind == "histogram":
                self._hists.setdefault(spec.name, {
                    # counts[i] = observations <= buckets[i]; last = +Inf
                    "counts": [0] * (len(spec.buckets) + 1),
                    "sum": 0.0, "count": 0,
                    "min": None, "max": None,
                })
            else:
                self._values.setdefault(spec.name, 0.0)
        return spec.name

    def _spec(self, name: str, kind: str) -> MetricSpec:
        spec = self._specs.get(name)
        if spec is None:
            raise KeyError(f"metric {name!r} not declared")
        if spec.kind != kind:
            raise TypeError(f"metric {name!r} is a {spec.kind}, not a {kind}")
        return spec

    def inc(self, name: str, value: float = 1.0) -> float:
        self._spec(name, "counter")
        if value < 0:
            raise ValueError(f"counter {name!r} cannot decrease")
        with self._lock:
            self._values[name] += value
            return self._values[name]

    def set(self, name: str, value: float) -> float:
        self._spec(name, "gauge")
        with self._lock:
            self._values[name] = float(value)
            return self._values[name]

    def observe(self, name: str, value: float) -> None:
        """Record one histogram observation (e.g. a request latency)."""
        spec = self._spec(name, "histogram")
        v = float(value)
        with self._lock:
            h = self._hists[name]
            i = 0
            while i < len(spec.buckets) and v > spec.buckets[i]:
                i += 1
            h["counts"][i] += 1
            h["sum"] += v
            h["count"] += 1
            h["min"] = v if h["min"] is None else min(h["min"], v)
            h["max"] = v if h["max"] is None else max(h["max"], v)

    def _quantile_locked(self, spec: MetricSpec, h: dict, q: float) -> float:
        """Prometheus-style bucket interpolation, clamped to the observed
        [min, max] so quantiles never exceed what was actually seen."""
        rank = q * h["count"]
        seen = 0
        for i, c in enumerate(h["counts"]):
            if c == 0:
                continue
            if seen + c >= rank:
                lo = spec.buckets[i - 1] if i > 0 else 0.0
                hi = (spec.buckets[i] if i < len(spec.buckets)
                      else h["max"])
                frac = (rank - seen) / c
                est = lo + (hi - lo) * frac
                return min(max(est, h["min"]), h["max"])
            seen += c
        return h["max"]

    def hist_summary(self, name: str) -> dict:
        """{"count", "sum", "min", "max", "p50", "p99"} (empty histogram →
        count 0 and None everywhere else)."""
        spec = self._spec(name, "histogram")
        with self._lock:
            h = self._hists[name]
            if h["count"] == 0:
                return {"count": 0, "sum": 0.0, "min": None, "max": None,
                        "p50": None, "p99": None}
            return {"count": h["count"], "sum": h["sum"],
                    "min": h["min"], "max": h["max"],
                    "p50": self._quantile_locked(spec, h, 0.50),
                    "p99": self._quantile_locked(spec, h, 0.99)}

    def get(self, name: str) -> float:
        with self._lock:
            if name not in self._specs:
                raise KeyError(f"metric {name!r} not declared")
            return self._values[name]

    def snapshot(self) -> Dict[str, float]:
        """Counters/gauges as floats; each histogram as its summary dict
        (additive — existing consumers only read the scalar fields)."""
        with self._lock:
            out = dict(self._values)
        for name, spec in list(self.specs().items()):
            if spec.kind == "histogram":
                out[name] = self.hist_summary(name)
        return out

    def specs(self) -> Dict[str, MetricSpec]:
        with self._lock:
            return dict(self._specs)


# ---- resilience counter contract ----
#
# The fault/retry/liveness planes (ps_pytorch_tpu/resilience/) each expose a
# snapshot() of cumulative counters; the trainers merge them into the step
# record (gated — only when a resilience plane is active) and
# tools/analyze.py's `faults` mode reads them back. This tuple is the one
# reviewable list of those fields: (name, unit, help).
RESILIENCE_COUNTERS = (
    ("kv_drops", "ops", "injected KV drops raised as transient errors"),
    ("kv_delays", "ops", "injected KV delays applied"),
    ("crashes", "events", "injected replica crashes fired"),
    ("ckpt_corruptions", "events",
     "injected post-commit checkpoint corruptions"),
    ("grad_nans", "events", "injected NaN-gradient steps"),
    ("kv_retries", "ops", "KV ops retried after a transient error"),
    ("kv_giveups", "ops", "KV ops failed after retries/budget ran out"),
    ("evictions", "events", "replicas evicted for missed heartbeats"),
    ("readmissions", "events", "evicted replicas readmitted on recovery"),
    ("mask_changes", "events", "leader participation-mask changes"),
    ("leader_kills", "events", "injected leader SIGKILLs fired"),
    ("kv_partition_drops", "ops",
     "KV ops dropped inside an injected partition window"),
    ("link_jitters", "ops", "injected per-link KV delays applied"),
    ("payload_bitflips", "ops",
     "injected in-alphabet chunk corruptions on KV reads"),
    ("payload_truncates", "ops", "injected torn-read chunk truncations"),
    ("grad_poisons", "steps",
     "steps where an injected grad_poison window scaled local gradients"),
    ("kv_backend_kills", "events",
     "injected kv_backend_kill outage windows opened"),
    ("kv_backend_wipes", "events",
     "injected kv_backend_wipe keyspace losses fired"),
    ("kv_backend_drops", "ops",
     "single-backend ops dropped inside a kv_backend_kill window"),
)


def declare_resilience_metrics(registry: Registry) -> Registry:
    """Declare every resilience counter on ``registry`` (all monotonic)."""
    for name, unit, help_ in RESILIENCE_COUNTERS:
        registry.counter(name, unit=unit, help=help_)
    return registry


# ---- gradient-integrity contract (ps_pytorch_tpu/resilience/integrity.py) --
#
# Same discipline: the reviewable surface of the three integrity layers.
# wire_integrity_failures comes from the transport channels (digest/decode/
# meta demotions); the rest from the leader-side GradIntegrity screen.
# Counters are cumulative (Prometheus renders them with _total — the drill
# gates on integrity_quarantines_total); quarantined-now is a gauge.
INTEGRITY_COUNTERS = (
    ("wire_integrity_failures", "reads",
     "channel reads demoted for digest mismatch / corrupt armour / torn "
     "meta"),
    ("integrity_screen_rejects", "contributions",
     "contributions rejected by the compressed-domain payload validators"),
    ("integrity_outlier_rejects", "contributions",
     "contributions rejected by the cross-contributor MAD outlier gate"),
    ("integrity_strikes", "events",
     "screened-out contributions charged to a contributor"),
    ("integrity_quarantines", "events",
     "contributors quarantined after reaching the strike limit"),
    ("integrity_readmissions", "events",
     "quarantined contributors readmitted on probation after clean "
     "screens"),
)
INTEGRITY_GAUGES = (
    ("integrity_quarantined", "contributors",
     "contributors currently quarantined"),
)


def declare_integrity_metrics(registry: Registry) -> Registry:
    """Declare the gradient-integrity counters/gauge on ``registry``."""
    for name, unit, help_ in INTEGRITY_COUNTERS:
        registry.counter(name, unit=unit, help=help_)
    for name, unit, help_ in INTEGRITY_GAUGES:
        registry.gauge(name, unit=unit, help=help_)
    return registry


# ---- replicated-KV contract (ps_pytorch_tpu/runtime/kvrep.py) -------------
#
# The quorum-replicated coordination plane's reviewable surface: quorum
# failures the retry plane saw, per-backend error/ejection/rejoin
# lifecycle, steady-state read-repair traffic, and anti-entropy resync
# volume — plus the two gauges a dashboard needs to see a degraded
# replica set AT A GLANCE.
KVREP_COUNTERS = (
    ("kvrep_quorum_failures", "ops",
     "logical KV ops that failed to reach a write/read quorum"),
    ("kvrep_backend_errors", "ops",
     "single-backend op failures absorbed below the quorum"),
    ("kvrep_ejections", "events",
     "backends ejected after consecutive failures"),
    ("kvrep_rejoins", "events",
     "ejected backends readmitted after probe + anti-entropy resync"),
    ("kvrep_read_repairs", "ops",
     "stale/absent replica copies overwritten during quorum reads"),
    ("kvrep_resyncs", "events", "anti-entropy resync passes completed"),
    ("kvrep_resync_keys", "keys",
     "replica copies repaired by anti-entropy resync"),
    ("kvrep_probes", "events", "probation probes sent to ejected backends"),
)
KVREP_GAUGES = (
    ("kvrep_backends", "backends", "configured KV replica backends"),
    ("kvrep_backends_healthy", "backends",
     "KV replica backends currently in the quorum set"),
)


def declare_kvrep_metrics(registry: Registry) -> Registry:
    """Declare the replicated-KV counters/gauges on ``registry``."""
    for name, unit, help_ in KVREP_COUNTERS:
        registry.counter(name, unit=unit, help=help_)
    for name, unit, help_ in KVREP_GAUGES:
        registry.gauge(name, unit=unit, help=help_)
    return registry


# ---- hierarchical sync contract (ps_pytorch_tpu/parallel/hierarchy.py) ----
#
# The 2-tier aggregation plane's reviewable surface: per-hop traffic,
# subtree partition/regraft lifecycle, aggregator failovers, and the live
# group-health gauges a dashboard needs to see a degraded run AT A GLANCE.
HIERARCHY_COUNTERS = (
    ("hierarchy_hops", "ops", "aggregation hops completed (any tier)"),
    ("hierarchy_group_publishes", "ops",
     "group aggregates re-encoded and published upward"),
    ("hierarchy_partitions", "events",
     "subtrees declared partitioned (went stale past the limit)"),
    ("hierarchy_regrafts", "events",
     "partitioned subtrees re-grafted after healing"),
    ("hierarchy_degraded_steps", "steps",
     "root updates applied with at least one subtree missing"),
    ("hierarchy_failovers", "events",
     "group aggregator roles adopted by another member"),
)
HIERARCHY_GAUGES = (
    ("hierarchy_groups", "groups", "sync groups in the topology"),
    ("hierarchy_groups_healthy", "groups",
     "groups contributing within the staleness limit"),
)


def declare_hierarchy_metrics(registry: Registry) -> Registry:
    """Declare the hierarchical-sync counters/gauges on ``registry``."""
    for name, unit, help_ in HIERARCHY_COUNTERS:
        registry.counter(name, unit=unit, help=help_)
    for name, unit, help_ in HIERARCHY_GAUGES:
        registry.gauge(name, unit=unit, help=help_)
    return registry


# ---- elastic control-plane contract (ps_pytorch_tpu/elastic/) ----
#
# Same discipline: the reviewable list of what the election/membership
# planes surface. leader_epoch and world_size are GAUGES (the epoch is
# monotonic but a freshly-promoted process starts from the observed value,
# not zero); membership_changes/elections are cumulative counters, so the
# Prometheus exposition renders them with the _total suffix.
ELASTIC_COUNTERS = (
    ("membership_changes", "events",
     "membership-epoch bumps (joins, leaves, evictions folded in)"),
    ("elections", "events", "leader campaigns run after a stale lease"),
)
ELASTIC_GAUGES = (
    ("leader_epoch", "epoch", "current leader-lease epoch"),
    ("world_size", "processes", "active members in the current view"),
)


def declare_elastic_metrics(registry: Registry) -> Registry:
    """Declare the elastic counters/gauges on ``registry``."""
    for name, unit, help_ in ELASTIC_COUNTERS:
        registry.counter(name, unit=unit, help=help_)
    for name, unit, help_ in ELASTIC_GAUGES:
        registry.gauge(name, unit=unit, help=help_)
    return registry


# ---- serving metric contract (ps_pytorch_tpu/serving/) ----
#
# Same discipline as RESILIENCE_COUNTERS: the one reviewable list of what
# the serving plane emits. Counters/gauges are (name, unit, help);
# histograms observe seconds with the DEFAULT_BUCKETS latency ladder.
SERVING_COUNTERS = (
    ("serve_requests", "requests", "requests completed"),
    ("serve_tokens", "tokens", "tokens sampled across all requests"),
    ("serve_rejected", "requests", "requests rejected at admission (queue full)"),
    ("serve_shed", "requests", "requests shed for a passed deadline"),
    ("serve_reloads", "events", "hot checkpoint reloads applied"),
    ("serve_resolve_races", "events", "terminal resolutions that lost the "
                                      "first-wins CAS (double-resolve "
                                      "attempts suppressed)"),
    ("serve_rejected_oversize", "requests", "requests rejected for an "
                                            "oversized or malformed body"),
    ("slo_violations", "events", "per-request SLO objective violations"),
)
SERVING_GAUGES = (
    ("serve_active_slots", "slots", "decode slots currently occupied"),
    ("serve_queue_depth", "requests", "admission queue depth"),
    ("serve_model_step", "step", "checkpoint step currently served"),
    ("slo_compliance", "", "fraction of SLO objectives met over the "
                           "slow window (1.0 = all)"),
    ("slo_burn_rate", "", "worst per-objective slow-window error-budget "
                          "burn rate (1.0 = budget exactly)"),
)
SERVING_HISTOGRAMS = (
    ("serve_request_latency_s", "s", "submit -> last token latency"),
    ("serve_ttft_s", "s", "submit -> first token latency (TTFT)"),
    ("serve_queue_wait_s", "s", "submit -> admission queue wait"),
)


def declare_serving_metrics(registry: Registry) -> Registry:
    """Declare the serving counters/gauges/histograms on ``registry``."""
    for name, unit, help_ in SERVING_COUNTERS:
        registry.counter(name, unit=unit, help=help_)
    for name, unit, help_ in SERVING_GAUGES:
        registry.gauge(name, unit=unit, help=help_)
    for name, unit, help_ in SERVING_HISTOGRAMS:
        registry.histogram(name, unit=unit, help=help_)
    return registry


# ---- router metric contract (ps_pytorch_tpu/serving/router.py) ----
#
# The fleet front-end's view: routed request outcomes, failover retries,
# hedged backups, and backend health transitions. Routed availability
# (router_requests vs router_failed) is what the SLO burn-rate engine
# consumes at the router — the client-visible number, not any one
# replica's.
ROUTER_COUNTERS = (
    ("router_requests", "requests", "requests routed to completion"),
    ("router_failed", "requests", "requests that exhausted retries and "
                                  "surfaced an error to the client"),
    ("router_retries", "attempts", "failover re-dispatches to a different "
                                   "replica after a retryable failure"),
    ("router_hedges", "requests", "hedged backup requests issued past the "
                                  "tail-latency threshold"),
    ("router_hedge_wins", "requests", "hedged backups that beat the "
                                      "primary attempt"),
    ("router_hedge_cancelled", "requests", "hedge losers cancelled after "
                                           "the first response won"),
    ("router_backend_ejections", "events", "backends marked unhealthy "
                                           "(probe/lease/forward failure)"),
)
ROUTER_GAUGES = (
    ("router_backends_ready", "replicas", "backends currently health-gated "
                                          "ready"),
    ("router_outstanding", "requests", "requests in flight across all "
                                       "backends"),
)
ROUTER_HISTOGRAMS = (
    ("router_request_latency_s", "s", "routed submit -> response latency "
                                      "(includes retries and hedges)"),
)


def declare_router_metrics(registry: Registry) -> Registry:
    """Declare the router counters/gauges/histograms on ``registry``."""
    for name, unit, help_ in ROUTER_COUNTERS:
        registry.counter(name, unit=unit, help=help_)
    for name, unit, help_ in ROUTER_GAUGES:
        registry.gauge(name, unit=unit, help=help_)
    for name, unit, help_ in ROUTER_HISTOGRAMS:
        registry.histogram(name, unit=unit, help=help_)
    return registry


# ---- training metric contract (ps_pytorch_tpu/runtime/ trainers) ----
#
# The live ops plane (telemetry/prometheus.py --metrics-port exporter)
# renders whatever the Registry holds; this tuple is the reviewable list of
# what the TRAINERS put there each step. Names mirror the MetricsLogger
# JSONL fields so a dashboard and a post-hoc analysis read the same
# vocabulary.
TRAINING_COUNTERS = (
    ("train_steps", "steps", "training steps completed"),
    ("dispatch_ahead_steps", "steps",
     "steps queued while the device still ran the step before (Trainer: "
     "the host loop keeps ahead of the chip)"),
    # Fed by Tracer.add from JAX's compile events (utils/compile_cache.py),
    # from the trainer's first line on: past step 1 of a run a rise in any
    # of them is a recompile in the step loop.
    ("jax_programs_compiled_total", "programs",
     "XLA programs the backend compiled or loaded from the compile cache"),
    ("jax_compile_cache_misses_total", "programs",
     "XLA programs compiled anew, not loaded from the compile cache"),
    ("jax_compile_seconds_total", "seconds",
     "seconds in the backend's compile or cache load, net of nested ones"),
)
# An MoE step's routing statistics (parallel/ep.py: aux; a dropless arch adds
# the next five, one that chooses under a bias the last two), set by LMTrainer on every logged step under the names the
# JSONL record gives them.
ROUTING_GAUGES = (
    ("aux", "", "MoE load-balance loss, experts * sum_e(share of assignments "
     "* mean router probability)"),
    ("z_loss", "", "router z-loss, mean of logsumexp(router logits)^2 over "
     "tokens and layers"),
    ("expert_load_max_over_mean", "", "busiest expert's assignments over "
     "tokens*top_k/experts, worst layer"),
    ("moe_dropped", "assignments", "token-to-expert assignments whose output "
     "was not added (a dropless router must read 0)"),
    ("moe_held_share", "", "assignments to the experts held here over "
     "tokens*top_k, mean over layers (1 where every expert is held)"),
    ("moe_tail_rows_share", "", "rows of a layer's main part that no held "
     "group owns over the rows it is sized for, mean over layers: the share "
     "of the grouped matmuls' row tiles visited and not multiplied (0 where "
     "every expert is held)"),
    ("moe_bias_abs_max", "", "largest |expert_bias| over layers and experts "
     "after the step's move: how far the balancing has shifted the top-k's "
     "choice (archs that choose under a bias)"),
    ("moe_load_all_max_over_mean", "", "busiest of ALL router outputs' "
     "assignments over tokens*top_k/experts, worst layer: what the bias acts "
     "on (archs that choose under a bias, and granite4h from its layers' own "
     "counts)"),
    ("mixer_held_share", "", "heads of each mixer (and channels of the "
     "shared expert) held here over the model's, 1 / lm_mixer_shares; set "
     "only where a share is held"),
)
# What a hybrid LM counts inside its step (models/transformer.py
# COUNTER_NAMES, parallel/sp.py), set by LMTrainer like the routing gauges.
HYBRID_GAUGES = (
    ("ssm_state_abs_max", "", "largest |h| over the state-space layers' "
     "states at the scan's chunk boundaries: the scan's numerical health"),
    ("diff_lambda_max", "", "largest |lambda| over the differential-"
     "attention layers"),
    ("gdn_state_abs_max", "", "largest |S| over the linear-attention layers' "
     "matrix states at the delta rule's chunk boundaries: bounded under unit "
     "keys and beta <= 1, so growth is a wrong kernel or a learning rate too "
     "high"),
    ("ssd_state_abs_max", "", "largest |h| over the Mamba-2 layers' head-wise "
     "states at the state-space-dual form's chunk boundaries: the walk's "
     "numerical health"),
    ("eva_pool_weight_max", "", "largest pooling weight over the EVA layers' "
     "chunks and heads: 1/chunk is a mean, 1.0 a chunk read through one "
     "token"),
    ("next_token_loss_head0", "", "head 0's own mean loss where the arch has "
     "several prediction heads: the next-token loss that compares with other "
     "models (train_loss is the mean over every head)"),
)
TRAINING_GAUGES = (
    ("train_step", "step", "current training step"),
    ("train_loss", "", "last step's training loss"),
    ("train_grad_norm", "", "last step's global gradient norm"),
    ("train_step_time_s", "s", "last step's wall time"),
    ("train_data_time_s", "s", "last step's input-pipeline wait"),
    ("train_examples_per_sec", "examples/s", "last step's goodput"),
    ("device_mem_peak_bytes", "bytes",
     "device HBM peak bytes in use (0 when the backend has no stats)"),
    ("device_mem_bytes", "bytes",
     "device HBM bytes in use (0 when the backend has no stats)"),
    ("device_mem_reserved_peak_bytes", "bytes",
     "device HBM peak bytes reserved for running programs' temporaries "
     "(0 when the backend has no stats)"),
    ("host_rss_bytes", "bytes", "host process peak RSS watermark"),
    ("compute_dtype", "bytes", "item size of the dtype the built LM computes "
     "in (2 = bfloat16, 4 = float32; set by LMTrainer); the JSONL field of "
     "the same name, on a run's first record, holds the dtype's name"),
) + ROUTING_GAUGES + HYBRID_GAUGES
TRAINING_HISTOGRAMS = (
    ("train_step_latency_s", "s", "per-step wall-time distribution"),
)


def declare_training_metrics(registry: Registry) -> Registry:
    """Declare the trainer-side counters/gauges/histograms on ``registry``."""
    for name, unit, help_ in TRAINING_COUNTERS:
        registry.counter(name, unit=unit, help=help_)
    for name, unit, help_ in TRAINING_GAUGES:
        registry.gauge(name, unit=unit, help=help_)
    for name, unit, help_ in TRAINING_HISTOGRAMS:
        registry.histogram(name, unit=unit, help=help_)
    return registry


def host_rss_bytes() -> int:
    """Peak resident-set watermark of this process via getrusage (no
    psutil dependency). ru_maxrss is KiB on Linux, bytes on macOS; 0 when
    the platform offers neither."""
    try:
        import resource
        import sys
        rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        return int(rss if sys.platform == "darwin" else rss * 1024)
    except Exception:
        return 0


# ---- derived per-step arithmetic (one definition; PERF.md cites this) ----

def compute_mfu(flops_per_step: Optional[int], step_time_s: float,
                peak_flops_per_chip: Optional[float],
                n_chips: int = 1) -> Optional[float]:
    """Model FLOPs utilization: achieved FLOPs/sec over aggregate peak.

    None (not 0.0) whenever an input is unknown — an unknown peak (CPU) or
    an uncounted step must read as "no claim", never as "0% utilized".
    """
    if not flops_per_step or flops_per_step <= 0 or step_time_s <= 0:
        return None
    if not peak_flops_per_chip or n_chips <= 0:
        return None
    return flops_per_step / (step_time_s * peak_flops_per_chip * n_chips)


def data_stall_fraction(data_time_s: float,
                        step_time_s: float) -> Optional[float]:
    """Fraction of the step spent waiting on the input pipeline, clamped to
    [0, 1] (a prefetched loader can report ~0 even when the host is busy)."""
    if step_time_s <= 0:
        return None
    return max(0.0, min(1.0, data_time_s / step_time_s))


# record field -> key of ``device.memory_stats()``
_DEVICE_MEM_FIELDS = (
    ("device_mem_peak_bytes", "peak_bytes_in_use"),
    ("device_mem_bytes", "bytes_in_use"),
    ("device_mem_reserved_peak_bytes", "peak_bytes_reserved"),
)


def device_memory_record(device=None) -> dict:
    """Device memory via the backend's memory_stats(): the given device's,
    or this process's local devices'. ``device_mem_peak_bytes`` and
    ``device_mem_bytes`` are the fullest device's live buffers (device 0
    alone hides a lopsided placement). On the TPU the peak of live buffers
    is NOT the peak a chip has to hold: a running program's temporaries are
    reserved apart, in ``device_mem_reserved_peak_bytes`` (ResNet-18 b=4096:
    0.16 GB live, 7.15 GB reserved). ``device_mem_per_device`` holds each
    device's three values, keyed by device id. {} when the backend has none
    (CPU) — additive fields, absent rather than null, so CPU JSONL stays
    compact."""
    if device is None:
        import jax
        devices = jax.local_devices()
    else:
        devices = [device]
    per_device = {}
    for d in devices:
        s = d.memory_stats()
        if s:
            per_device[str(d.id)] = {
                field: int(s[key]) for field, key in _DEVICE_MEM_FIELDS
                if s.get(key) is not None}
    out = {}
    for field, _ in _DEVICE_MEM_FIELDS:
        vals = [v[field] for v in per_device.values() if field in v]
        if vals:
            out[field] = max(vals)
    if out:
        out["device_mem_per_device"] = per_device
    return out


def set_device_memory_gauges(registry: Registry, mem: dict) -> None:
    """One ``device_memory_record()`` into the registry: the three gauges of
    TRAINING_GAUGES, and ``<field>_d<id>`` for each device (declared on
    first sight: the devices are known only at run time)."""
    for field, _ in _DEVICE_MEM_FIELDS:
        if field in mem:
            registry.set(field, mem[field])
    for dev, vals in mem.get("device_mem_per_device", {}).items():
        for field, v in vals.items():
            name = f"{field}_d{dev}"
            try:
                registry.set(name, v)
            except KeyError:
                registry.gauge(name, unit="bytes",
                               help=f"{field} of device {dev}")
                registry.set(name, v)


def derive_step_record(*, step_time_s: float, data_time_s: float = 0.0,
                       examples: Optional[int] = None,
                       tokens: Optional[int] = None,
                       flops_per_step: Optional[int] = None,
                       peak_flops_per_chip: Optional[float] = None,
                       n_chips: int = 1, device=None,
                       with_memory: bool = True) -> dict:
    """The MetricsLogger v2 derived fields for one step.

    Always contains ``mfu``, ``examples_per_sec``, ``data_stall_frac``
    (None when uncomputable — the keys are the schema); ``tokens_per_sec``
    and device-memory fields are additive when available.
    """
    rec = {
        "mfu": (None if (m := compute_mfu(flops_per_step, step_time_s,
                                          peak_flops_per_chip, n_chips))
                is None else round(m, 6)),
        "examples_per_sec": (round(examples / step_time_s, 2)
                            if examples and step_time_s > 0 else None),
        "data_stall_frac": (None if (f := data_stall_fraction(
            data_time_s, step_time_s)) is None else round(f, 4)),
    }
    if tokens and step_time_s > 0:
        rec["tokens_per_sec"] = round(tokens / step_time_s, 1)
    if with_memory:
        rec.update(device_memory_record(device))
    return rec
