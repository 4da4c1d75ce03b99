"""Typed configuration for the whole framework.

Replaces the reference's three-tier ad-hoc flag system (argparse surface at
``distributed_nn.py:24-68``, kwargs re-packing with renames at
``distributed_nn.py:82-107``, and the ``Cfg`` dict in ``tools/pytorch_ec2.py``)
with one dataclass that is CLI-overridable and serialized into checkpoints.

The reference's confusing renames (master ``kill_threshold`` <- CLI
``num_aggregate``; master ``timeout_threshold`` <- CLI ``kill_threshold``,
``distributed_nn.py:82-94``) are deliberately NOT reproduced: here
``num_aggregate`` always means "aggregate the first K contributions" and
``kill_threshold`` always means the straggler deadline (seconds).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
from dataclasses import dataclass, field
from typing import Any, Optional


# The rows of models/transformer.py:ARCHS (kept here as names only, so that
# building a config imports no model code; tests/test_arch_olmoe.py holds the two
# lists equal).
LM_ARCHS = ("gpt2", "olmoe", "smallthinker", "trinity", "phi4flash",
            "qwen3next", "nemotronh", "evabyte", "granite4h")
# ... of which those that route dropless (an MoE model: lm_parallelism=ep).
_DROPLESS_ARCHS = ("olmoe", "smallthinker", "trinity", "qwen3next",
                   "nemotronh", "granite4h")


@dataclass
class TrainConfig:
    # -- model / data (reference: distributed_nn.py:30-49) --
    network: str = "LeNet"          # LeNet|ResNet18|ResNet34|ResNet50|ResNet101|ResNet152|VGG11|VGG13|VGG16|VGG19
    dataset: str = "MNIST"          # MNIST|Cifar10|Cifar100|SVHN|synthetic
    batch_size: int = 128            # global batch size (split across the data mesh axis)
    test_batch_size: int = 1000
    data_dir: str = "./data"
    num_classes: int = 0             # 0 = infer from dataset (Cifar100 -> 100, distributed_nn.py:111-114)
    loader_workers: int = 1          # train-loader assembly threads; 0 = one per CPU (datasets.DataLoader workers)

    # -- optimization (reference: distributed_nn.py:36-44, optim/sgd.py, optim/adam.py) --
    optimizer: str = "sgd"           # sgd|adam
    lr: float = 0.01
    lr_schedule: str = "constant"    # constant|step|cosine (optim/schedules.py; reference tuned a constant via tune.sh)
    lr_warmup_steps: int = 0         # linear 0->lr prefix
    lr_decay_steps: int = 0          # step period / cosine horizon; 0 = max_steps
    lr_decay_factor: float = 0.1     # step gamma / cosine floor fraction
    momentum: float = 0.5
    weight_decay: float = 0.0
    nesterov: bool = False
    adam_beta1: float = 0.9
    adam_beta2: float = 0.999
    adam_eps: float = 1e-8
    amsgrad: bool = False

    # -- run control (reference: distributed_nn.py:34-36, 60-63) --
    epochs: int = 1
    max_steps: int = 1000
    eval_freq: int = 50              # checkpoint every N steps (sync_replicas_master_nn.py:194-196)
    train_dir: str = "./train_dir"   # checkpoint directory (NFS dir in the reference)
    resume: bool = True              # NEW capability: restore-to-train (reference has none, SURVEY §5.4)
    seed: int = 42

    # -- parallelism (replaces --comm-type/--mode/--num-aggregate/--kill-threshold) --
    mode: str = "sync"               # sync | kofn | async  (reference 'normal'|backup-workers|stale-grad)
    num_aggregate: int = 0           # K in K-of-N aggregation; 0 = all replicas (sync)
    kill_threshold: float = 0.0      # straggler deadline in seconds; 0 = no deadline
    staleness_limit: int = 4         # async mode: drop contributions older than this many steps
    staleness_decay: float = 0.0     # async mode: weight = decay**staleness; 0 = no decay (pure average)
    async_slices: int = 2            # async mode: device groups acting as independent slices
    fetch_every: int = 1             # async mode: slice re-fetches canonical weights every N of its steps
    publish_every: int = 1           # async leader publishes canonical params every N applied updates (bounds DCN publish traffic; final state always published)
    data_axis: int = 0               # number of data-parallel shards; 0 = all local devices
    model_axis: int = 1              # reserved mesh axis for TP (unused by these models)
    sync_batchnorm: bool = False     # reference keeps BN stats worker-local (distributed_worker.py:245-252)
    shard_update: bool = False       # ZeRO-1 cross-replica sharded weight update (parallel/zero.py)
    shard_wire: bool = False         # ZeRO-over-the-wire: sharded weight update on the KV plane (parallel/zero_wire.py; async mode, flat topology)

    # -- hierarchical sync (parallel/hierarchy.py: 2-tier multi-hop
    #    aggregation over the coordination KV; flat = the star topology) --
    sync_topology: str = "flat"      # flat | hier (hier requires compress_grad + a homomorphic grad_codec: hops sum in the compressed domain)
    sync_group_size: int = 0         # members per intra-group tier; 0 = auto (~sqrt of slice count)
    sync_intra_every: int = 1        # member -> group-aggregator hop every N member steps (fast intra-slice link)
    sync_inter_every: int = 1        # group -> root hop every N group rounds (slow inter-region link; raise to amortize WAN RTTs)
    hier_hop_retries: int = 3        # jittered retry attempts per upward hop before the hop is skipped (degraded, never fatal)

    # -- numerics / TPU --
    compute_dtype: str = "bfloat16"  # MXU-native compute dtype; params stay float32
    device_normalize: bool = True    # loaders ship raw uint8; the jitted step normalizes in-graph (4x less host->device traffic)
    conv_impl: str = "xla"           # xla | pallas | pallas_im2col (ResNet/VGG stride-1 3x3s via ops/pallas_conv.py; A/B'd on chip before any default change)
    donate: bool = True              # donate buffers to the jitted step
    remat: bool = False              # jax.checkpoint the forward for memory

    # -- compression (reference: --compress-grad, compression.py) --
    compress_grad: bool = False      # compress DCN-crossing gradient mirrors / checkpoints
    codec_level: int = 3
    grad_codec: str = "blosc"        # blosc | int8 (on-device Pallas) | int8lat/topk/randk (homomorphic: leader sums in the compressed domain, compression/codecs.py)
    grad_topk_frac: float = 0.01     # topk/randk: fraction of entries kept per leaf
    ef: bool = False                 # sender-side error feedback for lossy homomorphic codecs (residual carried across steps, checkpointed)
    ef_clip: float = 0.0             # per-leaf L2 cap on the EF residual; 0 = unclamped. Bounds what an absorbed poisoned gradient can re-emit through the validator-legal band (PERF.md §17/§18)

    # -- overlapped gradient wire (parallel/buckets.py + transport.py; the
    #    reference's per-layer send-during-backward, resnet_split.py:25-42) --
    wire_bucket_mb: float = 4.0      # bucket size target for the async DCN wire; 0 = legacy blocking single-payload schedule (bytes identical either way)
    wire_workers: int = 4            # encode/decode worker threads per channel; <=1 = no pipelining

    # -- LM / long-context surface (train_lm.py; reference has no LM) --
    lm_vocab: int = 256
    lm_d_model: int = 128
    lm_layers: int = 2
    lm_heads: int = 4
    lm_seq_len: int = 1024           # sharded over the mesh (ring attention)
    lm_corpus_tokens: int = 1_000_000
    lm_corpus_file: str = ""         # byte-level REAL corpus from any local file ("" = synthetic Markov stream)
    lm_parallelism: str = "sp"       # sp (sequence/ring) | tp (tensor) | pp (pipeline) | ep (MoE model, experts sharded over 'data'; also how an MoE model is chosen on ONE chip)
    lm_arch: str = "gpt2"            # gpt2 (LayerNorm, learned positions, GELU 4d FFN; MoE: capacity top-1/2) | olmoe (RMSNorm, RoPE, q/k norm, dropless top-k SwiGLU experts, z-loss; needs lm_parallelism=ep) | smallthinker (RMSNorm; three window-4096 RoPE layers to one full-causal layer without position encoding; dropless top-k ReLU-gated experts, gates renormalised, router before attention; needs lm_parallelism=ep) | trinity (RMSNorm on each sublayer's input and output; three window-2048 RoPE layers to one full-causal layer without position encoding; q/k norm a head; gated attention output; embedding times sqrt(d); lm_dense_layers dense SwiGLU layers, then dropless sigmoid-scored top-k SwiGLU experts chosen under a bias the step moves against the load, gates renormalised and scaled, one shared expert; needs lm_parallelism=ep) | phi4flash (a decoder-hybrid-decoder; LayerNorm eps 1e-5, no position encoding, SwiGLU FFN, head tied to the embedding; layer kinds by index and depth, lm_layers a multiple of 4: Mamba-1 state-space layers and window-512 differential-attention layers alternate in the first half, then one Mamba layer whose scan output and one full differential-attention layer whose K/V every later layer reads, then gated memory units and cross-attention layers alternate; needs lm_parallelism=sp on ONE device) | qwen3next (zero-centred RMSNorm; three Gated DeltaNet linear-attention layers (16 key / 32 value heads of 128, a 4-tap convolution, the chunked gated delta rule) to one softmax-attention layer with q/k norm a head, a gated output and RoPE on a quarter of the head; dropless softmax-scored top-k SwiGLU experts, gates renormalised, one shared expert under a sigmoid gate; needs lm_parallelism=ep) | nemotronh (RMSNorm eps 1e-5, no position encoding; every layer ONE pre-norm residual sublayer by the letter of the published 52-letter pattern MEMEM*E...: a Mamba-2 mixer (64 heads of 64 with a [64, 128] state, B and C in 8 groups, a biased 4-tap convolution, the chunked state-space-dual kernel, the gate before a norm in 8 groups), an attention mixer, or an expert layer alone: dropless sigmoid-scored top-k experts down(relu(up x)^2) without a gate projection, chosen under a bias the step moves against the load, gates renormalised and scaled by 2.5, one shared expert of twice the width; lm_layers at most 52; needs lm_parallelism=ep) | evabyte (byte-level; zero-centred RMSNorm eps 1e-5, RoPE theta 1e5, SwiGLU FFN; every layer an EVA layer: exact causal attention inside a window of 2048 that is a block of the diagonal, ONE softmax shared with the 16-token chunk summaries of every earlier window (a softmax-weighted pooling under two learned vectors a head), by the fused kernels of ops/eva_attention.py under lm_attention=flash; 8 prediction heads on one trunk, head i predicting token t + 1 + i, float32 logits; lm_seq_len a multiple of 16; needs lm_parallelism=sp on ONE device) | granite4h (RMSNorm eps 1e-5, no position encoding, head tied to the embedding; every layer a mixer AND an expert half: layer i attends where i % 10 == 5 (grouped-query heads, scores times 1/128) and is a Mamba-2 mixer otherwise (128 heads of 64 with a [64, 128] state, B, C and the gated norm in ONE group, a biased 4-tap convolution, chunks of 256); dropless top-k SwiGLU experts under a softmax over the chosen logits beside one shared SwiGLU expert of width 1536; the embedding times 12, each sublayer's output times 0.22, the logits over 16; lm_mixer_shares holds a share of the heads and of the shared expert; needs lm_parallelism=ep) — models/transformer.py ARCHS
    lm_kv_heads: int = 0             # key/value heads, each serving lm_heads / lm_kv_heads query heads (0 = lm_heads); sp on one device or ep, attention full | flash
    lm_head_dim: int = 0             # head size (0 = lm_d_model / lm_heads)
    lm_ffn_dim: int = 0              # FFN / expert width (0 = 4 * lm_d_model)
    lm_dense_layers: int = 0         # dropless archs: the first N blocks have a dense gated FFN (SwiGLU under the arch's activation, no biases) in place of experts
    lm_dense_ffn_dim: int = 0        # ... of this width (0 = 4 * lm_d_model)
    lm_attention: str = "auto"       # auto | full | flash (fused Pallas kernel). full/flash are sequence-local: sp over >1 device requires auto (ring)
    lm_model_axis: int = 0           # tp/pp: size of the 'model' mesh axis (0 = all devices)
    lm_microbatches: int = 4         # pp: GPipe microbatch count
    lm_experts: int = 8              # ep: expert count (divisible by device count)
    lm_moe_top_k: int = 1            # ep: experts per token. gpt2 arch (capacity routing): 1 = switch, 2 = GShard top-2; olmoe / smallthinker arch (dropless): 1..lm_experts
    lm_mixer_shares: int = 1         # dropless archs: chips a layer's mixers and shared expert are divided over; this model holds share 0 of that many of every mixer's heads (a Mamba-2 layer's heads with B and C whole, the query heads with their key/value heads) and of the shared expert's channels, column-parallel in and row-parallel out, and each sublayer computes its own part of the result (one chip of a tensor-parallel deployment, without the exchange); lm_heads and lm_kv_heads stay the model's counts
    lm_experts_held: int = 0         # dropless archs: experts this model holds of each layer's lm_experts, the first block of that many (0 = all); the router stays lm_experts wide and the layer computes its own experts' part of the result (one chip of an expert-parallel deployment, without the exchange)

    # -- fault injection (tests / straggler drills; SURVEY §5.3: the
    #    reference had none) --
    inject_step_delay: float = 0.0   # seconds of artificial per-step delay
    inject_delay_process: int = -1   # process_index to slow; -1 = nobody

    # -- resilience (resilience/: deterministic chaos, liveness, retries,
    #    hardened checkpoints; generalizes the reference's tag-77/backup-
    #    worker straggler handling to crashes and flaky control planes) --
    fault_spec: str = ""             # seeded fault plane, e.g. "kv_drop:p=0.05,seed=7;replica_crash:r=0,step=40;ckpt_corrupt:step=20" (resilience/faults.py grammar)
    heartbeat_interval_s: float = 0.0  # per-process liveness beat period in seconds; 0 = heartbeats off
    heartbeat_timeout_s: float = 0.0   # missed-beat deadline before mask eviction; 0 = 3x interval
    kv_retry_attempts: int = 5       # attempts per KV op on transient coordination-service errors; 1 = no retries
    kv_retry_base_s: float = 0.05    # backoff base (exponential x2, jittered, capped at 2 s)
    kv_retry_budget: int = 1000      # run-wide retry budget before failing fast; 0 = unbounded
    kv_replicas: str = ""            # quorum-replicated coordination plane: comma-separated backend specs (dir:<path> | http://host:port | mem:), e.g. "dir:/mnt/a,dir:/mnt/b,dir:/mnt/c"; "" = single unreplicated backend (runtime/kvrep.py)
    kv_quorum: int = 0               # write/read quorum over the kv_replicas backends; 0 = majority (N//2+1). Must stay > N/2 so any two quorums overlap
    kv_resync_s: float = 1.0         # probation base for an ejected KV backend: first rejoin probe (+ anti-entropy resync) after this many seconds, growing 2x per consecutive failure (jittered)
    ckpt_keep: int = 0               # keep-last-N committed checkpoints; 0 = keep all
    auto_resume: int = 0             # max automatic restarts from the latest VALID checkpoint after a crash (train.py)
    leader_lease_s: float = 0.0      # leader refreshes a coordination-KV lease this often; followers raise LeaderLost when it goes stale (0 = lease off; runtime/coordinator.py)

    # -- gradient integrity (resilience/integrity.py: wire digests are
    #    always on — they ride the transport meta; these knobs govern the
    #    leader-side pre-sum screen + contributor quarantine) --
    grad_integrity: bool = True      # screen contributions (payload validators + MAD outlier gate) before the async/hier aggregation sum and quarantine repeat offenders
    integrity_mad_threshold: float = 6.0  # robust z-score above which a contributor's grad norm is an outlier (one-sided; needs >= 4 contributors)
    integrity_strike_limit: int = 3  # screened-out contributions before quarantine
    integrity_readmit_clean: int = 3  # consecutive clean screens before a quarantined contributor is readmitted on probation

    # -- elastic control plane (ps_pytorch_tpu/elastic/: leader election,
    #    epoch'd membership, shard rebalancing; turns LeaderLost into a
    #    recovered event instead of a fatal one) --
    elastic: bool = False            # epoch-fenced leader election + membership registry over the coordination KV (requires leader_lease_s > 0)
    elastic_leader: int = 0          # process index of the INITIAL leader; on a real fleet keep it off the coordination-service host (process 0) so killing the leader doesn't kill the KV

    # -- serving (serve.py + ps_pytorch_tpu/serving/: continuous-batching
    #    inference over trained LM checkpoints with hot reload) --
    serve_slots: int = 8             # concurrent decode slots (the continuous batch)
    serve_max_queue: int = 64        # admission queue depth before 503 backpressure
    serve_reload_s: float = 10.0     # checkpoint poll interval in seconds; 0 = hot reload off
    serve_port: int = 8300           # HTTP port; 0 = ephemeral
    serve_host: str = "127.0.0.1"
    serve_deadline_s: float = 30.0   # default per-request deadline; queued past it -> shed (504)
    serve_max_new: int = 128         # default n_new when the request doesn't set one
    slo_spec: str = ""               # serving SLO objectives, e.g. "ttft_p99<100ms;latency_p99<2s;availability>=99.5" (telemetry/slo.py grammar; "" = no SLO tracking)
    reqtrace_keep: int = 256         # request-trace ring capacity; 0 = per-request lifecycle tracing off
    reqtrace_sample: float = 0.05    # fraction of fast `done` requests kept (slow tail + non-done outcomes are always kept)
    serve_max_body_bytes: int = 1048576  # POST /v1/generate body cap; oversized -> 413 before reading a byte
    serve_kv_dir: str = ""           # fleet coordination KV directory (FileKV); "" = standalone replica, no fleet registration
    serve_fleet: str = "fleet"       # fleet name: replicas register at serve/<fleet>/replica/<id> in the KV
    serve_replica_id: int = 0        # this replica's id in the fleet (also the replica_kill fault's r=)
    serve_advertise: str = ""        # host the fleet record advertises ("" = serve_host); set when replicas bind 0.0.0.0

    # -- logging / profiling / telemetry --
    log_every: int = 1
    metrics_file: str = ""          # optional JSONL metrics sink ("" = stdout only; multi-process runs suffix .p<k> per host)
    profile_dir: str = ""           # jax.profiler trace output ("" = off; SURVEY §5.1)
    profile_steps: str = "10-12"    # inclusive step range to trace, "start-end"
    trace_file: str = ""            # host-side Chrome trace_event JSON ("" = off; telemetry/trace.py, opens in Perfetto)
    timeline_file: str = ""         # leader-merged per-replica step timeline JSONL ("" = <metrics_file>.timeline when multi-process; telemetry/aggregate.py)

    # -- live ops plane (telemetry/prometheus.py, health.py, flightrec.py) --
    metrics_port: int = 0           # Prometheus /metrics + /healthz exporter port; 0 = off (multi-process runs bind port + process_index)
    health_spec: str = ""           # training-health watchdogs, e.g. "nonfinite:halt;spike:warn,factor=10;stall:warn" (telemetry/health.py grammar)
    flight_file: str = ""           # flight-recorder dump path ("" = <train_dir>/flightrec.json when health_spec or metrics_port is set)

    def __post_init__(self) -> None:
        if self.num_classes == 0:
            # Single source of truth for per-dataset class counts
            # (reference: num_classes=100 for Cifar100, distributed_nn.py:111-114).
            from ps_pytorch_tpu.data.datasets import DATASET_SHAPES
            self.num_classes = DATASET_SHAPES.get(self.dataset, (0, 0, 0, 10, 0))[3]
        if self.mode not in ("sync", "kofn", "async"):
            raise ValueError(f"unknown mode {self.mode!r}")
        if self.lr_schedule not in ("constant", "step", "cosine"):
            raise ValueError(f"unknown lr_schedule {self.lr_schedule!r} "
                             "(constant | step | cosine)")
        if self.lm_parallelism not in ("sp", "tp", "pp", "ep"):
            raise ValueError(f"unknown lm_parallelism "
                             f"{self.lm_parallelism!r} (sp | tp | pp | ep)")
        if self.lm_attention not in ("auto", "full", "flash"):
            raise ValueError(f"unknown lm_attention "
                             f"{self.lm_attention!r} (auto | full | flash)")
        if self.lm_arch not in LM_ARCHS:
            raise ValueError(f"unknown lm_arch {self.lm_arch!r} "
                             f"({' | '.join(LM_ARCHS)})")
        if self.lm_arch in _DROPLESS_ARCHS:
            if self.lm_parallelism != "ep":
                raise ValueError(f"lm_arch={self.lm_arch} is an MoE model: "
                                 f"pass lm_parallelism=ep (on one chip too)")
            if not 1 <= self.lm_moe_top_k <= self.lm_experts:
                raise ValueError(f"lm_moe_top_k={self.lm_moe_top_k} (dropless "
                                 f"routing: must be in 1..lm_experts="
                                 f"{self.lm_experts})")
        elif self.lm_moe_top_k not in (1, 2):
            # The capacity path: 1 = switch, 2 = GShard; k>2 would otherwise
            # surface as an opaque trace-time shape error inside MoEMLP.
            raise ValueError(f"lm_moe_top_k={self.lm_moe_top_k} (capacity "
                             "routing: must be 1 [switch] or 2 [GShard "
                             "top-2]; lm_arch=olmoe routes dropless with any "
                             "k up to lm_experts)")
        if self.lm_kv_heads < 0 or self.lm_heads % (self.lm_kv_heads
                                                    or self.lm_heads):
            raise ValueError(f"lm_kv_heads={self.lm_kv_heads} (must be 0 = "
                             f"lm_heads, or divide lm_heads="
                             f"{self.lm_heads})")
        if self.lm_head_dim < 0 or (self.lm_head_dim or 2) % 2:
            raise ValueError(f"lm_head_dim={self.lm_head_dim} (must be 0 = "
                             "lm_d_model / lm_heads, or even)")
        if (self.lm_kv_heads not in (0, self.lm_heads) or self.lm_head_dim) \
                and self.lm_parallelism in ("tp", "pp"):
            raise ValueError("lm_kv_heads / lm_head_dim: tp and pp are built "
                             "for equal head counts of lm_d_model / "
                             "lm_heads; use lm_parallelism sp or ep")
        if self.lm_experts_held:
            if self.lm_arch not in _DROPLESS_ARCHS:
                raise ValueError(
                    f"lm_experts_held={self.lm_experts_held} needs a "
                    f"dropless arch ({' | '.join(_DROPLESS_ARCHS)}): the "
                    f"capacity path shards its experts over the mesh")
            if self.lm_experts_held < 0 \
                    or self.lm_experts % self.lm_experts_held:
                raise ValueError(f"lm_experts_held={self.lm_experts_held} "
                                 f"(must divide lm_experts="
                                 f"{self.lm_experts})")
        if self.lm_mixer_shares != 1:
            kv = self.lm_kv_heads or self.lm_heads
            if self.lm_arch not in _DROPLESS_ARCHS:
                raise ValueError(
                    f"lm_mixer_shares={self.lm_mixer_shares} needs a "
                    f"dropless arch ({' | '.join(_DROPLESS_ARCHS)}): only "
                    f"models/moe.MoEBlock holds a share of its mixers")
            if self.lm_mixer_shares < 1 or self.lm_heads \
                    % self.lm_mixer_shares or kv % self.lm_mixer_shares:
                raise ValueError(
                    f"lm_mixer_shares={self.lm_mixer_shares} (must divide "
                    f"lm_heads={self.lm_heads} and the {kv} key/value heads: "
                    f"a share holds whole heads)")
        if self.lm_ffn_dim < 0:
            raise ValueError(f"lm_ffn_dim={self.lm_ffn_dim} (must be >= 0; "
                             "0 = 4 * lm_d_model)")
        if self.lm_dense_layers or self.lm_dense_ffn_dim:
            if self.lm_arch not in _DROPLESS_ARCHS:
                raise ValueError(
                    f"lm_dense_layers={self.lm_dense_layers} / "
                    f"lm_dense_ffn_dim={self.lm_dense_ffn_dim} need a "
                    f"dropless lm_arch ({' | '.join(_DROPLESS_ARCHS)}): "
                    f"leading dense layers are built for those")
            if not 0 <= self.lm_dense_layers <= self.lm_layers \
                    or self.lm_dense_ffn_dim < 0:
                raise ValueError(
                    f"lm_dense_layers={self.lm_dense_layers} (must be in "
                    f"0..lm_layers={self.lm_layers}), lm_dense_ffn_dim="
                    f"{self.lm_dense_ffn_dim} (must be >= 0)")
        if self.lm_microbatches < 1:
            # 0 reaches the pp step as a division by zero mid-trace.
            raise ValueError(f"lm_microbatches={self.lm_microbatches} "
                             "(must be >= 1)")
        # One registry, one message: the channel, the aggregator, and this
        # config all reject unknown codecs through require_codec, so a typo
        # reads identically wherever it is caught.
        from ps_pytorch_tpu.compression.codecs import (
            EF_GRAD_CODECS, GRAD_CODECS, require_codec,
        )
        require_codec("grad_codec", self.grad_codec, GRAD_CODECS)
        if not (0.0 < self.grad_topk_frac <= 1.0):
            raise ValueError(f"grad_topk_frac={self.grad_topk_frac} "
                             "(must be in (0, 1])")
        if self.ef and self.grad_codec not in EF_GRAD_CODECS:
            raise ValueError(
                f"--ef requires a lossy homomorphic grad_codec "
                f"({' | '.join(EF_GRAD_CODECS)}), got {self.grad_codec!r}")
        if self.conv_impl not in ("xla", "pallas", "pallas_im2col"):
            raise ValueError(f"unknown conv_impl {self.conv_impl!r} "
                             "(xla | pallas | pallas_im2col)")
        if self.loader_workers < 0:
            raise ValueError(f"loader_workers={self.loader_workers} "
                             "(must be >= 0; 0 = one per CPU)")
        if self.nesterov and (self.momentum <= 0):
            raise ValueError("Nesterov momentum requires a momentum")
        if self.fault_spec:
            # Parse now: a typo'd spec must fail at config time, not
            # mid-run when the fault would have fired.
            from ps_pytorch_tpu.resilience.faults import parse_fault_spec
            parse_fault_spec(self.fault_spec)
        if self.health_spec:
            # Same config-time discipline as fault_spec: a typo'd watchdog
            # must fail here, not during the incident it was meant to catch.
            from ps_pytorch_tpu.telemetry.health import parse_health_spec
            parse_health_spec(self.health_spec)
        if self.metrics_port < 0:
            raise ValueError(f"metrics_port={self.metrics_port} "
                             "(must be >= 0; 0 = exporter off)")
        if self.kv_retry_attempts < 1:
            raise ValueError(f"kv_retry_attempts={self.kv_retry_attempts} "
                             "(must be >= 1; 1 = no retries)")
        if self.wire_bucket_mb < 0:
            raise ValueError(f"wire_bucket_mb={self.wire_bucket_mb} "
                             "(must be >= 0; 0 = blocking wire)")
        if self.wire_workers < 0:
            raise ValueError(f"wire_workers={self.wire_workers} "
                             "(must be >= 0; <=1 = no pipelining)")
        for name in ("heartbeat_interval_s", "heartbeat_timeout_s",
                     "kv_retry_base_s"):
            if getattr(self, name) < 0:
                raise ValueError(f"{name} must be >= 0")
        if 0 < self.heartbeat_timeout_s <= self.heartbeat_interval_s:
            # An inverted deadline can NEVER be met: every process looks
            # dead between its own beats and membership flaps forever.
            # Reject at config time with the fix in the message instead
            # of letting the run silently evict healthy replicas.
            raise ValueError(
                f"heartbeat_timeout_s={self.heartbeat_timeout_s} <= "
                f"heartbeat_interval_s={self.heartbeat_interval_s}: a beat "
                f"can never land inside its own deadline, so liveness "
                f"flaps instead of detecting death. Set heartbeat_timeout_s "
                f"> heartbeat_interval_s (0 = 3x interval), or 0 for the "
                f"default.")
        if 0 < self.heartbeat_timeout_s <= self.leader_lease_s:
            # Same inversion one layer up: the leader refreshes its lease
            # every leader_lease_s, so a liveness deadline at or below the
            # lease period evicts a healthy leader between refreshes.
            raise ValueError(
                f"heartbeat_timeout_s={self.heartbeat_timeout_s} <= "
                f"leader_lease_s={self.leader_lease_s}: the leader beats "
                f"at the lease cadence, so this deadline evicts a healthy "
                f"leader between refreshes. Set heartbeat_timeout_s > "
                f"leader_lease_s (0 = derived default).")
        if self.kv_replicas:
            # Parse + quorum-math check now (same config-time discipline
            # as fault_spec): a typo'd backend or an unsafe quorum must
            # fail before anything is wired under the trainers.
            from ps_pytorch_tpu.runtime.kvrep import parse_backend_specs
            n_rep = len(parse_backend_specs(self.kv_replicas))
            majority = n_rep // 2 + 1
            if self.kv_quorum and not majority <= self.kv_quorum <= n_rep:
                raise ValueError(
                    f"kv_quorum={self.kv_quorum} is unsafe for {n_rep} "
                    f"replicas: any two quorums must overlap, so it must "
                    f"be in [{majority}, {n_rep}] (0 = majority).")
        if self.kv_quorum < 0:
            raise ValueError(f"kv_quorum={self.kv_quorum} (must be >= 0; "
                             "0 = majority)")
        if self.kv_resync_s <= 0:
            raise ValueError(f"kv_resync_s={self.kv_resync_s} "
                             "(must be > 0)")
        if self.ef_clip < 0:
            raise ValueError(f"ef_clip={self.ef_clip} (must be >= 0; "
                             "0 = unclamped residual)")
        if self.ckpt_keep < 0 or self.kv_retry_budget < 0 or \
                self.auto_resume < 0:
            raise ValueError("ckpt_keep / kv_retry_budget / auto_resume "
                             "must be >= 0")
        if self.leader_lease_s < 0:
            raise ValueError(f"leader_lease_s={self.leader_lease_s} "
                             "(must be >= 0; 0 = lease off)")
        if self.elastic and self.leader_lease_s <= 0:
            # The election is DRIVEN by lease staleness: without a lease
            # there is no death signal and a campaign can never start.
            raise ValueError("elastic=True requires leader_lease_s > 0 "
                             "(the lease is the failure detector)")
        if self.elastic_leader < 0:
            raise ValueError(f"elastic_leader={self.elastic_leader} "
                             "(must be >= 0)")
        if self.integrity_mad_threshold <= 0:
            raise ValueError(
                f"integrity_mad_threshold={self.integrity_mad_threshold} "
                "(must be > 0)")
        if self.integrity_strike_limit < 1 or self.integrity_readmit_clean < 1:
            raise ValueError("integrity_strike_limit / "
                             "integrity_readmit_clean must be >= 1")
        if self.serve_slots < 1:
            raise ValueError(f"serve_slots={self.serve_slots} (must be >= 1)")
        if self.serve_max_queue < 1:
            raise ValueError(f"serve_max_queue={self.serve_max_queue} "
                             "(must be >= 1)")
        if self.serve_max_new < 1:
            raise ValueError(f"serve_max_new={self.serve_max_new} "
                             "(must be >= 1)")
        if self.serve_reload_s < 0 or self.serve_deadline_s <= 0:
            raise ValueError("serve_reload_s must be >= 0 and "
                             "serve_deadline_s > 0")
        if self.serve_port < 0:
            raise ValueError(f"serve_port={self.serve_port} "
                             "(must be >= 0; 0 = ephemeral)")
        if self.serve_max_body_bytes < 1:
            raise ValueError(f"serve_max_body_bytes="
                             f"{self.serve_max_body_bytes} (must be >= 1)")
        if self.serve_replica_id < 0:
            raise ValueError(f"serve_replica_id={self.serve_replica_id} "
                             "(must be >= 0)")
        if self.slo_spec:
            # Config-time validation, same family as fault_spec/health_spec.
            from ps_pytorch_tpu.telemetry.slo import parse_slo_spec
            parse_slo_spec(self.slo_spec)
        if self.reqtrace_keep < 0:
            raise ValueError(f"reqtrace_keep={self.reqtrace_keep} "
                             "(must be >= 0; 0 = tracing off)")
        if not 0.0 <= self.reqtrace_sample <= 1.0:
            raise ValueError(f"reqtrace_sample={self.reqtrace_sample} "
                             "(must be in [0, 1])")
        if self.sync_topology not in ("flat", "hier"):
            raise ValueError(f"unknown sync_topology {self.sync_topology!r} "
                             "(flat | hier)")
        if self.sync_topology == "hier":
            # Intra-group aggregators sum member payloads in the compressed
            # domain and re-encode once per hop — only the homomorphic
            # codecs support that; reject at config time, not mid-hop.
            from ps_pytorch_tpu.compression.codecs import (
                HOMOMORPHIC_GRAD_CODECS,
            )
            if not self.compress_grad or \
                    self.grad_codec not in HOMOMORPHIC_GRAD_CODECS:
                raise ValueError(
                    "sync_topology=hier requires compress_grad=True and a "
                    f"homomorphic grad_codec "
                    f"({' | '.join(HOMOMORPHIC_GRAD_CODECS)}), got "
                    f"compress_grad={self.compress_grad} "
                    f"grad_codec={self.grad_codec!r}")
        if self.sync_group_size < 0:
            raise ValueError(f"sync_group_size={self.sync_group_size} "
                             "(must be >= 0; 0 = auto)")
        if self.sync_intra_every < 1 or self.sync_inter_every < 1:
            raise ValueError("sync_intra_every / sync_inter_every must be "
                             ">= 1")
        if self.hier_hop_retries < 1:
            raise ValueError(f"hier_hop_retries={self.hier_hop_retries} "
                             "(must be >= 1; 1 = no retries)")
        if self.shard_wire:
            # --shard-wire holds a bitwise guarantee (sharded update ==
            # replicated update, exactly). Reject at config time every
            # combination that cannot certify it, one clear message each.
            if self.shard_update:
                raise ValueError(
                    "--shard-wire and --shard-update are two homes for the "
                    "SAME ZeRO-1 state split: across KV replicas vs across "
                    "the in-mesh data axis. Nesting them would shard "
                    "already-sharded optimizer state; pick one.")
            if self.mode != "async":
                raise ValueError(
                    f"--shard-wire shards the weight update on the async KV "
                    f"plane; mode={self.mode!r} has no KV update path. Use "
                    f"--mode async, or --shard-update for the in-mesh "
                    f"(sync/kofn) form.")
            if self.sync_topology == "hier":
                raise ValueError(
                    "--shard-wire requires sync_topology=flat: hierarchical "
                    "multi-hop re-weighting aggregates per tier, so the "
                    "per-shard update could not be certified bitwise-equal "
                    "to the replicated update.")
            if self.compress_grad and self.grad_codec == "int8":
                raise ValueError(
                    "--shard-wire cannot use grad_codec=int8: its on-device "
                    "Pallas dequantize keeps per-contributor payloads "
                    "device-resident, while the sharded update is applied "
                    "host-side. Use blosc or a homomorphic codec "
                    "(int8lat | topk | randk); --ef composes fine.")
            if self.lr_schedule != "constant":
                raise ValueError(
                    f"--shard-wire supports lr_schedule=constant only (got "
                    f"{self.lr_schedule!r}): the host-side sharded optimizer "
                    f"pins the float32 step size; a jitted schedule would "
                    f"break the bitwise sharded==replicated guarantee.")
        if self.mode == "async" and self.publish_every > max(self.staleness_limit, 1):
            # Followers only ever see published versions: a publish gap
            # wider than the staleness window makes EVERY follower gradient
            # permanently stale (silently leader-only training).
            raise ValueError(
                f"publish_every={self.publish_every} > "
                f"staleness_limit={self.staleness_limit}: followers could "
                f"never contribute a fresh-enough gradient")

    # ---- serialization (into checkpoints / across the control plane) ----
    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), sort_keys=True)

    @classmethod
    def from_json(cls, s: str) -> "TrainConfig":
        return cls(**json.loads(s))

    def replace(self, **kw: Any) -> "TrainConfig":
        # Re-infer num_classes when the dataset changes without an explicit
        # override, so replace(dataset="Cifar100") doesn't keep a stale head.
        if "dataset" in kw and "num_classes" not in kw:
            kw["num_classes"] = 0
        return dataclasses.replace(self, **kw)


def add_train_args(parser: Optional[argparse.ArgumentParser] = None) -> argparse.ArgumentParser:
    """Build the CLI surface (reference flag parity: ``distributed_nn.py:24-68``)."""
    parser = parser or argparse.ArgumentParser(description="ps_pytorch_tpu trainer")
    for f in dataclasses.fields(TrainConfig):
        name = "--" + f.name.replace("_", "-")
        if f.type == "bool" or isinstance(f.default, bool):
            parser.add_argument(name, type=lambda s: s.lower() in ("1", "true", "yes"),
                                default=f.default, metavar="BOOL")
        else:
            parser.add_argument(name, type=type(f.default), default=f.default)
    return parser


def config_from_args(argv: Optional[list] = None) -> TrainConfig:
    args = add_train_args().parse_args(argv)
    return TrainConfig(**{f.name: getattr(args, f.name) for f in dataclasses.fields(TrainConfig)})
