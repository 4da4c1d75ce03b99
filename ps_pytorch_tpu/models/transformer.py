"""Decoder-only transformer LM with pluggable attention parallelism.

Beyond-parity model family (the reference is CNN-only, SURVEY §5.7): a
GPT-style causal LM whose attention can run (a) unsharded ("full") or
(b) as ring attention over a mesh axis ("ring", ``parallel/ring.py``) when
the module is applied inside ``shard_map`` with the sequence axis sharded —
the long-context training path (``parallel/sp.py``).

Everything except attention is per-token (LayerNorm, MLP, embeddings), so
the module body is identical in both modes; only the attention exchange
crosses shards. Learned positional embeddings are indexed by GLOBAL token
position, passed in by the caller (the sp step knows each shard's offset).
"""

import math
from functools import partial
from typing import Any, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ps_pytorch_tpu.models.remat import kept, remat_block
from ps_pytorch_tpu.models.ssm import gmu_sublayer, mamba_sublayer
from ps_pytorch_tpu.ops.flash_attention import flash_attention
from ps_pytorch_tpu.parallel.ring import full_attention, ring_attention
from ps_pytorch_tpu.telemetry.trace import device_scope


class Arch(NamedTuple):
    """What varies between the decoder blocks this repo runs; everything
    else (residual layout, separate bias-free q/k/v/o projections, the
    attention kernels, the untied head) is common. One ``arch`` name picks a
    row for both LM classes (``--lm-arch``)."""
    rms_norm: bool = False      # RMSNorm(eps) | LayerNorm (flax eps 1e-6)
    norm_eps: float = 1e-6
    zero_centred_norm: bool = False     # the RMSNorm's scale is 1 + w, w from 0, the statistics and the product float32 (ZeroCentredRMSNorm)
    rope_theta: float = 0.0     # 0: learned position table | RoPE base
    rope_share: float = 1.0     # the share of a head's features RoPE rotates, the first ones (partial_rotary_factor); the rest pass unrotated
    qk_norm: bool = False       # norm over all d features of q and k, before the heads split
    head_qk_norm: bool = False  # norm over ONE head's features of q and k, after the split (one scale [head_dim] for every head)
    attn_gate: bool = False     # attention's output times sigmoid(norm(x) Wg), elementwise, before Wo
    post_norm: bool = False     # a norm on each sublayer's OUTPUT as well: x + norm(sublayer(norm(x))), four norms a block
    dropless: bool = False      # MoE FFN: routed SwiGLU, sort + grouped matmul | capacity GELU
    embed_std: float = 0.0      # token embedding init: normal(std) | flax's default (1/sqrt(d))
    embed_scale: bool = False   # the embedded token times sqrt(d)
    embed_multiplier: float = 0.0   # > 0: the embedded token times this (embedding_multiplier)
    attn_scale: float = 0.0     # > 0: the scores' scale (attention_multiplier) | a head's features ** -0.5
    residual_scale: float = 1.0     # each sublayer's output times this before the residual sum (residual_multiplier)
    logits_divisor: float = 1.0     # the head's logits over this (logits_scaling)
    z_loss_coef: float = 0.0    # router z-loss in the ep step's loss
    aux_coef: float = 0.01      # load-balance loss in the ep step's loss
    # Layers of several kinds: layer l is of kind l % len(pattern), for the
    # window, the position encoding and the token mixer alike.
    mixer_layers: Tuple[str, ...] = ()  # per layer of the period: the mixer, "attention" | "gdn" | "eva" | "mamba2"; (): attention in every layer
    layer_pattern: str = ""     # no period but a letter a layer, each layer ONE pre-norm residual sublayer (``PATTERN_KINDS``): "M" a Mamba-2 mixer | "*" an attention mixer | "E" an expert layer; the depth is at most its length
    window: int = 0             # keys a window layer's query sees, itself included
    window_layers: Tuple[int, ...] = ()   # per layer of the period: 1 = window | 0 = every key before (): no window
    rope_layers: Tuple[int, ...] = ()     # per layer of the period: 1 = RoPE | 0 = no position encoding; (): every layer
    # The dropless expert layer.
    gate_norm: bool = False     # top-k gates renormalised to sum to 1
    expert_act: str = "silu"    # the gate projection's activation: silu | relu | relu2 (relu squared)
    expert_gated: bool = True   # experts (and shared experts) down(act(gate x) * up x) | False: down(act(up x)), two matmuls
    early_router: bool = False  # the router reads the block's first norm (before attention), not the second
    expert_down_std: float = 0.0    # experts' down projection init: normal(std) | flax's lecun_normal
    router_score: str = "softmax"   # scores of the router's logits: softmax over the experts | sigmoid of each
    router_bias_rate: float = 0.0   # > 0: a bias [experts] is added to the scores for the top-k's CHOICE only (the gates
    #                                 are the scores), and the ep step moves it by this much a step against the load
    route_scale: float = 1.0        # the gates times this
    shared_experts: int = 0         # experts of the routed ones' width that every token passes, beside the routed part
    shared_width: int = 0           # > 0: ONE shared expert of this width, whatever the routed ones' is (shared_intermediate_size)
    shared_gate: bool = False       # the shared experts' output times sigmoid(y w_s), w_s: d -> 1
    load_all_stat: bool = False     # the expert layers report the busiest of ALL the router's outputs too (an arch that chooses under a bias reports it from the bias's counts)
    # A Gated DeltaNet (linear-attention) layer's sizes, the arch's as Mamba's are.
    gdn_key_heads: int = 0      # key (and query) heads
    gdn_value_heads: int = 0    # value heads, a multiple: value heads r j .. r j + r - 1 read key head j
    gdn_key_dim: int = 0        # a key head's features
    gdn_value_dim: int = 0      # a value head's features
    gdn_conv: int = 0           # taps of the causal depthwise convolution over q, k, v
    # An EVA layer's sizes (ops/eva_attention.py): exact causal attention inside
    # a window that is a BLOCK of the diagonal, one softmax shared with the
    # chunk summaries of every earlier window.
    eva_window: int = 0         # tokens a window
    eva_chunk: int = 0          # tokens a summary pools
    eva_std: float = 0.0        # the pooling's two learned vectors a head (adaptive_phi, adaptive_mu_k): normal(std) clipped to +-std
    pred_heads: int = 1         # prediction heads on the one trunk: head i at position t predicts token t + 1 + i; logits [B, S, pred_heads, V] where > 1
    f32_logits: bool = False    # the head's matmul hands out float32 (its operands stay in the compute dtype)
    # A decoder-hybrid-decoder stack: the layer's kind follows from its index
    # AND the depth (``layer_kind``), not from a period.
    hybrid: bool = False        # state-space / window layers, then a cross-decoder that reads one layer's scan output and one layer's K/V
    ssm_state: int = 0          # a Mamba layer's states a channel (d_state)
    ssm_conv: int = 0           # ... its causal depthwise convolution's taps (d_conv)
    ssm_expand: int = 0         # ... its channels over d_model (d_inner = expand * d; dt_rank = ceil(d / 16))
    # A Mamba-2 layer's sizes (with ssm_state and ssm_conv): d_inner = ssm_heads * ssm_head_dim, whatever d is.
    ssm_heads: int = 0          # heads, each with one decay and a [ssm_head_dim, ssm_state] state
    ssm_head_dim: int = 0
    ssm_groups: int = 0         # B and C are shared by the ssm_heads / ssm_groups heads of a group; the gated norm's groups too
    ssm_chunk: int = 0          # tokens a chunk of the state-space-dual form
    diff_attn: bool = False     # differential attention: heads pair up (2j, 2j+1), a pair's output is softmax(q1 k1) v - lambda softmax(q2 k2) v over its two value heads side by side (one value 2 hd wide: two attention calls a layer), then a norm
    gated_ffn: bool = False     # the dense block's feed-forward: GatedFFN (SwiGLU, no biases) | Dense-GELU-Dense with biases
    tied_head: bool = False     # logits = ln_f(x) . tok_embed^T: no lm_head parameter
    no_positions: bool = False  # no position table although rope_theta is 0: no position encoding anywhere

    def layer_kind(self, layer: int, n_layers: int = 0) -> str:
        """One of ``LAYER_KINDS``: by the period's ``mixer_layers`` ("attention"
        where it has none), or for a hybrid by the index and the depth (which
        only a hybrid's caller has to give)."""
        if self.layer_pattern:
            if layer >= len(self.layer_pattern):
                raise ValueError(
                    f"layer {layer}: the arch's pattern names "
                    f"{len(self.layer_pattern)} layers, the published depth")
            return PATTERN_KINDS[self.layer_pattern[layer]]
        if not self.hybrid:
            return self.mixer_layers[layer % len(self.mixer_layers)] \
                if self.mixer_layers else "attention"
        if not n_layers or n_layers % 4:
            raise ValueError(f"a hybrid stack's depth is a multiple of 4 "
                             f"(two kinds alternate in each half), not "
                             f"{n_layers}")
        half = n_layers // 2
        if layer < half:
            return "window" if layer % 2 else "mamba"
        if layer <= half + 1:
            return "full_hands_kv" if layer % 2 else "mamba_hands_memory"
        return "cross" if layer % 2 else "gmu"

    @property
    def counts(self) -> bool:
        """Do the dense LM's blocks return ``(x, out)``: what a layer hands
        on and counts (``HANDED``, ``COUNTER_NAMES``), beside the stream."""
        return self.hybrid or "eva" in self.mixer_layers

    def layer_window(self, layer: int, n_layers: int = 0) -> Optional[int]:
        if self.hybrid:
            return self.window \
                if self.layer_kind(layer, n_layers) == "window" else None
        if self.window_layers and \
                self.window_layers[layer % len(self.window_layers)]:
            return self.window
        return None

    def layer_rope(self, layer: int) -> bool:
        return bool(self.rope_theta) and (
            not self.rope_layers
            or bool(self.rope_layers[layer % len(self.rope_layers)]))


# ``Arch.layer_kind``'s values. By the arch's period (``mixer_layers``):
# "attention", softmax attention over every key before the query or a sliding
# window of them; "gdn", a Gated DeltaNet layer (models/gdn.py); "eva", an EVA
# layer (ops/eva_attention.py: the q/k/v/o path of ``attention_sublayer`` round
# another core). By the letter of a pattern (``PATTERN_KINDS``): "mamba2", a
# Mamba-2 mixer alone (models/ssm.py); "experts", an expert layer alone, no
# mixer; "attention" again. A hybrid stack of depth L (a multiple of 4):
# "mamba" and "window" (window-attention) layers alternate in the first half;
# layer L/2 is "mamba_hands_memory", a Mamba layer whose scan output goes to
# every gated memory unit, layer L/2 + 1 "full_hands_kv", a full causal
# attention layer whose K and V go to every cross layer; then "gmu" (gated
# memory units) and "cross" (cross-attention) layers alternate.
LAYER_KINDS = ("attention", "gdn", "eva", "mamba2", "experts", "mamba",
               "window", "mamba_hands_memory", "full_hands_kv", "gmu", "cross")
# ``Arch.layer_pattern``'s letters (the published ``hybrid_override_pattern``'s):
# such a layer is the mixer alone or the expert layer alone, "experts" no mixer.
PATTERN_KINDS = {"M": "mamba2", "*": "attention", "E": "experts"}
ATTENTION_KINDS = ("attention", "window", "full_hands_kv", "cross")
# What a hybrid block may hand on, and what it counts (max over layers):
HANDED = ("memory", "k", "v")
LM_COUNTERS = "lm_counters"     # the flax collection the counters are sown in
COUNTER_NAMES = ("ssm_state_abs_max", "diff_lambda_max", "gdn_state_abs_max",
                 "ssd_state_abs_max", "eva_pool_weight_max")

ARCHS = {
    "gpt2": Arch(),
    # OLMoE-1B-7B (arXiv:2409.02060; allenai/OLMoE-1B-7B-0125-Instruct
    # config.json): rms_norm_eps 1e-5, rope_theta 10000, q/k norm, top-k
    # gates not renormalised, z-loss 0.001 (the paper's).
    # embed_std 1: with flax's 1/sqrt(d) embeddings the residual stream of a
    # freshly initialised model is the running mean of attention, nearly the
    # same vector at every position, and the router sends 97% of tokens to the
    # same 8 experts (max over mean 7.5 of a possible 8 on the chip, PR 25).
    # At std 1 the stream carries the token's identity through the first
    # RMSNorm and a seeded model routes near balance, as a trained one does.
    "olmoe": Arch(rms_norm=True, norm_eps=1e-5, rope_theta=10000.0,
                  qk_norm=True, dropless=True, z_loss_coef=0.001,
                  embed_std=1.0),
    # SmallThinker-21BA3B-Instruct (PowerInfer/SmallThinker-21BA3B-Instruct
    # config.json): rms_norm_eps 1e-6, sliding_window_size 4096 with RoPE
    # (theta 1.5e6) on three layers of four, full causal attention without
    # position encoding on the fourth (sliding_window_layout = rope_layout =
    # 0 1 1 1), top-6 gates renormalised (norm_topk_prob), ReLU-gated
    # experts, the router on the pre-attention norm; no q/k norm, no z-loss.
    # embed_std as olmoe's, for the same reason. expert_down_std: HF's
    # initializer_range 0.02 over sqrt(2 x 52 layers), GPT-2's rule for a
    # projection into the residual stream at the published depth. Under
    # lecun_normal one expert's output is 70% of the stream's norm, a gate is
    # 1/6 of it (renormalised), and a near-tie between the 6th and 7th expert
    # (600 of 16384 tokens between a bfloat16 model and a float32 reference)
    # moves a logit by 0.8-1.0 of 6: the chip could not tell bfloat16 from
    # float8 by the logits (PERF.md, PR 29). At 0.002 an expert is 4% of the
    # stream, as in a trained model.
    "smallthinker": Arch(rms_norm=True, norm_eps=1e-6, rope_theta=1.5e6,
                         dropless=True, embed_std=1.0, window=4096,
                         window_layers=(0, 1, 1, 1), rope_layers=(0, 1, 1, 1),
                         gate_norm=True, expert_act="relu",
                         early_router=True, expert_down_std=0.002),
    # Trinity-Mini (arcee-ai/Trinity-Mini config.json, model_type afmoe):
    # rms_norm_eps 1e-5; sliding_window 2048 with RoPE (theta 10000) on three
    # layers of four, full causal attention without position encoding on the
    # fourth (layer_types); q/k norm over each head's 128 features;
    # attention's output gated by sigmoid(a Wg); a norm on each sublayer's
    # output too; the embedding times sqrt(d) (mup_enabled); leading dense
    # SwiGLU layers (--lm-dense-layers), then expert layers: sigmoid scores,
    # top-8 chosen by score + bias, gates the scores over their sum times
    # route_scale 2.826, one shared expert; no auxiliary loss: the balancing
    # is the bias, moved 0.001 a step (load_balance_coeff). embed_std: every
    # sublayer's output passes a norm, so it has unit RMS whatever its
    # weights are, and the embedding's scale alone says how much of the
    # stream, and so of the logits, the blocks are. At HF's initializer_range
    # 0.02 (RMS 0.9 after the multiplier) a freshly initialised attention
    # layer's output, nearly one vector at every position, is most of what
    # the router reads: the busiest of the 128 outputs draws 4 times the mean
    # at step 1 and 11 times by step 40, and one flip of the 8th and 9th
    # expert between bfloat16 and float32 moves a logit by over 1 (PR 31,
    # chip). At 0.5 (RMS 23; the ten sublayers are a seventh of the stream)
    # the busiest output draws 1.26 and falls, and the reference check keeps
    # a limit between the program and float8 parameters under which 13 of 16
    # planted mistakes in the block fail; at 0.25 there is no room for a
    # limit, at 1.0 ten fail: benchmark/configs/trinity_mini.json
    # reference_check.why, benchmark/controls/.
    "trinity": Arch(rms_norm=True, norm_eps=1e-5, rope_theta=10000.0,
                    head_qk_norm=True, attn_gate=True, post_norm=True,
                    dropless=True, embed_std=0.5, embed_scale=True,
                    aux_coef=0.0, window=2048, window_layers=(1, 1, 1, 0),
                    rope_layers=(1, 1, 1, 0), gate_norm=True,
                    router_score="sigmoid", router_bias_rate=0.001,
                    route_scale=2.826, shared_experts=1),
    # Phi-4-mini-flash-reasoning (microsoft/Phi-4-mini-flash-reasoning
    # config.json, model_type phi4flash; SambaY, arXiv:2507.06607, with
    # differential attention, arXiv:2410.05258): layer_norm_eps 1e-5 on
    # LayerNorms with scale and bias, sliding_window 512 on the first half's
    # attention layers, mb_per_layer 2 (every other layer a Mamba-1 mixer of
    # the family's defaults: d_state 16, d_conv 4, expand 2, dt_rank d / 16),
    # a SwiGLU feed-forward without biases in every layer, the head tied to
    # the embedding, no position encoding anywhere. embed_std: HF's
    # initializer_range; the embedding is the head too, so its scale is the
    # logits' (benchmark/configs/phi4_mini_flash.json reference_check.why).
    "phi4flash": Arch(norm_eps=1e-5, hybrid=True, window=512, ssm_state=16,
                      ssm_conv=4, ssm_expand=2, diff_attn=True,
                      gated_ffn=True, tied_head=True, no_positions=True,
                      embed_std=0.02),
    # Qwen3-Next-80B-A3B-Instruct (Qwen/Qwen3-Next-80B-A3B-Instruct
    # config.json, model_type qwen3_next): rms_norm_eps 1e-6 on zero-centred
    # RMSNorms (scale 1 + w); three Gated DeltaNet layers to one softmax
    # attention layer (full_attention_interval 4: layer i is attention where
    # (i + 1) % 4 == 0), the linear layers with 16 key and 32 value heads of
    # 128 and a 4-tap convolution; attention with q/k norm a head, its output
    # gated, RoPE (theta 1e7) on the first quarter of a head's features
    # (partial_rotary_factor 0.25); softmax scores, top-k gates renormalised
    # (norm_topk_prob), one shared expert under a sigmoid gate, load-balance
    # loss 0.001 (the family's router_aux_loss_coef), no z-loss. embed_std as
    # olmoe's, for the same reason (the zero-centred norm changes nothing in
    # it: a freshly initialised stream is still what the router reads).
    # expert_down_std: 0.02 over sqrt(2 x 48 layers), smallthinker's rule at
    # this depth, taken over on that row's reading (a near-tie between the
    # last chosen expert and the next is a flip between a bfloat16 model and
    # a float32 reference; lecun_normal was not read on this model). With it
    # the chip tells the program as run (0.07-0.09) from float8 parameters
    # (0.88): benchmark/configs/qwen3_next_80b_a3b.json reference_check.why.
    # The linear layers' initialisers are models/gdn.py's.
    "qwen3next": Arch(rms_norm=True, norm_eps=1e-6, zero_centred_norm=True,
                      rope_theta=1e7, rope_share=0.25, head_qk_norm=True,
                      attn_gate=True, dropless=True, embed_std=1.0,
                      aux_coef=0.001, gate_norm=True, expert_down_std=0.002,
                      shared_experts=1, shared_gate=True,
                      mixer_layers=("gdn", "gdn", "gdn", "attention"),
                      gdn_key_heads=16, gdn_value_heads=32, gdn_key_dim=128,
                      gdn_value_dim=128, gdn_conv=4),
    # NVIDIA-Nemotron-3-Nano-30B-A3B (nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-
    # BF16 config.json, model_type nemotron_h; Nemotron-H, arXiv:2504.03624;
    # Mamba-2, arXiv:2405.21060): 52 layers, each ONE pre-norm residual
    # sublayer by the letter of hybrid_override_pattern: 23 Mamba-2 mixers (64
    # heads of 64 with a [64, 128] state, B and C in 8 groups, a biased 4-tap
    # convolution, chunks of 128, the gate BEFORE a norm in 8 groups), 6
    # attention mixers (no position encoding anywhere: rope_theta is carried
    # and not read) and 23 expert layers: sigmoid scores, top-6 chosen by
    # score + bias (trinity's bias, moved 0.001 a step), gates the scores over
    # their sum (norm_topk_prob) times routed_scaling_factor 2.5, experts
    # down(relu(up x)^2) without a gate projection, one shared expert of the
    # same form and twice the width (moe_shared_expert_intermediate_size 3712
    # = 2 x 1856: shared_experts 2); no auxiliary loss; layer_norm_epsilon
    # 1e-5. embed_std as olmoe's and expert_down_std 0.02 / sqrt(2 x 52
    # layers) as smallthinker's, for their reasons
    # (benchmark/configs/nemotron3_nano_30b_a3b.json reference_check.why). The
    # Mamba-2 layers' initialisers are models/ssm.py's.
    "nemotronh": Arch(rms_norm=True, norm_eps=1e-5, no_positions=True,
                      dropless=True, embed_std=1.0, aux_coef=0.0,
                      gate_norm=True, expert_act="relu2", expert_gated=False,
                      expert_down_std=0.00196, router_score="sigmoid",
                      router_bias_rate=0.001, route_scale=2.5,
                      shared_experts=2,
                      layer_pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*"
                                    "EMEMEMEM*EMEMEMEME",
                      ssm_state=128, ssm_conv=4, ssm_heads=64,
                      ssm_head_dim=64, ssm_groups=8, ssm_chunk=128),
    # EvaByte (EvaByte/EvaByte config.json, model_type evabyte; EVA,
    # arXiv:2302.04542, in the reduced form of the model's public eva.py):
    # byte-level, 320 ids; rms_norm_eps 1e-5 on zero-centred RMSNorms
    # (norm_add_unit_offset: scale 1 + w); every layer an EVA layer
    # (attention_class eva: window_size 2048, chunk_size 16, RoPE theta 1e5
    # over the whole head) and a SwiGLU feed-forward without biases; an untied
    # head of num_pred_heads 8 x 320 outputs whose logits leave in float32
    # (fp32_logits). embed_std and eva_std: the published init_std. What
    # config.json has no key for (the form of phi and mu, the pooling's scale,
    # the block and not a band, the heads' equal weight in the loss) is
    # benchmark/configs/evabyte_6_5b.json's ``assumed``.
    "evabyte": Arch(rms_norm=True, norm_eps=1e-5, zero_centred_norm=True,
                    rope_theta=1e5, gated_ffn=True, embed_std=0.01275,
                    mixer_layers=("eva",), eva_window=2048, eva_chunk=16,
                    eva_std=0.01275, pred_heads=8, f32_logits=True),
    # Granite-4.0-H-Small (ibm-granite/granite-4.0-h-small config.json,
    # model_type granitemoehybrid; Mamba-2, arXiv:2405.21060): 40 layers, each
    # a mixer AND an expert half; layer i attends where i % 10 == 5 and is a
    # Mamba-2 mixer otherwise (layer_types: 36 to 4): 128 heads of 64 with a
    # [64, 128] state, B, C and the gated norm in ONE group over all heads, a
    # biased 4-tap convolution, chunks of 256; attention without position
    # encoding (position_embedding_type nope: rope_theta is carried and not
    # read) at the scores' scale attention_multiplier 1/128, not 128 ** -0.5;
    # 72 SwiGLU experts, the top 10 logits under a softmax of their own (the
    # softmax over all 72 renormalised over the ten: gate_norm) beside ONE
    # shared SwiGLU expert of shared_intermediate_size 1536; rms_norm_eps
    # 1e-5; the head tied to the embedding; four scalars: the embedding times
    # 12, each sublayer's output times 0.22 before the residual sum, the
    # logits over 16, the scores' scale. aux_coef: the family's default
    # router_aux_loss_coef. embed_std: the embedding is the head too, so its
    # scale is the logits' (phi4flash's reason) and, times 12, the share of
    # the stream that is the token itself: at 0.05 that part has RMS 0.6
    # beside twenty sublayer outputs of 0.22 each, and a token's own logit is
    # about 6 (benchmark/configs/granite_4_0_h_small.json assumed.embed_std).
    # expert_down_std 0.02 / sqrt(2 x 40 layers), smallthinker's rule at this
    # depth, for its reason. The Mamba-2 layers' initialisers are
    # models/ssm.py's. How many of the heads and of the shared expert's
    # channels a model holds is its ``mixer_shares`` (--lm-mixer-shares).
    "granite4h": Arch(rms_norm=True, norm_eps=1e-5, no_positions=True,
                      dropless=True, embed_std=0.05, tied_head=True,
                      aux_coef=0.001, gate_norm=True,
                      expert_down_std=0.00224, shared_width=1536,
                      load_all_stat=True, embed_multiplier=12.0,
                      attn_scale=0.0078125, residual_scale=0.22,
                      logits_divisor=16.0,
                      mixer_layers=("mamba2",) * 5 + ("attention",)
                      + ("mamba2",) * 4,
                      ssm_state=128, ssm_conv=4, ssm_heads=128,
                      ssm_head_dim=64, ssm_groups=1, ssm_chunk=256),
}


class ZeroCentredRMSNorm(nn.Module):
    """``x rsqrt(mean(x^2) + eps) (1 + w)``, ``w`` from 0, in float32 whatever
    ``dtype`` the result leaves in: weight decay pulls the scale to 1, not to
    0. The parameter is named ``scale`` as ``nn.RMSNorm``'s is."""
    epsilon: float = 1e-6
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        w = self.param("scale", nn.initializers.zeros, (x.shape[-1],))
        x = x.astype(jnp.float32)
        x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                              + self.epsilon)
        return (x * (1.0 + w)).astype(self.dtype)


def make_norm(arch: str, dtype, name: Optional[str] = None) -> nn.Module:
    """The block's normalisation, one definition for every use (input,
    post-attention, q/k, final). Unnamed, flax numbers it in its caller's
    scope, which is what keeps GPT-2's ``LayerNorm_0..1``."""
    a = ARCHS[arch]
    if a.zero_centred_norm:
        return ZeroCentredRMSNorm(epsilon=a.norm_eps, dtype=dtype, name=name)
    if a.rms_norm:
        return nn.RMSNorm(epsilon=a.norm_eps, dtype=dtype, name=name)
    return nn.LayerNorm(epsilon=a.norm_eps, dtype=dtype, name=name)


def rope(x, positions, theta: float):
    """Rotary position embedding, rotate-half pairing (feature i with
    i + hd/2, as HF's ``rotate_half``). x: [B, h, S, hd]; positions: [S]
    GLOBAL token positions (a decode step passes its cache offset)."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def rope_on_a_share(x, positions, theta: float, share: float):
    """``rope`` on the first ``share`` of a head's features
    (``partial_rotary_factor``), paired among themselves; the rest pass as
    they are."""
    rotated = int(x.shape[-1] * share)
    return jnp.concatenate([rope(x[..., :rotated], positions, theta),
                            x[..., rotated:]], axis=-1)


def diff_lambda_init(layer: int) -> float:
    """Differential attention's lambda_init for the layer of that index."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def _clipped_normal(std: float):
    """normal(std) clipped to +-std."""
    def init(key, shape, dtype=jnp.float32):
        return jnp.clip(jax.random.normal(key, shape, dtype) * std, -std, std)
    return init


def eva_core(mod: nn.Module, q, k, v, a: Arch, *, fused: bool):
    """An EVA layer's core on q, k, v [B, H, S, hd] as the scores take them
    (rotated): ``(o, the largest pooling weight)``. Two learned vectors a
    head in the block's scope, ``adaptive_phi`` (the pooling's query) and
    ``adaptive_mu_k`` (added to every pooled key). ``fused``: the Pallas
    kernels; else the plain form, float32 scores over ``[tokens |
    summaries]`` (what the kernels are held to, and what initialises the
    model). The summaries run under the device scope ``eva_pool``, the core
    under ``attn_core``; the fused op opens both itself, forward and
    backward."""
    # where the arch asks for it: the other archs' start-up does not pay for it
    from ps_pytorch_tpu.ops import eva_attention as eva

    shape = q.shape[1:2] + q.shape[3:]
    phi = mod.param("adaptive_phi", _clipped_normal(a.eva_std), shape)
    mu = mod.param("adaptive_mu_k", _clipped_normal(a.eva_std), shape)
    sizes = dict(window=a.eva_window, chunk=a.eva_chunk)
    if fused:
        return eva.eva_attention(q, k, v, phi, mu, **sizes)
    with device_scope("eva_pool"):
        ks, vs, alpha = eva.eva_pool_reference(k, v, phi, mu,
                                               chunk=a.eva_chunk)
    with device_scope("attn_core"):
        o = eva.eva_core_reference(q, k, v, ks, vs, **sizes)
    return o.astype(q.dtype), jax.lax.stop_gradient(jnp.max(alpha))


def attention_sublayer(mod: nn.Module, x, positions, *, arch: str,
                       n_heads: int, dtype, attention_impl: str,
                       axis_name: str = "data", decode: bool = False,
                       decode_cache_len: int = 0, layer: int = 0,
                       kv_heads: int = 0, head_dim: int = 0,
                       n_layers: int = 0, shared_kv=None,
                       mixer_axis: Optional[str] = None):
    """``x + Wo . attention(norm(x))``, ``norm(x)`` (an early router's input)
    and what the layer can hand on or count (``k``, ``v`` in heads, as the
    attention calls take them; ``diff_lambda_max``); with the arch's
    ``attn_gate`` the attention's
    output is gated by ``sigmoid(norm(x) Wg)`` before ``Wo``, with
    ``post_norm`` the sum is ``x + norm(Wo . ...)``. The one q/k/v/o path of
    both ``Block`` and ``models/moe.MoEBlock``, called from their
    ``@nn.compact`` body, so its sub-modules are numbered in the CALLER's
    scope (GPT-2: ``LayerNorm_0``, ``Dense_0..3``, the tree
    ``benchmark/reference/gpt2_medium.py`` reads). ``layer`` (with
    ``n_layers`` for a hybrid arch) picks the layer's kind where the arch
    mixes kinds (window or not, RoPE or not); ``kv_heads`` (0 = ``n_heads``)
    key/value heads of ``head_dim`` (0 = ``d / n_heads``) serve ``n_heads /
    kv_heads`` query heads each. ``shared_kv``: another layer's ``(k, v)``;
    the layer then has a query and an output projection only (``Dense_0..1``).
    The scores' scale is the arch's ``attn_scale`` where it gives one (``hd **
    -0.5`` otherwise), on every path alike; ``Wo``'s result is multiplied by
    the arch's ``residual_scale`` before the sum. A model that holds a share
    of the heads (``n_heads`` and ``kv_heads`` the counts HELD, q/k/v their
    columns and ``Wo`` their rows) gives its PART of the sublayer's output;
    with ``mixer_axis`` bound the parts are summed over that mesh axis, with
    none the part is this chip's own and what the absent heads would add is
    left out.

    With the arch's ``diff_attn`` heads pair up as (2j, 2j+1): query pair j
    reads key pair ``j // group`` and, as its one value, that pair's two
    value heads side by side (2 hd wide); the pair's output is ``softmax(q1
    k1) v - lambda softmax(q2 k2) v`` under an RMSNorm over the 2 hd features
    (one learned scale) times ``1 - lambda_init``, ``lambda = exp(lq1 . lk1)
    - exp(lq2 . lk2) + lambda_init`` from four learned vectors [hd] a layer.
    The attention functions take a value wider than the keys, so each softmax
    is formed once: two calls a layer, ``attend(q[i], k[i], v)`` over the
    pairs' heads ``i`` and the value [B, Hkv / 2, S, 2 hd], which is the ``v``
    projection's features as they lie. The one transposing copy into heads
    leaves a pair's heads apart, [2, B, pairs, S, hd], and ``q[i]``, ``k[i]``
    are that array's two blocks: nothing is sliced by stride or joined,
    forward or backward. The blocks are what is kept under remat and what
    ``k`` hands on (a pair of arrays, ``v`` beside it), so the cross layer's
    calls read another layer's K and V as they are."""
    a = ARCHS[arch]
    b, s, d = x.shape
    hd = head_dim or d // n_heads
    n_kv = kv_heads or n_heads
    window = a.layer_window(layer, n_layers)
    if positions is None:
        positions = jnp.arange(s)
    # Separate q/k/v projections (not one packed Dense(3d)): under
    # tensor parallelism each kernel's OUTPUT dim is sharded over
    # 'model', and with per-projection kernels a shard's slice is
    # head-aligned (d = heads*hd), so attention can stay shard-local; a
    # packed qkv kernel puts shard boundaries inside q/k/v
    # (parallel/tp.py layout table).
    with device_scope("attn_proj"):
        y = make_norm(arch, dtype)(x)
        q = nn.Dense(n_heads * hd, use_bias=False, dtype=dtype)(y)
        if shared_kv is None:
            k = nn.Dense(n_kv * hd, use_bias=False, dtype=dtype)(y)
            v = nn.Dense(n_kv * hd, use_bias=False, dtype=dtype)(y)
    with device_scope("attn_pos"):
        # a q/k norm's backward reads its input (``remat_block``)
        unnormed = lambda q, k: (kept(q, "attn_q_unnormed"),
                                 kept(k, "attn_k_unnormed"))
        if a.qk_norm:
            q, k = unnormed(q, k)
            q = make_norm(arch, dtype, name="q_norm")(q)
            k = make_norm(arch, dtype, name="k_norm")(k)
        to_heads = to_values = lambda t, w=hd: t.reshape(
            b, s, -1, w).transpose(0, 2, 1, 3)
        if a.diff_attn:
            # a pair's heads apart, [2, B, pairs, S, hd]; its value 2 hd wide
            to_heads = lambda t: t.reshape(b, s, -1, 2, hd).transpose(
                3, 0, 2, 1, 4)
            to_values = partial(to_values, w=2 * hd)
        q = to_heads(q)
        k, v = (to_heads(k), to_values(v)) if shared_kv is None else shared_kv
        if a.head_qk_norm:
            q, k = unnormed(q, k)
            q = make_norm(arch, dtype, name="q_norm")(q)
            k = make_norm(arch, dtype, name="k_norm")(k)
        if a.layer_rope(layer):
            turn = rope if a.rope_share == 1.0 else partial(
                rope_on_a_share, share=a.rope_share)
            q, k = (turn(t, positions, a.rope_theta) for t in (q, k))
        # as ``attend`` takes them (``remat_block``): a differential layer's
        # two calls each take a block of q and of k; a cross layer's K/V are
        # another block's output
        if a.diff_attn:
            q, k = tuple(q), tuple(k)
        q = kept(q, "attn_q")
        if shared_kv is None:
            k, v = kept(k, "attn_k"), kept(v, "attn_v")
    out = {"k": k, "v": v}

    scale = a.attn_scale or None    # None: a head's features ** -0.5
    if scale is not None and (decode or attention_impl == "ring"):
        raise ValueError(
            f"lm_arch={arch}: the decode cache and ring attention score at a "
            f"head's features ** -0.5, not at the arch's attn_scale={scale}")

    def attend(q, k, v):
        if decode:
            return cached_attention(mod, q, k, v, decode_cache_len,
                                    window=window)
        if attention_impl == "ring":
            return ring_attention(q, k, v, axis_name, causal=True,
                                  window=window)
        if attention_impl == "flash":
            # Fused blockwise kernel (ops/flash_attention.py): no [S, S]
            # materialization — the single-chip long-context path.
            return flash_attention(q, k, v, causal=True, window=window,
                                   scale=scale)
        return full_attention(q, k, v, causal=True, window=window,
                              scale=scale)

    eva = a.layer_kind(layer, n_layers) == "eva"
    if (eva or a.diff_attn) and (decode or attention_impl == "ring"):
        refuse_hybrid(arch, "decode" if decode else "ring attention")
    if eva:
        o, out["eva_pool_weight_max"] = eva_core(
            mod, q, k, v, a, fused=attention_impl == "flash")
    elif a.diff_attn:
        with device_scope("attn_core"):
            o, o2 = (attend(q[i], k[i], v) for i in (0, 1))
    else:
        with device_scope("attn_core"):
            o = attend(q, k, v)
    with device_scope("attn_pos"):
        if a.diff_attn:
            lq1, lk1, lq2, lk2 = (
                mod.param(name, nn.initializers.normal(0.1), (hd,))
                for name in ("lambda_q1", "lambda_k1", "lambda_q2",
                             "lambda_k2"))
            lam_init = diff_lambda_init(layer)
            lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) \
                + lam_init
            out["diff_lambda_max"] = jnp.abs(lam)
            o = o - lam.astype(dtype) * o2
            o = nn.RMSNorm(epsilon=a.norm_eps, dtype=dtype, name="subln")(o) \
                * jnp.asarray(1.0 - lam_init, dtype)
        o = o.transpose(0, 2, 1, 3).reshape(b, s, n_heads * hd)
    if a.attn_gate:
        with device_scope("attn_proj"):
            gate = kept(nn.Dense(n_heads * hd, use_bias=False, dtype=dtype,
                                 name="gate")(y), "attn_gate")
        with device_scope("attn_pos"):
            o = o * nn.sigmoid(gate)
    with device_scope("attn_proj"):
        # what the block's second half starts from, bar a norm and a sum
        o = kept(nn.Dense(d, use_bias=False, dtype=dtype)(o), "attn_out")
        if mixer_axis is not None:      # the shares' parts of the output
            o = jax.lax.psum(o, mixer_axis)
        if a.post_norm:
            o = make_norm(arch, dtype, name="post_attn_norm")(o)
        if a.residual_scale != 1.0:
            o = o * jnp.asarray(a.residual_scale, dtype)
        x = x + o
    return x, y, out


def refuse_head_kinds(model, where: str) -> None:
    """``parallel/tp.py`` and ``pp.py`` lay out and rebuild the block for
    equal head counts of ``d / heads`` and one causal mask, and for blocks
    of one kind of mixer (attention) that hand nothing on."""
    refuse_hybrid(getattr(model, "arch", "gpt2"), where)
    a = ARCHS[getattr(model, "arch", "gpt2")]
    kv = getattr(model, "kv_heads", 0) or model.n_heads
    if kv != model.n_heads or getattr(model, "head_dim", 0) \
            or a.window_layers:
        raise ValueError(
            f"{where} is not built for grouped-query heads, a head size "
            f"apart from d / heads, or window layers (kv_heads={kv} of "
            f"{model.n_heads}, head_dim={getattr(model, 'head_dim', 0)}, "
            f"lm_arch={getattr(model, 'arch', 'gpt2')}): train those under "
            f"lm_parallelism sp (one device) or ep")


# What an arch whose mixers carry a recurrent state lacks outside the one
# ``lm_parallelism`` that trains it, by the kind of state (``_state_kind``) and
# by where it is refused. A new kind of state adds a row.
_NO_SLOT = "a slot that holds {} beside the per-layer cache"
_STATE_LACKS = {
    # state-space layers and tensors handed from layer to layer
    "hybrid": {
        "trains under": "sp on one device",
        **dict.fromkeys(("generate.py", "serve.py"), _NO_SLOT.format(
            "each state-space layer's recurrent state and the handed-on K/V")),
        "tensor parallelism": "a layout over the model axis for the "
                              "state-space projections, the scan's channels "
                              "and the handed-on tensors",
        "pipeline parallelism": "the handed-on scan output and K/V carried "
                                "across stages, and their gradients back",
        "expert parallelism": "an expert block for the hybrid stack (it is a "
                              "dense model)",
        "ring attention": "a scan whose state crosses sequence shards, and "
                          "differential heads in the ring",
        "decode": "differential heads and recurrent state in the decode cache",
    },
    # linear-attention ("gdn") layers
    "gdn": {
        "trains under": "ep",
        **dict.fromkeys(("generate.py", "serve.py", "decode"), _NO_SLOT.format(
            "each linear-attention layer's matrix state and its "
            "convolution's last inputs")),
        "tensor parallelism": "a layout over the model axis for the linear-"
                              "attention layers' key and value heads, their "
                              "convolution's channels and their gates",
        "pipeline parallelism": "stages built from blocks of more than one "
                                "kind of mixer",
        "ring attention": "a delta rule whose state crosses sequence shards",
    },
    # Mamba-2 ("mamba2") layers in blocks of one sublayer
    "mamba2": {
        "trains under": "ep",
        **dict.fromkeys(("generate.py", "serve.py", "decode"), _NO_SLOT.format(
            "each Mamba-2 layer's head-wise state and its convolution's "
            "last inputs")),
        "tensor parallelism": "a layout over the model axis for the Mamba-2 "
                              "layers' heads, their groups' B and C and "
                              "their convolution's channels, and for blocks "
                              "that are a mixer or an expert layer alone",
        "pipeline parallelism": "stages built from blocks that are a mixer "
                                "or an expert layer alone, of three kinds",
        "ring attention": "a state-space-dual walk whose state crosses "
                          "sequence shards",
    },
    # Mamba-2 mixers by the period (``mixer_layers``), each block a mixer AND
    # an expert half, of which a model may hold a share (``mixer_shares``)
    "mamba2_mixers": {
        "trains under": "ep",
        **dict.fromkeys(("generate.py", "serve.py", "decode"), _NO_SLOT.format(
            "each Mamba-2 layer's head-wise state and its convolution's "
            "last inputs")),
        "tensor parallelism": "the exchange across the model axis: a share's "
                              "partial outputs and its gated norm's sum of "
                              "squares are reduced over a NAMED axis inside "
                              "the block (mixer_axis), which parallel/tp.py's "
                              "GSPMD layout neither binds nor places the "
                              "shares' parameters for",
        "pipeline parallelism": "stages built from blocks of more than one "
                                "kind of mixer, and a head tied to the first "
                                "stage's embedding",
        "ring attention": "a state-space-dual walk whose state crosses "
                          "sequence shards",
    },
    # EVA ("eva") layers: a window of keys plus a growing list of summaries
    "eva": {
        "trains under": "sp on one device",
        **dict.fromkeys(("generate.py", "serve.py", "decode"), _NO_SLOT.format(
            "one window's keys and values and the list of chunk summaries, "
            "which grows by one every chunk of tokens")),
        "tensor parallelism": "a layout over the model axis for the EVA "
                              "kernels' heads and their two learned vectors "
                              "a head",
        "pipeline parallelism": "stages that carry more than one prediction "
                                "head's targets to the last stage's loss",
        "expert parallelism": "an expert block round the EVA mixer (it is a "
                              "dense model)",
        "ring attention": "summaries of earlier windows that cross sequence "
                          "shards, and targets further than one token past "
                          "a shard's end",
    },
}


def _state_kind(a: Arch) -> Optional[str]:
    if a.hybrid:
        return "hybrid"
    for kind in ("gdn", "eva"):
        if kind in a.mixer_layers:
            return kind
    if "mamba2" in a.mixer_layers:
        return "mamba2_mixers"
    return "mamba2" if "M" in a.layer_pattern else None


def refuse_hybrid(arch: str, where: str) -> None:
    """Every entry point that cannot run an arch whose mixers carry a
    recurrent state refuses it by name here, saying what is missing."""
    lacks = _STATE_LACKS.get(_state_kind(ARCHS[arch]), {})
    if where in lacks:
        raise ValueError(
            f"lm_arch={arch} is not built for {where}: missing "
            f"{lacks[where]}; train it with train_lm.py under "
            f"lm_parallelism {lacks['trains under']}")


class EmbedRows(nn.Module):
    """``nn.Embed`` (same parameter ``embedding``, same default init) that
    gathers its rows from the float32 table and casts the rows: ``nn.Embed``
    of a narrower dtype casts the whole [V, d] table every step, and its
    gradient is scatter-added in that dtype."""
    num_embeddings: int
    features: int
    dtype: Any = jnp.float32
    embedding_init: Any = nn.linear.default_embed_init

    @nn.compact
    def __call__(self, ids):
        table = self.param("embedding", self.embedding_init,
                           (self.num_embeddings, self.features))
        return jnp.take(table, ids, axis=0).astype(self.dtype)


def embed_tokens(tokens, positions, *, arch: str, vocab_size: int,
                 d_model: int, max_seq_len: int, dtype):
    """Token embedding, plus the learned position table where the arch has
    one (RoPE archs carry position inside attention). Called from the LM
    classes' ``@nn.compact`` body."""
    a = ARCHS[arch]
    init = {"embedding_init": nn.initializers.normal(a.embed_std)} \
        if a.embed_std else {}
    with device_scope("embed"):
        x = EmbedRows(vocab_size, d_model, dtype=dtype, name="tok_embed",
                      **init)(tokens)
        if a.embed_scale:
            x = x * jnp.asarray(d_model ** 0.5, dtype)
        if a.embed_multiplier:
            x = x * jnp.asarray(a.embed_multiplier, dtype)
        if not a.rope_theta and not a.no_positions:
            x = x + EmbedRows(max_seq_len, d_model, dtype=dtype,
                              name="pos_embed")(positions)[None]
    return x


def cached_attention(mod: nn.Module, q, k, v, length: int,
                     window: Optional[int] = None):
    """Causal attention over a running k/v cache, shared by the dense
    Block and MoEBlock decode paths (the cache variables live in the
    CALLING module's "cache" collection).

    q/k/v: [B, h, S, hd] with ANY S >= 1 — S=1 is the per-token sampling
    step; S>1 is one-shot prefill (the whole prompt written to the cache
    in ONE forward pass, MXU-shaped, instead of S dispatch-bound scan
    steps). Queries at cache offset i..i+S-1 attend causally: query t sees
    cache slots <= i+t. Mirrors full_attention's numerics (scale, -inf
    mask, softmax) so decode logits match the training forward bit-for-bit
    up to reduction order (tests/test_generate.py pins the parity). Fewer
    key/value heads than query heads, or a ``window``, are refused: the cache
    row and its mask know neither."""
    if window is not None or k.shape[1] != q.shape[1]:
        raise ValueError(
            f"decode is not built for grouped-query heads or a window (kv "
            f"heads {k.shape[1]} of {q.shape[1]}, window {window}): the "
            f"cache holds one row a query head and every key before it")
    b, h, s, hd = q.shape
    ck = mod.variable("cache", "k", jnp.zeros, (b, h, length, hd), q.dtype)
    cv = mod.variable("cache", "v", jnp.zeros, (b, h, length, hd), q.dtype)
    idx = mod.variable("cache", "idx", lambda: jnp.zeros((), jnp.int32))
    i = idx.value
    ck.value = jax.lax.dynamic_update_slice(ck.value, k, (0, 0, i, 0))
    cv.value = jax.lax.dynamic_update_slice(cv.value, v, (0, 0, i, 0))
    idx.value = i + s
    scale = hd ** -0.5
    att = jnp.einsum("bhqd,bhkd->bhqk", q * scale, ck.value,
                     preferred_element_type=jnp.float32)
    q_pos = i + jnp.arange(s)                                   # [S]
    ok = jnp.arange(length)[None, :] <= q_pos[:, None]          # [S, length]
    att = jnp.where(ok[None, None], att, -jnp.inf)
    p = jax.nn.softmax(att, axis=-1)            # float32 for any q.dtype
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(cv.value.dtype), cv.value,
                      preferred_element_type=jnp.float32).astype(q.dtype)


ACTS = {"silu": nn.silu, "relu": nn.relu,
        "relu2": lambda x: jnp.square(nn.relu(x))}


class GatedFFN(nn.Module):
    """``(act(x Wgate) * (x Wup)) Wdown`` without biases: a hybrid arch's
    feed-forward, a dropless model's dense layer and its shared experts
    (SwiGLU under ``silu``). Not ``gated``: ``act(x Wup) Wdown``, no gate
    projection (an arch whose experts have none)."""
    d_hidden: int
    dtype: Any = jnp.float32
    act: str = "silu"
    gated: bool = True

    @nn.compact
    def __call__(self, x):
        dense = lambda n, name: nn.Dense(n, use_bias=False, dtype=self.dtype,
                                         name=name)
        act = ACTS[self.act]
        if self.gated:
            h = act(dense(self.d_hidden, "gate")(x)) \
                * dense(self.d_hidden, "up")(x)
        else:
            h = act(dense(self.d_hidden, "up")(x))
        return dense(x.shape[-1], "down")(h)


class Block(nn.Module):
    n_heads: int
    d_model: int
    dtype: Any = jnp.float32
    attention_impl: str = "full"      # "full" | "ring" | "flash"
    axis_name: str = "data"
    # Autoregressive decoding (models/generate.py): one token per call,
    # k/v appended to a fixed-length cache ("cache" collection) so each
    # step attends over the whole prefix without recomputing it. Static
    # cache length keeps the decode step a single compiled program under
    # lax.scan. Param tree is IDENTICAL to training (same six Dense calls
    # in the same order), so any checkpoint decodes as-is.
    decode: bool = False
    decode_cache_len: int = 0

    arch: str = "gpt2"                # ARCHS row (norm, positions, q/k norm)
    ffn_dim: int = 0                  # dense FFN width (0 = 4 * d_model)
    layer: int = 0                    # index in the stack: the layer's kind, where the arch mixes kinds
    kv_heads: int = 0                 # key/value heads (0 = n_heads)
    head_dim: int = 0                 # 0 = d_model / n_heads
    n_layers: int = 0                 # the stack's depth: a hybrid arch's kinds follow from it

    @nn.compact
    def __call__(self, x, positions=None, handed=None):
        # x: [B, S_local, D]; positions: [S_local] global token positions
        # (read by RoPE archs only; None = 0..S-1). A hybrid arch's block
        # takes what earlier layers handed on (``HANDED``: the scan output
        # for a gated memory unit, K and V for a cross layer) and returns
        # ``(x, out)``: what this layer hands on and counts (an arch of EVA
        # layers too: ``Arch.counts``).
        d = x.shape[-1]
        a = ARCHS[self.arch]
        kind = a.layer_kind(self.layer, self.n_layers)
        norm = lambda: make_norm(self.arch, self.dtype)
        if kind in ("mamba", "mamba_hands_memory"):
            x, out = mamba_sublayer(
                self, x, norm(), dtype=self.dtype, d_state=a.ssm_state,
                d_conv=a.ssm_conv, expand=a.ssm_expand)
        elif kind == "gmu":
            x, out = gmu_sublayer(x, handed["memory"], norm(),
                                  dtype=self.dtype), {}
        else:
            x, _, out = attention_sublayer(
                self, x, positions, arch=self.arch, n_heads=self.n_heads,
                dtype=self.dtype, attention_impl=self.attention_impl,
                axis_name=self.axis_name, decode=self.decode,
                decode_cache_len=self.decode_cache_len, layer=self.layer,
                kv_heads=self.kv_heads, head_dim=self.head_dim,
                n_layers=self.n_layers,
                shared_kv=(handed["k"], handed["v"]) if kind == "cross"
                else None)
        with device_scope("ffn"):
            y = norm()(x)
            if a.gated_ffn:
                x = x + GatedFFN(self.ffn_dim or 4 * d, self.dtype,
                                 name="mlp")(y)
            else:
                y = nn.Dense(self.ffn_dim or 4 * d, dtype=self.dtype)(y)
                y = nn.gelu(y)
                x = x + nn.Dense(d, dtype=self.dtype)(y)
        if not a.counts:
            return x
        hands = {"mamba_hands_memory": ("memory",),
                 "full_hands_kv": ("k", "v")}.get(kind, ())
        return x, {k: v for k, v in out.items()
                   if k in hands or k in COUNTER_NAMES}


class TransformerLM(nn.Module):
    vocab_size: int = 256
    n_layers: int = 2
    n_heads: int = 4
    d_model: int = 128
    max_seq_len: int = 2048
    dtype: Any = jnp.float32
    attention_impl: str = "full"
    axis_name: str = "data"
    # Per-BLOCK rematerialization: backward stores only block-boundary
    # activations and recomputes each block's interior, bar what
    # ``remat_block`` keeps by name. Checkpointing any
    # coarser (e.g. the whole loss) saves no peak memory — the recompute
    # holds all residuals at once anyway. Param tree is unchanged, so
    # remat can be toggled on an existing checkpoint.
    remat: bool = False
    # Autoregressive decode mode (see Block.decode): one token per call,
    # fixed-length k/v caches. Same param tree as training.
    decode: bool = False
    decode_cache_len: int = 0
    arch: str = "gpt2"                # ARCHS row; see Block
    ffn_dim: int = 0
    kv_heads: int = 0
    head_dim: int = 0

    @nn.compact
    def __call__(self, tokens, positions: Optional[jax.Array] = None,
                 train: bool = True):
        # tokens: [B, S_local] int32; positions: [S_local] global positions
        # (defaults to 0..S-1 — correct only when unsharded).
        if positions is None:
            positions = jnp.arange(tokens.shape[1])
        a = ARCHS[self.arch]
        x = embed_tokens(tokens, positions, arch=self.arch,
                         vocab_size=self.vocab_size, d_model=self.d_model,
                         max_seq_len=self.max_seq_len, dtype=self.dtype)
        Blk = remat_block(Block) if (self.remat and not self.decode) \
            else Block
        # A hybrid stack: two layers hand a tensor to every later layer that
        # reads it (autodiff sums the readers' gradients back into the one
        # that made it, through the blocks' remat), and layers count.
        handed, counted = {}, {}
        for i in range(self.n_layers):
            blk = Blk(self.n_heads, self.d_model, self.dtype,
                      self.attention_impl, self.axis_name,
                      decode=self.decode,
                      decode_cache_len=self.decode_cache_len,
                      arch=self.arch, ffn_dim=self.ffn_dim, layer=i,
                      kv_heads=self.kv_heads, head_dim=self.head_dim,
                      n_layers=self.n_layers, name=f"block_{i}")
            if not a.counts:
                x = blk(x, positions)
                continue
            x, out = blk(x, positions, handed)
            for k, v in out.items():
                if k in HANDED:
                    handed = {**handed, k: v}
                else:
                    counted.setdefault(k, []).append(v)
        # Logits in ``dtype``, like every other output of the model: the loss
        # that consumes them casts to float32 (parallel/{sp,tp,pp,ep}.py,
        # runtime/lm_eval.py), so under float32 nothing changes.
        with device_scope("head"):
            for k, vs in counted.items():  # a no-op unless the caller asks
                self.sow(LM_COUNTERS, k, jnp.max(jnp.stack(vs)))
            x = make_norm(self.arch, self.dtype, name="ln_f")(x)
            if a.tied_head:
                # the embedding's rows are the head's columns: one parameter,
                # which receives both gradients
                table = self.variables["params"]["tok_embed"]["embedding"]
                return jax.lax.dot_general(
                    x, table.astype(self.dtype), (((2,), (1,)), ((), ())))
            # several prediction heads share the trunk and the one matmul:
            # [B, S, pred_heads, V], head i's logits for token t + 1 + i
            wide = {"dot_general": partial(
                jax.lax.dot_general, preferred_element_type=jnp.float32)} \
                if a.f32_logits else {}
            logits = nn.Dense(self.vocab_size * a.pred_heads, use_bias=False,
                              dtype=self.dtype, name="lm_head", **wide)(x)
            return logits if a.pred_heads == 1 else logits.reshape(
                logits.shape[:2] + (a.pred_heads, self.vocab_size))


def lm_counters(collections) -> dict:
    """The counters ``TransformerLM`` sowed, from ``apply(...,
    mutable=[LM_COUNTERS])``'s second result: {name: scalar}."""
    return {k: v[0] for k, v in collections.get(LM_COUNTERS, {}).items()}
