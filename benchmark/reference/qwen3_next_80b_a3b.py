"""Plain float32 reference of the Qwen3-Next block stack: Gated DeltaNet
linear-attention layers by their recurrence, gated softmax attention with a
rotated quarter, and top-k experts beside a gated shared expert; its loss, its
parameter count and its FLOPs.

Written from the published configuration (``Qwen/Qwen3-Next-80B-A3B-Instruct``
``config.json``, ``model_type`` ``qwen3_next``) and, for what that file has no
key for, from the family's published modelling code (``transformers``
``models/qwen3_next/modeling_qwen3_next.py``) and the Gated Delta Networks
paper (arXiv:2412.06464); each such point is under ``assumed`` in
``configs/qwen3_next_80b_a3b.json``. Every norm ``N`` is the zero-centred
RMSNorm ``x rsqrt(mean(x^2) + eps) (1 + w)``; no biases anywhere. Layer ``l``
with input ``h`` (S x d) is softmax attention where ``(l + 1) %
full_attention_interval == 0`` and linear attention otherwise:

    a = N(h; w_in)
    linear:   [q | k | v | z] = a Wqkvz        (Hk dk, Hk dk, Hv dv, Hv dv)     [b | alpha] = a Wba  (Hv, Hv)
              [q | k | v] = silu(conv([q | k | v]))    depthwise, causal, linear_conv_kernel_dim taps, no bias
              beta = sigmoid(b)     g = -exp(A_log) softplus(alpha + dt_bias)       a value head
              q = q / sqrt(sum q^2 + 1e-6) / sqrt(dk)    k = k / sqrt(sum k^2 + 1e-6)    a head
              value head i reads key head i // (Hv / Hk); from S = 0 [dk, dv], TOKEN BY TOKEN:
                  S = exp(g_t) S;   S = S + k_t (beta_t (v_t - S^T k_t))^T;   o_t = S^T q_t
              o = rms(o; w_o, a head) * silu(z)          a plain scale [dv], the norm first
              h = h + concat_heads(o) Wout
    softmax:  q = a Wq (heads x hd)   k = a Wk (kv x hd)   v = a Wv (kv x hd)   gate = a Wg (heads x hd)
              q = N(q; w_q) a head,  k = N(k; w_k) a head
              RoPE(rope_theta, rotate-half) on the FIRST partial_rotary_factor hd features of q and k
              query head i reads key/value head i // (heads / kv);  causal;  scale 1/sqrt(hd)
              h = h + (concat_heads(softmax(q k^T + mask) v) * sigmoid(gate)) Wo
    m = N(h; w_mlp)
    p = softmax(m Wr) over all E outputs;  I = top-k of p;  w_i = p_i / sum_{j in I} p_j
    f = sigmoid(m w_s) shared(m) + sum_{i in I, i held} w_i expert_i(m)         both SwiGLU
    h = h + f

then a final ``N`` and an untied ``lm_head``. The linear layers are the
recurrence itself, one token at a time under ``lax.scan``: the system's chunked
form (``ps_pytorch_tpu/ops/gated_delta_rule.py``) is held to something that is
not a chunked form.

**A share of the experts.** ``num_experts`` counts the experts HELD
(``reduced``: one chip of the expert-parallel deployment the file states); the
router, its softmax, the top-k and the renormalised weights keep the published
width (``num_experts_published``), and what the absent experts would have added
is left out, of this reference as of the program; the shared expert is whole.
``experts_share`` says which contiguous block is held. A config without the
published key holds every expert.

Independent of ``ps_pytorch_tpu``: it takes the system's variables only as
named arrays and computes in float32 under ``highest`` matmul precision. K and
V are repeated per query head with ``jnp.repeat``; attention is a dense masked
softmax, one head and one block of queries at a time against every key; the
routed experts are a loop over the held ones on every token with a dense
weight (``w`` or 0): no sort, no grouped matmul, no kernel. The functions a mistake can be planted
in are module attributes (``controls/qwen3_next_80b_a3b.py`` replaces them by
name): ``norm``, ``causal_conv``, ``beta_of``, ``gate_of``, ``unit_key``,
``query_scale``, ``key_head_of``, ``rotated_features``, ``attention_gate``,
``shared_gate``, ``renormalised``, and ``STATE_BITS`` (the mantissa bits the
delta rule's state keeps across a boundary every ``STATE_ROUND_TOKENS``
tokens). Names it reads, per
``params/block_<i>``: ``ZeroCentredRMSNorm_0`` (before the mixer),
``ZeroCentredRMSNorm_1`` (before the experts); a linear layer's
``in_proj_qkvz``, ``in_proj_ba``, ``conv_weight`` [taps, channels], ``A_log``,
``dt_bias``, ``gdn_norm``, ``out_proj``; an attention layer's ``Dense_0..3``
(q, k, v, o), ``gate``, ``q_norm``, ``k_norm``; ``moe/router``,
``moe/experts_gate|up|down`` ([held, d, f], [held, d, f], [held, f, d]),
``shared/gate|up|down``, ``shared_gate``; at the top ``tok_embed``, ``ln_f``,
``lm_head``.
"""

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024      # queries scored at a time against every key
L2_EPS = 1e-6
STATE_BITS = 23         # mantissa bits the delta rule's state keeps: float32's
STATE_ROUND_TOKENS = 64     # ... rounded to them every this many tokens, where fewer


def _experts(config):
    """-> (router outputs E, experts held, index of the first held)."""
    held = config.get("experts_held", config["num_experts"])
    e = config.get("num_experts_published", config["num_experts"])
    return e, held, config.get("experts_share", 0) * held


def is_linear(config, layer):
    """Is the configuration's layer ``layer`` a linear-attention layer?"""
    return (layer + 1) % config["full_attention_interval"] != 0


def norm(x, p, eps):
    """The zero-centred RMSNorm: the learned vector is the scale's offset
    from 1."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + p["scale"])


def _rope(x, theta, rotated):
    """x: [S, hd], positions 0..S-1; the first ``rotated`` features rotate,
    feature i paired with i + rotated / 2; the rest pass."""
    s = x.shape[0]
    half = rotated // 2
    inv_freq = 1.0 / theta ** (jnp.arange(0, rotated, 2, dtype=jnp.float32)
                               / rotated)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    r = x[:, :rotated]
    turned = jnp.concatenate([-r[:, half:], r[:, :half]], axis=-1)
    return jnp.concatenate([r * cos + turned * sin, x[:, rotated:]], axis=-1)


def _swiglu(p, m):
    return (jax.nn.silu(m @ p["gate"]["kernel"]) * (m @ p["up"]["kernel"])) \
        @ p["down"]["kernel"]


def causal_conv(u, weight):
    """out[t] = sum_j weight[j] u[t - (taps - 1) + j], zeros before the
    sequence. u: [S, channels]; weight: [taps, channels]."""
    taps, s = weight.shape[0], u.shape[0]
    padded = jnp.pad(u, ((taps - 1, 0), (0, 0)))
    return sum(weight[j] * padded[j:j + s] for j in range(taps))


def beta_of(b):
    return jax.nn.sigmoid(b)


def gate_of(a_log, alpha, dt_bias):
    """g <= 0, a value head: the state's log decay a token."""
    return -jnp.exp(a_log) * jax.nn.softplus(alpha + dt_bias)


def unit_key(k):
    return k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + L2_EPS)


def query_scale(dk):
    return dk ** -0.5


def key_head_of(t, r):
    """q or k [S, Hk, dk] for every value head: head i reads key head i // r."""
    return jnp.repeat(t, r, axis=1)


def delta_rule(q, k, v, g, beta):
    """The gated delta rule token by token. q, k: [S, H, dk]; v: [S, H, dv];
    g, beta: [S, H] (one head of q and k for every value head: the caller
    repeats). -> o [S, H, dv]."""
    def token(state, x):
        q_t, k_t, v_t, g_t, b_t, t = x
        state = state * jnp.exp(g_t)[:, None, None]
        delta = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", state, k_t))
        state = state + k_t[:, :, None] * delta[:, None, :]
        out = jnp.einsum("hkv,hk->hv", state, q_t)
        if STATE_BITS < 23:
            state = jnp.where(
                (t + 1) % STATE_ROUND_TOKENS == 0,
                jax.lax.reduce_precision(state, exponent_bits=8,
                                         mantissa_bits=STATE_BITS), state)
        return state, out

    state = jnp.zeros((v.shape[1], k.shape[2], v.shape[2]), jnp.float32)
    return jax.lax.scan(token, state,
                        (q, k, v, g, beta, jnp.arange(q.shape[0])))[1]


def _linear_attention(bp, a, config):
    """The Gated DeltaNet mixer's contribution on the normed stream ``a``."""
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    s = a.shape[0]
    qkvz = a @ bp["in_proj_qkvz"]["kernel"]
    qkv, z = qkvz[:, :2 * hk * dk + hv * dv], qkvz[:, 2 * hk * dk + hv * dv:]
    assert bp["conv_weight"].shape[0] == config["linear_conv_kernel_dim"]
    qkv = jax.nn.silu(causal_conv(qkv, bp["conv_weight"]))
    q = qkv[:, :hk * dk].reshape(s, hk, dk)
    k = qkv[:, hk * dk:2 * hk * dk].reshape(s, hk, dk)
    v = qkv[:, 2 * hk * dk:].reshape(s, hv, dv)
    ba = a @ bp["in_proj_ba"]["kernel"]
    beta = beta_of(ba[:, :hv])
    g = gate_of(bp["A_log"], ba[:, hv:], bp["dt_bias"])
    q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + L2_EPS) \
        * query_scale(dk)
    q, k = (key_head_of(t, hv // hk) for t in (q, unit_key(k)))
    o = delta_rule(q, k, v, g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + config["rms_norm_eps"]) * bp["gdn_norm"]["scale"]
    o = o * jax.nn.silu(z.reshape(s, hv, dv))
    return o.reshape(s, hv * dv) @ bp["out_proj"]["kernel"]


def rotated_features(config):
    """How many of a head's features RoPE rotates: the first ones."""
    return int(config["head_dim"] * config["partial_rotary_factor"])


def attention_gate(a, bp):
    return jax.nn.sigmoid(a @ bp["gate"]["kernel"])


def _attention(bp, a, config):
    """The gated softmax attention's contribution on the normed stream."""
    eps, theta = config["rms_norm_eps"], float(config["rope_theta"])
    heads, kv_heads, hd = (config["num_attention_heads"],
                           config["num_key_value_heads"], config["head_dim"])
    rotated = rotated_features(config)
    s = a.shape[0]
    by_head = lambda t, n: t.reshape(s, n, hd).transpose(1, 0, 2)
    q = norm(by_head(a @ bp["Dense_0"]["kernel"], heads), bp["q_norm"], eps)
    k = norm(by_head(a @ bp["Dense_1"]["kernel"], kv_heads), bp["k_norm"],
              eps)
    v = by_head(a @ bp["Dense_2"]["kernel"], kv_heads)
    k = jnp.repeat(k, heads // kv_heads, axis=0)          # head i <- i // group
    v = jnp.repeat(v, heads // kv_heads, axis=0)
    block = min(s, QUERY_BLOCK)
    assert s % block == 0, (s, block)
    key_pos = jnp.arange(s)

    def head(qkv):
        qh, kh, vh = qkv                                  # each [S, hd]
        qh, kh = _rope(qh, theta, rotated), _rope(kh, theta, rotated)

        def queries(args):
            qb, first = args                              # [block, hd], its first position
            seen = (first + jnp.arange(block))[:, None] >= key_pos[None, :]
            att = qb @ kh.T * hd ** -0.5
            att = jax.nn.softmax(jnp.where(seen, att, -jnp.inf), axis=-1)
            return att @ vh

        out = jax.lax.map(queries, (qh.reshape(s // block, block, hd),
                                    jnp.arange(0, s, block)))
        return out.reshape(s, hd)

    o = jax.lax.map(head, (q, k, v))                      # [heads, S, hd]
    o = o.transpose(1, 0, 2).reshape(s, heads * hd)
    return (o * attention_gate(a, bp)) @ bp["Dense_3"]["kernel"]


def renormalised(w):
    """The chosen scores over their sum (norm_topk_prob)."""
    return w / jnp.sum(w, axis=-1, keepdims=True)


def shared_gate(m, bp):
    return jax.nn.sigmoid(m @ bp["shared_gate"]["kernel"])


def route(m, router, config):
    """-> weights [S, E], zeros off the top-k: softmax over all E outputs,
    the top-k, the chosen ones over their sum."""
    assert config["norm_topk_prob"]
    p = jax.nn.softmax(m @ router, axis=-1)
    kth = jax.lax.top_k(p, config["num_experts_per_tok"])[0][:, -1:]
    return renormalised(jnp.where(p >= kth, p, 0.0))


def expert_layer(bp, m, config):
    """-> (the expert layer's contribution on the normed stream ``m``: the
    held experts' part and the gated shared expert; [2, E]: the assignments
    to every router output, all k choices, and the sum of the router's
    probabilities over the tokens: what ``balance`` is made of)."""
    moe = bp["moe"]
    w = route(m, moe["router"]["kernel"], config)
    _, held, first = _experts(config)

    def expert(f, x):
        w_gate, w_up, w_down, w_e = x                     # one expert's, w_e [S]
        return f + w_e[:, None] * ((jax.nn.silu(m @ w_gate) * (m @ w_up))
                                   @ w_down), None

    shared = shared_gate(m, bp) * _swiglu(bp["shared"], m)
    f = jax.lax.scan(expert, shared,
                     (moe["experts_gate"], moe["experts_up"],
                      moe["experts_down"], w[:, first:first + held].T))[0]
    probs = jax.nn.softmax(m @ moe["router"]["kernel"], axis=-1)
    return f, jnp.stack([jnp.sum(w > 0, axis=0).astype(jnp.float32),
                         jnp.sum(probs, axis=0)])


def balance(sums, tokens):
    """A layer's load-balance term over ``tokens`` tokens (every sequence of
    the batch together): E sum_e f_e P_e, f_e the assignments to output e a
    token (sum_e f_e = k), P_e the mean router probability: HF's
    ``load_balancing_loss_func``. ``sums``: ``expert_layer``'s, summed."""
    return sums.shape[-1] * jnp.sum(sums[0] * sums[1]) / tokens ** 2


def _layer(bp, h, config, layer):
    """One block on one sequence h [S, d]; -> (h, ``expert_layer``'s sums)."""
    eps = config["rms_norm_eps"]
    a = norm(h, bp["ZeroCentredRMSNorm_0"], eps)
    h = h + (_linear_attention(bp, a, config) if is_linear(config, layer)
             else _attention(bp, a, config))
    f, sums = expert_layer(bp, norm(h, bp["ZeroCentredRMSNorm_1"], eps),
                           config)
    return h + f, sums


def _forward(variables, tokens, config):
    """-> (logits [B, S, V], the load-balance term: the layers' mean)."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), variables["params"])
    assert not config["tie_word_embeddings"]
    with jax.default_matmul_precision("highest"):
        rows, sums = [], 0.0
        for b in range(tokens.shape[0]):
            h = p["tok_embed"]["embedding"][tokens[b]]
            per_layer = []
            for i in range(config["num_hidden_layers"]):
                h, layer_sums = _layer(p[f"block_{i}"], h, config, i)
                per_layer.append(layer_sums)
            sums = sums + jnp.stack(per_layer)          # [layers, 2, E]
            h = norm(h, p["ln_f"], config["rms_norm_eps"])
            rows.append(h @ p["lm_head"]["kernel"])
        return jnp.stack(rows), jnp.mean(
            jax.vmap(lambda x: balance(x, tokens.size))(sums))


def forward(variables, tokens, config):
    """variables: {"params"}; tokens: [B, S] int32; -> float32 logits [B, S,
    vocab]."""
    return _forward(variables, tokens, config)[0]


def loss(variables, tokens, config):
    """The mean next-token cross-entropy plus ``router_aux_loss_coef`` times
    the load-balance term: what the program trains with."""
    logits, term = _forward(variables, tokens, config)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))
    return ce + config["router_aux_loss_coef"] * term


def _layer_params(config):
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    kw = config["linear_num_key_heads"] * config["linear_key_head_dim"]
    vw = config["linear_num_value_heads"] * config["linear_value_head_dim"]
    hv = config["linear_num_value_heads"]
    e, held, _ = _experts(config)
    return {"attention": 3 * d * q + 2 * d * kv,        # q, gate, o; k, v
            "attention_norms": 2 * config["head_dim"],
            "linear": d * (2 * kw + 2 * vw) + d * 2 * hv + vw * d,
            "linear_conv": config["linear_conv_kernel_dim"] * (2 * kw + vw),
            "linear_vectors": 2 * hv + config["linear_value_head_dim"],
            "norms": 2 * d,
            "shared": 3 * d * config["shared_expert_intermediate_size"],
            "shared_gate": d,
            "router": d * e, "experts": held * 3 * d * f}


def layer_counts(config):
    """-> (linear-attention layers, softmax-attention layers)."""
    n = config["num_hidden_layers"]
    linear = sum(is_linear(config, i) for i in range(n))
    return linear, n - linear


def param_count(config, **_):
    """Parameters held: a linear layer's two projections in, its convolution,
    A_log, dt_bias, the output norm's scale and the projection out; an
    attention layer's q, k, v, o and gate and its two head norms; every
    layer's two norms, router over all E outputs, shared expert with its gate
    and held experts; embedding, head, final norm."""
    lp = _layer_params(config)
    linear, full = layer_counts(config)
    d = config["hidden_size"]
    return 2 * config["vocab_size"] * d + d \
        + linear * (lp["linear"] + lp["linear_conv"] + lp["linear_vectors"]) \
        + full * (lp["attention"] + lp["attention_norms"]) \
        + (linear + full) * (lp["norms"] + lp["router"] + lp["shared"]
                             + lp["shared_gate"] + lp["experts"])


def recurrence_macs_per_token(config):
    """The delta rule's work a token and linear layer BY THE RECURRENCE,
    whatever chunk size or kernel computes it: for each of the dk x dv state
    elements of each value head the decay's multiply (half a multiply-add)
    and three multiply-adds (S^T k, the rank-one update, S^T q)."""
    return 3.5 * config["linear_num_value_heads"] \
        * config["linear_key_head_dim"] * config["linear_value_head_dim"]


def macs_per_token(config, seq_len):
    """Required forward multiply-adds for one token, by part: the linear
    layers' projections and convolution, and their recurrence; the attention
    layers' q, k, v, o and gate projections, and attention by the keys the
    causal mask admits (two products of heads x head_dim a key); the shared
    expert whole with its gate; the router over all E outputs; the routed
    experts at balance over the share held (k x held / E experts a token,
    three d x f matmuls each); the head."""
    lp = _layer_params(config)
    linear, full = layer_counts(config)
    e, held, _ = _experts(config)
    q = config["num_attention_heads"] * config["head_dim"]
    return {"linear_projections": linear * (lp["linear"] + lp["linear_conv"]),
            "linear_recurrence": linear * recurrence_macs_per_token(config),
            "projections": full * lp["attention"],
            "attention": full * 2 * q * (seq_len + 1) / 2,
            "shared": (linear + full) * (lp["shared"] + lp["shared_gate"]),
            "router": (linear + full) * lp["router"],
            "experts": (linear + full) * config["num_experts_per_tok"] / e
            * lp["experts"],
            "head": config["hidden_size"] * config["vocab_size"]}


def train_flops_per_sample(config, seq_len, **_):
    """Required forward+backward FLOPs for one token at sequence length
    ``seq_len``: ``macs_per_token`` times 2 FLOPs, times 3 for forward plus
    both gradients. Embedding look-ups, norms, RoPE, softmax, sigmoid, top-k,
    the sort and the optimizer are not counted; recomputation (``--remat``)
    never is, and neither is what a chunked form of the delta rule computes
    beyond the recurrence."""
    return 3 * 2 * sum(macs_per_token(config, seq_len).values())
