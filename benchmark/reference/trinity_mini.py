"""Plain float32 reference of the Trinity-Mini (AFMoE) block stack, its loss,
the step that moves its router bias, its parameter count and its FLOPs.

Written from the published configuration (``arcee-ai/Trinity-Mini``
``config.json``, ``model_type`` ``afmoe``) and, for what that file has no key
for, from the family's published modelling code (``transformers``
``models/afmoe/modeling_afmoe.py``) and torchtitan's auxiliary-loss-free
balancing, whose ``MoEArgs`` the config's keys mirror; each such point is under
``assumed`` in ``configs/trinity_mini.json``. For layer ``l`` with input ``h``
(S x d), every norm an RMSNorm with a learned scale and ``rms_norm_eps``, no
biases on any projection:

    x0 = E[token] * sqrt(d)                               # mup_enabled
    a  = rms(h; w_in)
    q  = a Wq (heads x hd)   k = a Wk (kv x hd)   v = a Wv (kv x hd)   g = a Wg (heads x hd)
    q  = rms(q; w_q) per head,  k = rms(k; w_k) per head  # over the hd features of ONE head
    layer_types[l] == 'sliding_attention':  RoPE(rope_theta, rotate-half) on q and k, positions 0..S-1;
                                            query i sees key j  iff  0 <= i - j < sliding_window
    'full_attention':                       no position encoding at all;  iff  j <= i
    query head h reads key/value head h // (heads / kv);  scale 1/sqrt(hd)
    o  = softmax(q k^T + mask) v
    h  = h + rms((concat_heads(o) * sigmoid(g)) Wo; w_post_attn)     # the norm is on the sublayer's OUTPUT
    m  = rms(h; w_pre_mlp)
    l < num_dense_layers:  f = (silu(m Wgate) * (m Wup)) Wdown         # width intermediate_size
    else:   s = sigmoid(m Wr)                                          # all E outputs
            I = top-k of (s + b)                                       # b = expert_bias: chooses, does not weigh
            w_i = route_scale * s_i / (sum_{j in I} s_j + 1e-20)       # route_norm
            f = shared(m) + sum_{i in I, i held} w_i * expert_i(m)     # both SwiGLU, width moe_intermediate_size
    h  = h + rms(f; w_post_mlp)

then a final RMSNorm and an untied ``lm_head``. The configuration's layers are
the published layers ``0 .. num_hidden_layers - 1`` (``layer_types`` is carried
whole and read from its start); its first ``num_dense_layers`` are dense.

**A share of the experts.** ``num_experts`` counts the experts HELD
(``reduced``: one chip of the expert-parallel deployment the file states); the
router, its sigmoid, the bias, the top-k and the weights keep the published
width (``num_experts_published``), and what the absent experts would have added
is left out, of this reference as of the program; the shared expert is whole.
``experts_share`` says which contiguous block is held. A config without the
published key holds every expert.

**The bias's step** (``bias_step``): after each optimizer step, for every
expert layer, with ``c_e`` the step's assignments to router output ``e`` (all E,
over every device): ``delta = rate * sign(mean(c) - c_e)``, ``delta -=
mean(delta)``, ``b += delta`` (``load_balance_coeff`` is the rate). The training
loss is the mean next-token cross-entropy alone: the family's balancing is the
bias, there is no auxiliary loss.

Independent of ``ps_pytorch_tpu``: it takes the system's variables only as named
arrays and computes in float32 under ``highest`` matmul precision. K and V are
repeated per query head with ``jnp.repeat``; attention is a dense masked
softmax, one head and one block of queries at a time against every key; the
routed experts are a loop over the held ones on every token with a dense weight
(``w`` or 0): no sort, no grouped matmul, no kernel. Heads, query blocks and
experts run under ``jax.lax.map`` / ``jax.lax.scan`` (one block's scores or one
expert's activations alive at a time, so that S = 8192 fits beside a trainer's
state; unrolled in Python such loops took the chip's compiler minutes, PR 25);
the five layers are a Python loop, as in ``smallthinker_21b_a3b.py``. Names it
reads, per ``params/block_<i>``: ``RMSNorm_0`` (input), ``Dense_0..3`` (q, k, v,
o), ``gate``, ``q_norm``, ``k_norm``, ``post_attn_norm``, ``RMSNorm_1`` (before
the feed-forward), ``post_mlp_norm``, and ``mlp/gate|up|down`` (a dense layer)
or ``moe/router``, ``moe/experts_gate|up|down`` ([held, d, f], [held, d, f],
[held, f, d]) and ``shared/gate|up|down``; at the top ``tok_embed``, ``ln_f``,
``lm_head``; and ``moe_state/block_<i>/moe/expert_bias`` [E].
"""

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024      # queries scored at a time against every key
GATE_EPS = 1e-20


def _experts(config):
    """-> (router outputs E, experts held, index of the first held)."""
    held = config.get("experts_held", config["num_experts"])
    e = config.get("num_experts_published", config["num_experts"])
    return e, held, config.get("experts_share", 0) * held


def _windowed(config, layer):
    """Is the configuration's layer ``layer`` a window layer (else global)?"""
    kind = config["layer_types"][layer]
    assert kind in ("sliding_attention", "full_attention"), kind
    return kind == "sliding_attention"


def _rms(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * p["scale"]


def _rope(x, theta):
    """x: [S, hd], positions 0..S-1, rotate-half pairing."""
    s, hd = x.shape
    half = hd // 2
    inv_freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    rotated = jnp.concatenate([-x[:, half:], x[:, :half]], axis=-1)
    return x * cos + rotated * sin


def _swiglu(p, m):
    return (jax.nn.silu(m @ p["gate"]["kernel"]) * (m @ p["up"]["kernel"])) \
        @ p["down"]["kernel"]


def _attention(bp, h, config, layer):
    """The attention sublayer's contribution, before its output norm."""
    eps, theta = config["rms_norm_eps"], float(config["rope_theta"])
    heads, kv_heads, hd = (config["num_attention_heads"],
                           config["num_key_value_heads"], config["head_dim"])
    windowed = _windowed(config, layer)
    s, _ = h.shape
    a = _rms(h, bp["RMSNorm_0"], eps)
    by_head = lambda t, n: t.reshape(s, n, hd).transpose(1, 0, 2)
    q = _rms(by_head(a @ bp["Dense_0"]["kernel"], heads), bp["q_norm"], eps)
    k = _rms(by_head(a @ bp["Dense_1"]["kernel"], kv_heads), bp["k_norm"],
             eps)
    v = by_head(a @ bp["Dense_2"]["kernel"], kv_heads)
    k = jnp.repeat(k, heads // kv_heads, axis=0)          # head h <- h // group
    v = jnp.repeat(v, heads // kv_heads, axis=0)
    block = min(s, QUERY_BLOCK)
    assert s % block == 0, (s, block)
    key_pos = jnp.arange(s)

    def head(qkv):
        qh, kh, vh = qkv                                  # each [S, hd]
        if windowed:
            qh, kh = _rope(qh, theta), _rope(kh, theta)

        def queries(args):
            qb, first = args                              # [block, hd], its first position
            dist = (first + jnp.arange(block))[:, None] - key_pos[None, :]
            seen = dist >= 0
            if windowed:
                seen = seen & (dist < config["sliding_window"])
            att = qb @ kh.T * hd ** -0.5
            att = jax.nn.softmax(jnp.where(seen, att, -jnp.inf), axis=-1)
            return att @ vh

        out = jax.lax.map(queries, (qh.reshape(s // block, block, hd),
                                    jnp.arange(0, s, block)))
        return out.reshape(s, hd)

    o = jax.lax.map(head, (q, k, v))                      # [heads, S, hd]
    o = o.transpose(1, 0, 2).reshape(s, heads * hd)
    return (o * jax.nn.sigmoid(a @ bp["gate"]["kernel"])) \
        @ bp["Dense_3"]["kernel"]


def route(m, router, bias, config):
    """-> (scores [S, E], weights [S, E] with zeros off the top-k): sigmoid
    scores; the top-k of score + bias; the chosen scores over their sum,
    times ``route_scale``."""
    assert config["score_func"] == "sigmoid" and config["route_norm"]
    assert config["num_expert_groups"] == config["n_group"] == 1   # no group limit
    s = jax.nn.sigmoid(m @ router)
    biased = s + bias
    kth = jax.lax.top_k(biased, config["num_experts_per_tok"])[0][:, -1:]
    w = jnp.where(biased >= kth, s, 0.0)
    w = config["route_scale"] * w / (jnp.sum(w, axis=-1, keepdims=True)
                                     + GATE_EPS)
    return s, w


def _feed_forward(bp, bias, m, config, layer):
    """-> (f, the layer's weights [S, E] or None): the feed-forward
    sublayer's contribution on the normed stream ``m``, before its output
    norm."""
    if layer < config["num_dense_layers"]:
        return _swiglu(bp["mlp"], m), None
    moe = bp["moe"]
    _, w = route(m, moe["router"]["kernel"], bias, config)
    _, held, first = _experts(config)

    def expert(f, e):
        w_gate, w_up, w_down, w_e = e                     # one expert's, w_e [S]
        return f + w_e[:, None] * ((jax.nn.silu(m @ w_gate) * (m @ w_up))
                                   @ w_down), None

    assert config["num_shared_experts"] == 1
    f, _ = jax.lax.scan(expert, _swiglu(bp["shared"], m),
                        (moe["experts_gate"], moe["experts_up"],
                         moe["experts_down"], w[:, first:first + held].T))
    return f, w


def _layer(bp, bias, h, config, layer):
    """One block on one sequence h [S, d]; -> (h, weights [S, E] | None)."""
    eps = config["rms_norm_eps"]
    h = h + _rms(_attention(bp, h, config, layer), bp["post_attn_norm"], eps)
    f, w = _feed_forward(bp, bias, _rms(h, bp["RMSNorm_1"], eps), config,
                         layer)
    return h + _rms(f, bp["post_mlp_norm"], eps), w


def _forward(variables, tokens, config):
    """-> (logits [B, S, V], {block name: weights [B, S, E]} of the expert
    layers)."""
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    p, state = f32(variables["params"]), f32(variables.get("moe_state", {}))
    n = config["num_hidden_layers"]
    assert config["mup_enabled"] and not config["tie_word_embeddings"]
    with jax.default_matmul_precision("highest"):
        rows, routed = [], {}
        for b in range(tokens.shape[0]):
            h = p["tok_embed"]["embedding"][tokens[b]] \
                * config["hidden_size"] ** 0.5
            for i in range(n):
                name = f"block_{i}"
                bias = state[name]["moe"]["expert_bias"] \
                    if i >= config["num_dense_layers"] else None
                h, w = _layer(p[name], bias, h, config, i)
                if w is not None:
                    routed.setdefault(name, []).append(w)
            h = _rms(h, p["ln_f"], config["rms_norm_eps"])
            rows.append(h @ p["lm_head"]["kernel"])
        return jnp.stack(rows), {k: jnp.stack(v) for k, v in routed.items()}


def forward(variables, tokens, config):
    """variables: {"params", "moe_state"}; tokens: [B, S] int32; -> float32
    logits [B, S, vocab]."""
    return _forward(variables, tokens, config)[0]


def expert_counts(variables, tokens, config):
    """-> {block name: assignments to each of the E router outputs} over
    every token of ``tokens``."""
    return {k: jnp.sum(w > 0, axis=(0, 1))
            for k, w in _forward(variables, tokens, config)[1].items()}


def bias_step(bias, counts, config):
    """The bias [E] after one step that made ``counts`` [E] assignments."""
    counts = counts.astype(jnp.float32)
    delta = config["load_balance_coeff"] * jnp.sign(jnp.mean(counts) - counts)
    return bias + delta - jnp.mean(delta)


def loss(variables, tokens, config):
    """The mean next-token cross-entropy; nothing else is in the loss."""
    logits = forward(variables, tokens, config)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))


def _layer_params(config):
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    e, held, _ = _experts(config)
    attention = 3 * d * q + 2 * d * kv          # q, gate, o; k, v
    norms = 4 * d + 2 * config["head_dim"]
    return {"attention": attention, "norms": norms,
            "dense": 3 * d * config["intermediate_size"],
            "shared": config["num_shared_experts"] * 3 * d * f,
            "router": d * e, "experts": held * 3 * d * f}


def param_count(config, **_):
    """Parameters held (the bias is state, not a parameter): per layer q, k,
    v, o and the gate, four norm vectors and the two head norms; a dense
    layer's feed-forward, or the router over all E outputs, the shared expert
    and the held experts; embedding, head, final norm."""
    lp = _layer_params(config)
    n, n_dense = config["num_hidden_layers"], config["num_dense_layers"]
    d = config["hidden_size"]
    return 2 * config["vocab_size"] * d + d \
        + n * (lp["attention"] + lp["norms"]) + n_dense * lp["dense"] \
        + (n - n_dense) * (lp["router"] + lp["shared"] + lp["experts"])


def keys_per_query(seq_len, window=None):
    """Mean number of keys a query sees at ``seq_len``: (S + 1) / 2 under the
    causal mask, the band's mean under a window of ``window`` keys."""
    w = seq_len if window is None else min(window, seq_len)
    return (w * (w + 1) / 2 + (seq_len - w) * w) / seq_len


def macs_per_token(config, seq_len):
    """Required forward multiply-adds for one token, by part: the q, k, v, o
    and gate projections; attention by the keys each layer's mask admits (two
    products of heads x head_dim a key); the dense layers' feed-forward and the
    shared expert whole; the router over all E outputs; the routed experts at
    balance over the share held (k x held / E experts a token, three d x f
    matmuls each); the head."""
    lp = _layer_params(config)
    n, n_dense = config["num_hidden_layers"], config["num_dense_layers"]
    e, held, _ = _experts(config)
    q = config["num_attention_heads"] * config["head_dim"]
    keys = sum(keys_per_query(
        seq_len, config["sliding_window"] if _windowed(config, i) else None)
        for i in range(n))
    return {"projections": n * lp["attention"],
            "attention": 2 * q * keys,
            "dense": n_dense * lp["dense"],
            "shared": (n - n_dense) * lp["shared"],
            "router": (n - n_dense) * lp["router"],
            "experts": (n - n_dense) * config["num_experts_per_tok"] / e
            * lp["experts"],
            "head": config["hidden_size"] * config["vocab_size"]}


def train_flops_per_sample(config, seq_len, **_):
    """Required forward+backward FLOPs for one token at sequence length
    ``seq_len``: ``macs_per_token`` times 2 FLOPs, times 3 for forward plus
    both gradients. Attention is charged by the pairs its mask admits.
    Embedding look-ups, norms, RoPE, softmax, sigmoid, top-k, the sort, the
    bias's step and the optimizer are not counted; recomputation (``--remat``)
    never is."""
    return 3 * 2 * sum(macs_per_token(config, seq_len).values())
