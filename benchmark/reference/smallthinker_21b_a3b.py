"""Plain float32 reference of the SmallThinker-21BA3B-Instruct block stack, its
loss, its parameter count and its FLOPs.

Written from the published configuration
(``PowerInfer/SmallThinker-21BA3B-Instruct`` ``config.json``) and the family's
description in the model-configs catalog (window layers with RoPE and global
layers without position encoding, 64 ReLU-gated experts top-6, the router placed
before attention); what neither states is under ``assumed`` in
``configs/smallthinker_21b_a3b.json``. For layer ``l`` with input ``x`` (S x d),
every norm an RMSNorm with a learned scale and ``rms_norm_eps``, no biases:

    a  = rms(x; w_in)
    r  = a Wr   (S x E)      p = softmax(r)          # the router reads the PRE-attention norm
    I  = top-k of p per token,  g_i = p_i / sum_I p  # norm_topk_prob: renormalised over the k
    q  = a Wq (heads x hd)   k = a Wk (kv_heads x hd)   v = a Wv (kv_heads x hd)
    if rope_layout[l]:  q, k -> RoPE(rope_theta, rotate-half), positions 0..S-1      # else none at all
    query head h reads key/value head h // (heads / kv_heads); scale 1/sqrt(hd)
    query i sees key j  iff  j <= i                       (sliding_window_layout[l] == 0)
                        iff  0 <= i - j < sliding_window_size   (== 1: HF's convention, itself included)
    x  = x + concat_heads(softmax(q k^T) v) Wo
    m  = rms(x; w_post)
    x  = x + sum_{i in I, i held} g_i * (relu(m Wgate_i) * (m Wup_i)) Wdown_i

then a final RMSNorm and an untied ``lm_head``. **A share of the experts.** The
configuration's ``moe_num_primary_experts`` counts the experts HELD (``reduced``:
one chip of the expert-parallel deployment the file states); the router, its
softmax, the top-k and the gates keep the published width
(``moe_num_primary_experts_published``), and what the absent experts would have
added is left out — of this reference as of the program — and that partial
result goes on to the next layer. ``experts_share`` says which contiguous block
is held (default 0). A config without the published key holds every expert.

The training loss is the mean next-token cross-entropy +
``load_balance_coef_as_run`` x load balance over ALL router outputs
(+ ``z_loss_coef_as_run`` x router z-loss, 0 as run), the auxiliary terms
computed per layer and averaged over layers.

Independent of ``ps_pytorch_tpu``: it takes the system's parameter tree only as
named arrays and computes in float32 under ``highest`` matmul precision. K and V
are repeated per query head with ``jnp.repeat``; attention is a dense masked
softmax, one head and one block of queries at a time against every key, both
masks explicit boolean matrices; the experts are a loop over the held ones on
every token with a dense gate (``g`` or 0) — no sort, no grouped matmul, no
kernel. The loops are ``jax.lax.map`` / ``jax.lax.scan`` (sequential, one
block's scores or one expert's activations alive at a time, so that S = 16384
fits beside a trainer's state; unrolled in Python such loops took the chip's
compiler minutes). Names it reads, per ``block_<i>``: ``RMSNorm_0`` (input),
``Dense_0..3`` (q, k, v, o), ``RMSNorm_1`` (post-attention), ``moe/router``,
``moe/experts_gate|up|down`` ([held, d, f], [held, d, f], [held, f, d]); at the
top ``tok_embed``, ``ln_f``, ``lm_head``.
"""

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024      # queries scored at a time against every key


def _experts(config):
    """-> (router outputs E, experts held, index of the first held)."""
    held = config.get("experts_held", config["moe_num_primary_experts"])
    e = config.get("moe_num_primary_experts_published",
                   config["moe_num_primary_experts"])
    return e, held, config.get("experts_share", 0) * held


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


def _layer(bp, x, config, layer):
    """One block on one sequence x [S, d]; -> (x, router logits [S, E],
    dense gates [S, E] with zeros off the top-k)."""
    eps, theta = config["rms_norm_eps"], float(config["rope_theta"])
    heads, kv_heads, hd = (config["num_attention_heads"],
                           config["num_key_value_heads"], config["head_dim"])
    k_top = config["moe_num_active_primary_experts"]
    window = config["sliding_window_size"] \
        if config["sliding_window_layout"][layer] else None
    s, _ = x.shape
    a = _rms(x, bp["RMSNorm_0"], eps)

    r = a @ bp["moe"]["router"]["kernel"]                 # before attention
    p = jax.nn.softmax(r, axis=-1) \
        if config["moe_primary_router_apply_softmax"] else r
    kth = jax.lax.top_k(p, k_top)[0][:, -1:]
    g = jnp.where(p >= kth, p, 0.0)
    if config["norm_topk_prob"]:
        g = g / jnp.sum(g, axis=-1, keepdims=True)

    by_head = lambda t, n: t.reshape(s, n, hd).transpose(1, 0, 2)
    q = by_head(a @ bp["Dense_0"]["kernel"], heads)
    k = by_head(a @ bp["Dense_1"]["kernel"], kv_heads)
    v = by_head(a @ bp["Dense_2"]["kernel"], kv_heads)
    k = jnp.repeat(k, heads // kv_heads, axis=0)          # head h <- h // group
    v = jnp.repeat(v, heads // kv_heads, axis=0)
    block = min(s, QUERY_BLOCK)
    assert s % block == 0, (s, block)
    key_pos = jnp.arange(s)

    def head(qkv):
        qh, kh, vh = qkv                                  # each [S, hd]
        if config["rope_layout"][layer]:
            qh, kh = _rope(qh, theta), _rope(kh, theta)

        def queries(args):
            qb, first = args                              # [block, hd], its first position
            dist = (first + jnp.arange(block))[:, None] - key_pos[None, :]
            seen = dist >= 0
            if window is not None:
                seen = seen & (dist < window)
            att = qb @ kh.T * hd ** -0.5
            att = jax.nn.softmax(jnp.where(seen, att, -jnp.inf), axis=-1)
            return att @ vh

        out = jax.lax.map(queries, (qh.reshape(s // block, block, hd),
                                    jnp.arange(0, s, block)))
        return out.reshape(s, hd)

    o = jax.lax.map(head, (q, k, v))                      # [heads, S, hd]
    x = x + o.transpose(1, 0, 2).reshape(s, heads * hd) \
        @ bp["Dense_3"]["kernel"]
    m = _rms(x, bp["RMSNorm_1"], eps)
    moe = bp["moe"]
    _, held, first = _experts(config)

    def expert(x, w):
        w_gate, w_up, w_down, g_e = w                     # one expert's, g_e [S]
        up = jax.nn.relu(m @ w_gate) * (m @ w_up)
        return x + g_e[:, None] * (up @ w_down), None

    x, _ = jax.lax.scan(expert, x, (moe["experts_gate"], moe["experts_up"],
                                    moe["experts_down"],
                                    g[:, first:first + held].T))
    return x, r, g


def _forward(variables, tokens, config):
    """-> (logits [B, S, V], [(router logits, gates)] per layer, each
    [B, S, E])."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), variables["params"])
    n = config["num_hidden_layers"]
    with jax.default_matmul_precision("highest"):
        rows, routed = [], []
        for b in range(tokens.shape[0]):
            x = p["tok_embed"]["embedding"][tokens[b]]
            per_layer = []
            for i in range(n):
                x, r, g = _layer(p[f"block_{i}"], x, config, i)
                per_layer.append((r, g))
            x = _rms(x, p["ln_f"], config["rms_norm_eps"])
            rows.append(x @ p["lm_head"]["kernel"])
            routed.append(per_layer)
        layers = [(jnp.stack([seq[i][0] for seq in routed]),
                   jnp.stack([seq[i][1] for seq in routed]))
                  for i in range(n)]
        return jnp.stack(rows), layers


def forward(variables, tokens, config):
    """variables: {"params"}; tokens: [B, S] int32; -> float32 logits
    [B, S, vocab]."""
    return _forward(variables, tokens, config)[0]


def loss_terms(variables, tokens, config):
    """-> (cross-entropy, load balance, z-loss), each a scalar: the mean
    next-token cross-entropy; ``E * sum_e f_e P_e`` over all E router outputs
    with ``f_e`` the assignments to expert e over the tokens (all k choices,
    so the f sum to k) and ``P_e`` the mean router probability; the mean of
    ``logsumexp(r)^2``; the last two averaged over layers."""
    logits, layers = _forward(variables, tokens, config)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))
    n_e = _experts(config)[0]
    lb = z = 0.0
    for r, g in layers:
        r, g = r.reshape(-1, n_e), g.reshape(-1, n_e)
        f = jnp.mean((g > 0).astype(jnp.float32), axis=0)
        lb = lb + n_e * jnp.sum(f * jnp.mean(jax.nn.softmax(r, -1), axis=0))
        z = z + jnp.mean(jax.nn.logsumexp(r, axis=-1) ** 2)
    return ce, lb / len(layers), z / len(layers)


def loss(variables, tokens, config):
    ce, lb, z = loss_terms(variables, tokens, config)
    return ce + config["load_balance_coef_as_run"] * lb \
        + config["z_loss_coef_as_run"] * z


def param_count(config, **_):
    """Parameters held: per layer q, k, v, o, the router over all E outputs,
    two norm vectors and the held experts; embedding, head, final norm."""
    d, f, v = (config["hidden_size"], config["moe_ffn_hidden_size"],
               config["vocab_size"])
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    e, held, _ = _experts(config)
    layer = 2 * d * q + 2 * d * kv + d * e + 2 * d + held * 3 * d * f
    return 2 * v * d + config["num_hidden_layers"] * layer + d


def keys_per_query(seq_len, window=None):
    """Mean number of keys a query sees at ``seq_len``: (S + 1) / 2 under the
    causal mask, the band's mean under a window of ``window`` keys."""
    w = seq_len if window is None else min(window, seq_len)
    return (w * (w + 1) / 2 + (seq_len - w) * w) / seq_len


def macs_per_token(config, seq_len):
    """Required forward multiply-adds for one token, by part: the q, k, v, o
    projections; the router; attention by the keys the layer's mask admits
    (two products of heads x head_dim a key); the routed experts at balance
    over the share held (k x held / E experts a token, three d x f matmuls
    each); the head."""
    d, f, v = (config["hidden_size"], config["moe_ffn_hidden_size"],
               config["vocab_size"])
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    e, held, _ = _experts(config)
    n = config["num_hidden_layers"]
    keys = sum(keys_per_query(
        seq_len, config["sliding_window_size"]
        if config["sliding_window_layout"][i] else None) for i in range(n))
    return {"projections": n * (2 * d * q + 2 * d * kv),
            "router": n * d * e,
            "attention": 2 * q * keys,
            "experts": n * config["moe_num_active_primary_experts"] * held / e
            * 3 * d * f,
            "head": d * v}


def train_flops_per_sample(config, seq_len, **_):
    """Required forward+backward FLOPs for one token at sequence length
    ``seq_len``: ``macs_per_token`` times 2 FLOPs, times 3 for forward plus
    both gradients. Attention is charged by the pairs its mask admits, not
    dense S x S: at S = 16384 under a window of 4096 the dense charge would
    be five times what the kernels have to do. Embedding look-ups, norms,
    RoPE, softmax, top-k, the sort and the optimizer are not counted;
    recomputation (``--remat``) never is."""
    return 3 * 2 * sum(macs_per_token(config, seq_len).values())
