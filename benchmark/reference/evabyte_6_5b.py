"""Plain float32 reference of the EvaByte stack, its loss over eight prediction
heads, its parameter count and its FLOPs.

Written from the published configuration (``EvaByte/EvaByte`` ``config.json``,
``model_type`` ``evabyte``) and the EVA paper ("Efficient Attention via
Control Variates", arXiv:2302.04542) in the reduced form the model's public
``eva.py`` runs (two learned vectors a head, no sampling); what the
configuration has no key for is under ``assumed`` in
``configs/evabyte_6_5b.json``. d = ``hidden_size``, L = ``num_hidden_layers``,
H = ``num_attention_heads`` heads of hd = d / H, W = ``window_size``, c =
``chunk_size``, P = W / c, no bias anywhere, no dropout. N is an RMSNorm with
``rms_norm_eps`` whose scale is ``1 + w`` (``norm_add_unit_offset``). Every
layer:

    x = x + EVA(N(x)) Wo
    x = x + (silu(N(x) Wgate) * (N(x) Wup)) Wdown        # width intermediate_size

EVA, head h, s = hd^-1/2: q, k = rope(y Wq), rope(y Wk) over all hd features
at absolute positions (``rope_theta``, rotate-half pairing), v = y Wv.
Summaries, from the ROTATED k: chunk j holds tokens c j .. c j + c - 1,

    alpha_m = softmax_{m in chunk j}(s k_m . phi_h)
    ks_j = sum_m alpha_m k_m + mu_h          vs_j = sum_m alpha_m v_m

and for query n of window w = n // W, over the tokens E_n = {m : m // W = w,
m <= n} (a block of the diagonal, not a band) and the summaries R_n = {j : j <
w P} (every chunk of every EARLIER window), ONE softmax:

    o_n = (sum_E e^{s q_n.k_m} v_m + sum_R e^{s q_n.ks_j} vs_j)
          / (sum_E e^{s q_n.k_m} + sum_R e^{s q_n.ks_j})

After the last layer N and an untied head d -> ``num_pred_heads`` x V: logits
[S, heads, V]; head i at position t predicts byte t + 1 + i. The loss is the
mean cross-entropy over every (position, head) whose target lies inside the
sequence, all heads weighted alike.

Independent of ``ps_pytorch_tpu``: it takes the system's variables only as
named arrays and computes in float32 under ``highest`` matmul precision. The
attention is a dense masked softmax over ``[every token | every summary]``,
one head and one block of ``QUERY_BLOCK`` queries at a time: nothing tiled by
window, nothing skipped, the mask alone says what a query sees. The functions
a mistake can be planted in are module attributes
(``controls/evabyte_6_5b.py`` replaces them by name): ``sees_token``,
``sees_summary``, ``attend`` (the one softmax), ``pool_scores``, ``pooled_key``,
``summary_keys``, ``chunk_of``, ``norm_scale``, ``target_offset``,
``heads_in_loss``. Names it reads, per ``params/block_<i>``:
``ZeroCentredRMSNorm_0`` (before the mixer), ``ZeroCentredRMSNorm_1`` (before
the feed-forward), ``Dense_0..3`` (q, k, v, o), ``adaptive_phi``,
``adaptive_mu_k`` [H, hd], ``mlp/gate|up|down``; at the top
``tok_embed/embedding``, ``ln_f/scale`` and ``lm_head/kernel`` [d, heads x V],
head-major.

Departures from the published description: none in the mathematics as far as
the sources give it; what they do not give is ``assumed``.
"""

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024      # queries scored at a time against every key


def _head_dim(config):
    return config["hidden_size"] // config["num_attention_heads"]


def chunk_of(config):
    return config["chunk_size"]


def norm_scale(w):
    """``norm_add_unit_offset``: the learned ``w`` starts at 0 and scales by
    ``1 + w``."""
    return 1.0 + w


def _norm(x, p, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * norm_scale(p["scale"])


def _rope(x, theta):
    """x [S, H, hd], absolute positions 0..S-1, feature i pairs with i + hd/2."""
    s, _, hd = x.shape
    half = hd // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def sees_token(n, m, config):
    """Query n sees token m: the same window, at or before it."""
    w = config["window_size"]
    return (n // w == m // w) & (m <= n)


def sees_summary(n, j, config):
    """Query n sees chunk j's summary: a chunk of an EARLIER window."""
    w = config["window_size"]
    return j < (n // w) * (w // chunk_of(config))


def pool_scores(k, phi, config):
    """k [chunks, c, hd], phi [hd] -> the pooling softmax's logits."""
    return (k @ phi) * _head_dim(config) ** -0.5


def pooled_key(ks, mu):
    return ks + mu


def summary_keys(k_rotated, k_plain):
    """The keys the summaries pool: the rotated ones."""
    return k_rotated


def attend(scores_tokens, scores_summaries, v, vs):
    """ONE softmax over both key sets: scores [Q, S] and [Q, S / c] with -inf
    where the query does not see, values [S, hd] and [S / c, hd]."""
    p = jax.nn.softmax(jnp.concatenate([scores_tokens, scores_summaries],
                                       axis=-1), axis=-1)
    return p @ jnp.concatenate([v, vs], axis=0)


def _eva_head(q, k, k_plain, v, phi, mu, config):
    """One head: q, k (rotated), k_plain, v [S, hd]; phi, mu [hd] -> [S, hd]."""
    s, hd = q.shape
    c = chunk_of(config)
    src = summary_keys(k, k_plain).reshape(s // c, c, hd)
    alpha = jax.nn.softmax(pool_scores(src, phi, config), axis=-1)
    ks = pooled_key(jnp.einsum("jc,jcd->jd", alpha, src), mu)
    vs = jnp.einsum("jc,jcd->jd", alpha, v.reshape(s // c, c, hd))
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    m, j = jnp.arange(s), jnp.arange(s // c)
    scale = hd ** -0.5

    def rows(args):
        q_blk, n = args
        st = jnp.where(sees_token(n[:, None], m[None, :], config),
                       q_blk @ k.T * scale, -jnp.inf)
        ss = jnp.where(sees_summary(n[:, None], j[None, :], config),
                       q_blk @ ks.T * scale, -jnp.inf)
        return attend(st, ss, v, vs)

    out = jax.lax.map(rows, (q.reshape(s // block, block, hd),
                             m.reshape(s // block, block)))
    return out.reshape(s, hd)


def _eva(bp, y, config):
    s = y.shape[0]
    hd, theta = _head_dim(config), float(config["rope_theta"])
    heads = lambda t: t.reshape(s, -1, hd)
    q, k, v = (heads(y @ bp[f"Dense_{i}"]["kernel"]) for i in range(3))
    by_head = lambda t: jnp.moveaxis(t, 1, 0)
    o = jax.lax.map(
        lambda a: _eva_head(*a, config),
        (by_head(_rope(q, theta)), by_head(_rope(k, theta)), by_head(k),
         by_head(v), bp["adaptive_phi"], bp["adaptive_mu_k"]))
    return jnp.moveaxis(o, 0, 1).reshape(s, -1) @ bp["Dense_3"]["kernel"]


def _swiglu(p, m):
    return (jax.nn.silu(m @ p["gate"]["kernel"]) * (m @ p["up"]["kernel"])) \
        @ p["down"]["kernel"]


def _forward_one(params, tokens, config):
    """tokens [S] -> logits [S, heads, V]."""
    eps = config["rms_norm_eps"]
    if tokens.shape[0] % chunk_of(config):
        raise ValueError(f"S={tokens.shape[0]} is no whole number of chunks "
                         f"of {chunk_of(config)}")
    x = params["tok_embed"]["embedding"][tokens]
    for i in range(config["num_hidden_layers"]):
        bp = params[f"block_{i}"]
        x = x + _eva(bp, _norm(x, bp["ZeroCentredRMSNorm_0"], eps), config)
        x = x + _swiglu(bp["mlp"], _norm(x, bp["ZeroCentredRMSNorm_1"], eps))
    logits = _norm(x, params["ln_f"], eps) @ params["lm_head"]["kernel"]
    return logits.reshape(x.shape[0], config["num_pred_heads"], -1)


def forward(variables, tokens, config):
    """variables: {'params': TransformerLM tree (arch evabyte)}; tokens [B, S]
    -> logits [B, S, heads, V] float32."""
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                          variables["params"])
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(lambda t: _forward_one(params, t, config), tokens)


def target_offset(head):
    """Head i at position t predicts byte t + 1 + i."""
    return 1 + head


def heads_in_loss(config):
    return range(config["num_pred_heads"])


def loss(variables, tokens, config):
    """The mean cross-entropy over every (position, head) whose target lies
    inside the sequence, all heads weighted alike; nothing else is in it."""
    logp = jax.nn.log_softmax(forward(variables, tokens, config), axis=-1)
    s = tokens.shape[1]
    total, count = 0.0, 0
    for i in heads_in_loss(config):
        off = target_offset(i)
        rows = s - max(off, 1)          # positions with a target inside
        picked = jnp.take_along_axis(
            logp[:, :rows, i], tokens[:, off:off + rows, None], axis=-1)
        total, count = total - jnp.sum(picked), count + picked.size
    return total / count


# ---- counts -----------------------------------------------------------------

def params_by_kind(config):
    d, f, v = (config["hidden_size"], config["intermediate_size"],
               config["vocab_size"])
    heads, hd = config["num_attention_heads"], _head_dim(config)
    return {"layer": 4 * d * d + 3 * d * f + 2 * d + 2 * heads * hd,
            "embedding": v * d,
            "head": d * config["num_pred_heads"] * v,
            "final_norm": d}


def param_count(config, **_):
    by = params_by_kind(config)
    return config["num_hidden_layers"] * by["layer"] + by["embedding"] \
        + by["head"] + by["final_norm"]


def live_pairs(seq_len, config):
    """(query, key) and (query, summary) pairs one head's mask admits: each
    window's causal block and, for a query of window w, the w P summaries of
    the windows before it."""
    w, p = config["window_size"], config["window_size"] // config["chunk_size"]
    pairs, start, i = 0, 0, 0
    while start < seq_len:
        n = min(w, seq_len - start)
        pairs += n * (n + 1) // 2 + n * i * p
        start, i = start + n, i + 1
    return pairs


def macs_per_token(config, seq_len):
    """Required forward multiply-adds for one token, by part: every matrix
    once (q, k, v, o and the feed-forward of every layer); the head (the
    embedding's look-up is not a matmul); attention BY THE LIVE PAIRS, 2 hd a
    pair (the score and the weighted sum) over heads and layers; the pooling,
    3 hd a token, head and layer (its score, its sum of keys, its sum of
    values)."""
    d, f = config["hidden_size"], config["intermediate_size"]
    layers, heads, hd = (config["num_hidden_layers"],
                         config["num_attention_heads"], _head_dim(config))
    return {"matrices": layers * (4 * d * d + 3 * d * f),
            "head": d * config["num_pred_heads"] * config["vocab_size"],
            "attention": layers * heads * 2 * hd
            * live_pairs(seq_len, config) / seq_len,
            "pooling": layers * heads * 3 * hd}


def train_flops_per_sample(config, seq_len, **_):
    """Required forward+backward FLOPs for one token at sequence length
    ``seq_len``: ``macs_per_token`` times 2 FLOPs, times 3 for forward plus
    both gradients (12 hd a live pair). Norms, softmax, silu, RoPE, the loss
    and the optimizer are not counted; recomputation (``--remat``) never is."""
    return 3 * 2 * sum(macs_per_token(config, seq_len).values())
