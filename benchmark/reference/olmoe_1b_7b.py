"""Plain float32 reference of the OLMoE-1B-7B block stack, its loss, its
parameter count and its FLOPs.

Written from the published architecture (Muennighoff et al. 2024,
arXiv:2409.02060; ``allenai/OLMoE-1B-7B-0125-Instruct`` ``config.json``; the
layer equations are HF's ``modeling_olmoe.py``). For a layer with input ``x``
(T x d), every norm an RMSNorm with a learned scale and ``rms_norm_eps``:

    h  = rms(x; w_in)
    q  = rms(h Wq; w_qn)   k = rms(h Wk; w_kn)   # over all d features, before the heads split
    v  = h Wv                                    # no biases anywhere
    q, k -> heads, RoPE(rope_theta, rotate-half: feature i with i + hd/2), positions 0..S-1
    x  = x + concat_heads(softmax_causal(q k^T / sqrt(hd)) v) Wo
    h  = rms(x; w_post)
    r  = h Wr   (T x E)      p = softmax(r)
    I  = top-k of p per token,  g_i = p_i        # norm_topk_prob false: gates NOT renormalised
    x  = x + sum_{i in I} g_i * (silu(h Wgate_i) * (h Wup_i)) Wdown_i

then a final RMSNorm and an untied ``lm_head``. The training loss is the mean
next-token cross-entropy + ``load_balance_coef_as_run`` x load balance +
``z_loss_coef_as_run`` x router z-loss, the two auxiliary terms computed per
layer and averaged over layers (``configs/olmoe_1b_7b.json:assumed``).

Independent of ``ps_pytorch_tpu``: it takes the system's parameter tree only as
named arrays and computes in float32 under ``highest`` matmul precision,
attention as a dense masked softmax one head at a time, the experts as a loop
over all of them on every token with a dense gate (``g`` or 0) — no sort, no
grouped matmul, no kernel. The two loops are ``jax.lax.map`` / ``jax.lax.scan``
(sequential, one head's scores or one expert's activations alive at a time;
unrolled in Python the 160 bodies of a 2-layer model took the chip's compiler
three minutes). Names it reads, per ``block_<i>``: ``RMSNorm_0``
(input), ``Dense_0..3`` (q, k, v, o), ``q_norm``, ``k_norm``, ``RMSNorm_1``
(post-attention), ``moe/router``, ``moe/experts_gate|up|down`` ([E, d, f],
[E, d, f], [E, f, d]); at the top ``tok_embed``, ``ln_f``, ``lm_head``.
"""

import jax
import jax.numpy as jnp


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


def _layer(bp, x, config):
    """One block on one sequence x [S, d]; -> (x, router logits [S, E],
    dense gates [S, E] with zeros off the top-k)."""
    eps, theta = config["rms_norm_eps"], float(config["rope_theta"])
    n_heads, k = config["num_attention_heads"], config["num_experts_per_tok"]
    s, d = x.shape
    hd = d // n_heads
    h = _rms(x, bp["RMSNorm_0"], eps)
    q = _rms(h @ bp["Dense_0"]["kernel"], bp["q_norm"], eps)
    kk = _rms(h @ bp["Dense_1"]["kernel"], bp["k_norm"], eps)
    v = h @ bp["Dense_2"]["kernel"]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def head(qkv):
        qh, kh, vh = qkv                                  # each [S, hd]
        att = _rope(qh, theta) @ _rope(kh, theta).T * hd ** -0.5
        att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
        return att @ vh

    by_head = lambda t: t.reshape(s, n_heads, hd).transpose(1, 0, 2)
    o = jax.lax.map(head, (by_head(q), by_head(kk), by_head(v)))
    x = x + o.transpose(1, 0, 2).reshape(s, d) @ bp["Dense_3"]["kernel"]
    h = _rms(x, bp["RMSNorm_1"], eps)
    r = h @ bp["moe"]["router"]["kernel"]
    p = jax.nn.softmax(r, axis=-1)
    kth = jax.lax.top_k(p, k)[0][:, -1:]
    g = jnp.where(p >= kth, p, 0.0)
    if config["norm_topk_prob"]:
        g = g / jnp.sum(g, axis=-1, keepdims=True)
    moe = bp["moe"]

    def expert(x, w):
        w_gate, w_up, w_down, g_e = w                     # one expert's, g_e [S]
        up = jax.nn.silu(h @ w_gate) * (h @ w_up)
        return x + g_e[:, None] * (up @ w_down), None

    x, _ = jax.lax.scan(expert, x, (moe["experts_gate"], moe["experts_up"],
                                    moe["experts_down"], g.T))
    return x, r, g


def _forward(variables, tokens, config):
    """-> (logits [B, S, V], [(router logits, gates)] per layer, each
    [B, S, E])."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), variables["params"])
    with jax.default_matmul_precision("highest"):
        rows, routed = [], []
        for b in range(tokens.shape[0]):
            x = p["tok_embed"]["embedding"][tokens[b]]
            per_layer = []
            for i in range(config["num_hidden_layers"]):
                x, r, g = _layer(p[f"block_{i}"], x, config)
                per_layer.append((r, g))
            x = _rms(x, p["ln_f"], config["rms_norm_eps"])
            rows.append(x @ p["lm_head"]["kernel"])
            routed.append(per_layer)
        layers = [(jnp.stack([seq[i][0] for seq in routed]),
                   jnp.stack([seq[i][1] for seq in routed]))
                  for i in range(config["num_hidden_layers"])]
        return jnp.stack(rows), layers


def forward(variables, tokens, config):
    """variables: {"params"}; tokens: [B, S] int32; -> float32 logits
    [B, S, vocab]."""
    return _forward(variables, tokens, config)[0]


def loss_terms(variables, tokens, config):
    """-> (cross-entropy, load balance, z-loss), each a scalar: the mean
    next-token cross-entropy; ``E * sum_e f_e P_e`` with ``f_e`` the
    assignments to expert e over the tokens (all k choices, so the f sum to
    k) and ``P_e`` the mean router probability; the mean of
    ``logsumexp(r)^2``; the last two averaged over layers."""
    logits, layers = _forward(variables, tokens, config)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))
    n_e = config["num_experts"]
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
    d, f, v = config["hidden_size"], config["intermediate_size"], config["vocab_size"]
    e, n = config["num_experts"], config["num_hidden_layers"]
    layer = 4 * d * d + d * e + e * 3 * d * f + 4 * d   # q k v o, router, experts, 4 norm vectors
    return 2 * v * d + n * layer + d


def train_flops_per_sample(config, seq_len, **_):
    """Required forward+backward FLOPs for one token at sequence length
    ``seq_len``: per layer 4 d^2 multiply-adds in q, k, v, o; 2 S d for
    attention charged dense S x S as PaLM does; k x 3 d f in the k routed
    SwiGLU experts; E d in the router; d x vocab in the head; times 2 FLOPs,
    times 3 for forward plus both gradients. Embedding look-ups, norms, RoPE,
    softmax, top-k, the sort and the optimizer are not counted;
    recomputation never is."""
    d, f, v = config["hidden_size"], config["intermediate_size"], config["vocab_size"]
    e, k, n = (config["num_experts"], config["num_experts_per_tok"],
               config["num_hidden_layers"])
    per_token_macs = n * (4 * d * d + 2 * seq_len * d + k * 3 * d * f + e * d) \
        + d * v
    return 3 * 2 * per_token_macs
