"""Plain float32 reference of the Phi-4-mini-flash (SambaY) stack, its loss, its
parameter count and its FLOPs.

Written from the published configuration (``microsoft/Phi-4-mini-flash-
reasoning`` ``config.json``, ``model_type`` ``phi4flash``), the SambaY paper
(arXiv:2507.06607), Differential Transformer (arXiv:2410.05258) and Mamba
(arXiv:2312.00752); what the configuration has no key for is under ``assumed``
in ``configs/phi4_mini_flash.json`` and, where it is a number, under ``mamba``
there. d = ``hidden_size``, L = ``num_hidden_layers`` (a multiple of 4), no
position encoding anywhere, no dropout, no bias but where stated. LN is a
LayerNorm with scale and bias and ``layer_norm_eps``. Every layer i:

    x = x + Mixer_i(LN(x))
    x = x + (silu(LN(x) Wgate) * (LN(x) Wup)) Wdown          # width intermediate_size

and the kind of layer i (``layer_kind``; ``mb_per_layer`` 2, the second half
the cross-decoder):

    i <  L/2, even   Mamba
    i <  L/2, odd    differential attention, query t sees keys t - (sliding_window - 1) .. t
    i == L/2         Mamba; its scan output m (before the gate, D * u included) goes to every gated memory unit
    i == L/2 + 1     differential attention, causal over all keys; its K and V go to every cross layer
    i >  L/2 + 1, even   gated memory unit   (silu(a Win) * m) Wout,  Win: d -> d_inner, Wout: d_inner -> d
    i >  L/2 + 1, odd    differential attention with its own Wq and Wo only, on layer L/2 + 1's K and V, causal

Mamba (``mamba``: d_inner = expand * d, d_state, d_conv, dt_rank): [u, z] = a
Win; u = silu(conv1d(u)) depthwise, causal, with bias; [dt, B, C] = u Wx;
delta = softplus(dt Wdt + b_dt); A = -exp(A_log); h_t = exp(delta_t A) h_{t-1}
+ (delta_t u_t) B_t^T, h_0 = 0; m_t = h_t C_t + D u_t; out = (m * silu(z)) Wout.

Differential attention (H query heads, Hkv key/value heads of hd): heads pair
up as (2j, 2j+1): H/2 query pairs (q1, q2), Hkv/2 key pairs (k1, k2), and the
value heads of a pair side by side as one value 2 hd wide; query pair j reads
key/value pair j // (H / Hkv). o_j = softmax(q1 k1^T / sqrt(hd)) v - lambda
softmax(q2 k2^T / sqrt(hd)) v, both under the layer's mask; lambda = exp(lq1 .
lk1) - exp(lq2 . lk2) + lambda_init, lambda_init = 0.8 - 0.6 exp(-0.3 i); o_j
= RMSNorm(o_j; w, eps) (1 - lambda_init) over its 2 hd features; the H/2
outputs side by side pass Wo. Then a final LN and the head, tied to the
embedding: logits = LN(x) E^T.

``vocab_size`` counts the rows HELD (``reduced``: one chip of a
vocabulary-parallel deployment); embedding, head, logits and loss are over
them.

Independent of ``ps_pytorch_tpu``: it takes the system's variables only as
named arrays and computes in float32 under ``highest`` matmul precision. The
recurrence is a ``lax.scan`` over single tokens, nothing chunked; attention is
a dense masked softmax, one pair and one block of queries at a time against
every key, both softmaxes of a pair spelled out; the convolution is a sum of
shifted copies. The functions a mistake can be planted in are module
attributes (``controls/phi4_mini_flash.py`` replaces them by name):
``diff_lambda``, ``lambda_init``, ``window_of``, ``pairs``, ``causal_conv``,
``delta_of``, ``memory_of``, ``cross_kv``, and ``STATE_BITS`` (the scan
state's mantissa bits). Names it reads, per ``params/block_<i>``:
``LayerNorm_0`` (before the mixer), ``LayerNorm_1`` (before the
feed-forward), ``mlp/gate|up|down``; a Mamba layer ``in_proj``,
``conv_weight`` [d_conv, d_inner], ``conv_bias``, ``x_proj``, ``dt_proj``,
``dt_bias``, ``A_log`` [d_inner, d_state], ``D``, ``out_proj``; an attention
layer ``Dense_0..3`` (q, k, v, o), ``lambda_q1|k1|q2|k2`` [hd],
``subln/scale`` [2 hd]; a cross layer ``Dense_0..1`` (q, o) with the lambdas
and ``subln``; a gated memory unit ``in_proj``, ``out_proj``; at the top
``tok_embed/embedding`` and ``ln_f``.

Departures from the published description: none in the mathematics; the
initialisers, the pairing of heads and where ``m`` is taken are ``assumed``.
"""

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024      # queries scored at a time against every key
STATE_BITS = 23         # mantissa bits the scan's state keeps: float32's


def layer_kind(config, layer):
    n = config["num_hidden_layers"]
    assert n % 4 == 0 and config["mb_per_layer"] == 2
    half = n // 2
    if layer < half:
        return "window" if layer % 2 else "mamba"
    if layer <= half + 1:
        return "full_hands_kv" if layer % 2 else "mamba_hands_memory"
    return "cross" if layer % 2 else "gmu"


def _mamba_sizes(config):
    m = config["mamba"]
    return (m["expand"] * config["hidden_size"], m["d_state"], m["d_conv"],
            m["dt_rank"])


def _head_dim(config):
    return config["hidden_size"] // config["num_attention_heads"]


def _ln(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _swiglu(p, m):
    return (jax.nn.silu(m @ p["gate"]["kernel"]) * (m @ p["up"]["kernel"])) \
        @ p["down"]["kernel"]


# ---- what a mistake can be planted in -------------------------------------

def lambda_init(layer):
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def diff_lambda(bp, layer):
    return jnp.exp(jnp.sum(bp["lambda_q1"] * bp["lambda_k1"])) \
        - jnp.exp(jnp.sum(bp["lambda_q2"] * bp["lambda_k2"])) \
        + lambda_init(layer)


def window_of(config, layer):
    """Keys a query of this layer sees, itself included; None: every key
    before it."""
    return config["sliding_window"] \
        if layer_kind(config, layer) == "window" else None


def pairs(x):
    """[S, heads, hd] -> the first and the second head of every pair."""
    return x[:, 0::2], x[:, 1::2]


def causal_conv(u, weight, bias):
    """out[t] = bias + sum_k weight[k] u[t - (K - 1) + k], zeros before the
    sequence. u [S, C], weight [K, C]."""
    taps, s = weight.shape[0], u.shape[0]
    padded = jnp.pad(u, ((taps - 1, 0), (0, 0)))
    return bias + sum(weight[k] * padded[k:k + s] for k in range(taps))


def delta_of(dt):
    return jax.nn.softplus(dt)


def memory_of(m, z, skip_u):
    """What a Mamba layer hands on: its scan output ``m`` (``skip_u`` = D * u
    is in it), before the gate ``silu(z)``."""
    return m


def cross_kv(handed, a, config, params):
    """The K and V a cross layer reads: the handed-on ones (``a``, the layer's
    own normed input, is not read)."""
    return handed


# ---- the layers -------------------------------------------------------------

def selective_scan(u, delta, a_neg, b, c):
    """h_t = exp(delta_t A) h_{t-1} + (delta_t u_t) B_t^T; -> h_t C_t for every
    t. u, delta [S, Di]; a_neg [Di, N]; b, c [S, N]: one token at a time."""
    def step(h, xs):
        u_t, d_t, b_t, c_t = xs
        h = jnp.exp(d_t[:, None] * a_neg) * h + (d_t * u_t)[:, None] * b_t
        if STATE_BITS < 23:
            h = jax.lax.reduce_precision(h, exponent_bits=8,
                                         mantissa_bits=STATE_BITS)
        return h, h @ c_t
    h0 = jnp.zeros((u.shape[1], a_neg.shape[1]), jnp.float32)
    return jax.lax.scan(step, h0, (u, delta, b, c))[1]


def _mamba(bp, a, config):
    """-> (the mixer's output, what it hands on); a [S, d]."""
    d_inner, n, _, rank = _mamba_sizes(config)
    u, z = jnp.split(a @ bp["in_proj"]["kernel"], 2, axis=-1)
    u = jax.nn.silu(causal_conv(u, bp["conv_weight"], bp["conv_bias"]))
    dt, b, c = jnp.split(u @ bp["x_proj"]["kernel"], [rank, rank + n], axis=-1)
    delta = delta_of(dt @ bp["dt_proj"]["kernel"] + bp["dt_bias"])
    skip_u = bp["D"] * u
    m = selective_scan(u, delta, -jnp.exp(bp["A_log"]), b, c) + skip_u
    return (m * jax.nn.silu(z)) @ bp["out_proj"]["kernel"], \
        memory_of(m, z, skip_u)


def _attend(q, k, v, window):
    """softmax(q k^T / sqrt(hd) + mask) v for one head: q, k [S, hd], v [S,
    dv]; query blocks of QUERY_BLOCK against every key."""
    s, hd = q.shape
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    key_pos = jnp.arange(s)

    def rows(args):
        q_blk, q_pos = args
        dist = q_pos[:, None] - key_pos[None, :]
        ok = dist >= 0 if window is None else (dist >= 0) & (dist < window)
        scores = jnp.where(ok, q_blk @ k.T / math.sqrt(hd), -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ v

    out = jax.lax.map(rows, (q.reshape(s // block, block, hd),
                             key_pos.reshape(s // block, block)))
    return out.reshape(s, v.shape[-1])


def _diff_attention(bp, q, k, v, config, layer):
    """q [S, H, hd], k, v [S, Hkv, hd] -> [S, H * hd] before Wo."""
    s, heads, hd = q.shape
    q1, q2 = pairs(q)
    k1, k2 = pairs(k)
    value = jnp.concatenate(pairs(v), axis=-1)          # [S, Hkv/2, 2 hd]
    group = q1.shape[1] // k1.shape[1]
    lam, window = diff_lambda(bp, layer), window_of(config, layer)
    by_pair = lambda t: jnp.moveaxis(jnp.repeat(t, group, axis=1), 1, 0)

    def pair(args):
        qa, qb, ka, kb, vv = args
        return _attend(qa, ka, vv, window) - lam * _attend(qb, kb, vv, window)

    o = jax.lax.map(pair, (jnp.moveaxis(q1, 1, 0), jnp.moveaxis(q2, 1, 0),
                           by_pair(k1), by_pair(k2), by_pair(value)))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + config["layer_norm_eps"]) * bp["subln"]["scale"]
    o = o * (1.0 - lambda_init(layer))
    return jnp.moveaxis(o, 0, 1).reshape(s, heads * hd)


def _heads(x, hd):
    return x.reshape(x.shape[0], -1, hd)


def _attention(bp, a, config, layer, handed, params):
    """-> (the mixer's output, (k, v) in heads)."""
    hd = _head_dim(config)
    q = _heads(a @ bp["Dense_0"]["kernel"], hd)
    if layer_kind(config, layer) == "cross":
        k, v = cross_kv(handed, a, config, params)
        wo = bp["Dense_1"]["kernel"]
    else:
        k = _heads(a @ bp["Dense_1"]["kernel"], hd)
        v = _heads(a @ bp["Dense_2"]["kernel"], hd)
        wo = bp["Dense_3"]["kernel"]
    return _diff_attention(bp, q, k, v, config, layer) @ wo, (k, v)


def _forward_one(params, tokens, config):
    """tokens [S] -> logits [S, V]."""
    eps = config["layer_norm_eps"]
    table = params["tok_embed"]["embedding"]
    x = table[tokens]
    memory = kv = None
    for i in range(config["num_hidden_layers"]):
        bp = params[f"block_{i}"]
        kind = layer_kind(config, i)
        a = _ln(x, bp["LayerNorm_0"], eps)
        if kind in ("mamba", "mamba_hands_memory"):
            out, m = _mamba(bp, a, config)
            if kind == "mamba_hands_memory":
                memory = m
        elif kind == "gmu":
            out = (jax.nn.silu(a @ bp["in_proj"]["kernel"]) * memory) \
                @ bp["out_proj"]["kernel"]
        else:
            out, own = _attention(bp, a, config, i, kv, params)
            if kind == "full_hands_kv":
                kv = own
        x = x + out
        x = x + _swiglu(bp["mlp"], _ln(x, bp["LayerNorm_1"], eps))
    return _ln(x, params["ln_f"], eps) @ table.T


def forward(variables, tokens, config):
    """variables: {'params': TransformerLM tree (arch phi4flash)}; tokens [B,
    S] -> logits [B, S, V] float32."""
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                          variables["params"])
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(lambda t: _forward_one(params, t, config), tokens)


def loss(variables, tokens, config):
    """The mean next-token cross-entropy; nothing else is in the loss."""
    logits = forward(variables, tokens, config)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))


# ---- counts -----------------------------------------------------------------

def _layer_params(config):
    """Parameters of one layer of each kind, matrices and vectors apart."""
    d, f = config["hidden_size"], config["intermediate_size"]
    hd = _head_dim(config)
    q, kv = config["num_attention_heads"] * hd, \
        config["num_key_value_heads"] * hd
    d_inner, n, taps, rank = _mamba_sizes(config)
    ffn, norms = 3 * d * f, 4 * d
    diff = 4 * hd + 2 * hd          # four lambda vectors, the pair norm's scale
    return {
        "mamba": {"matrices": d * 2 * d_inner + d_inner * (rank + 2 * n)
                  + rank * d_inner + d_inner * d + ffn,
                  "vectors": norms + taps * d_inner + 3 * d_inner
                  + d_inner * n},       # conv taps and bias, dt bias, D; A_log
        "attention": {"matrices": 2 * d * q + 2 * d * kv + ffn,
                      "vectors": norms + diff},
        "cross": {"matrices": 2 * d * q + ffn, "vectors": norms + diff},
        "gmu": {"matrices": 2 * d * d_inner + ffn, "vectors": norms},
    }


_COUNTED_AS = {"mamba": "mamba", "mamba_hands_memory": "mamba",
               "window": "attention", "full_hands_kv": "attention",
               "cross": "cross", "gmu": "gmu"}


def param_count(config, **_):
    """Parameters held: every layer by its kind, the embedding (which is the
    head) over the rows held, the final norm's scale and bias."""
    lp = _layer_params(config)
    layers = sum(sum(lp[_COUNTED_AS[layer_kind(config, i)]].values())
                 for i in range(config["num_hidden_layers"]))
    d = config["hidden_size"]
    return layers + config["vocab_size"] * d + 2 * d


def keys_per_query(seq_len, window=None):
    """Mean number of keys a query sees at ``seq_len``: (S + 1) / 2 under the
    causal mask, the band's mean under a window of ``window`` keys."""
    w = seq_len if window is None else min(window, seq_len)
    return (w * (w + 1) / 2 + (seq_len - w) * w) / seq_len


# Multiply-adds the recurrence needs for one (channel, state) pair of one
# token, forward: delta * A, the exponential (counted as one), a * h + b, (delta
# u) * B, h * C and its add into y: seven operations, 3.5 multiply-adds.
SCAN_MACS_PER_STATE = 3.5


def macs_per_token(config, seq_len):
    """Required forward multiply-adds for one token, by part: every matrix
    once (the projections and feed-forwards of every layer); the head (the
    embedding's look-up is not a matmul); attention by the keys each layer's
    mask admits, two softmaxes a query pair, each hd wide against the keys and
    2 hd wide against the values (3 x heads x hd a key); the convolution's
    taps; the scan's elementwise work (``SCAN_MACS_PER_STATE`` a channel and
    state, plus delta * u and D * u a channel)."""
    lp = _layer_params(config)
    n = config["num_hidden_layers"]
    kinds = [layer_kind(config, i) for i in range(n)]
    d_inner, n_state, taps, _ = _mamba_sizes(config)
    mambas = sum(k in ("mamba", "mamba_hands_memory") for k in kinds)
    keys = sum(keys_per_query(seq_len, window_of(config, i))
               for i in range(n) if _COUNTED_AS[kinds[i]] in ("attention",
                                                              "cross"))
    q = config["num_attention_heads"] * _head_dim(config)
    return {"matrices": sum(lp[_COUNTED_AS[k]]["matrices"] for k in kinds),
            "head": config["hidden_size"] * config["vocab_size"],
            "attention": 3 * q * keys,
            "conv": mambas * taps * d_inner,
            "scan": mambas * d_inner * (SCAN_MACS_PER_STATE * n_state + 2)}


def train_flops_per_sample(config, seq_len, **_):
    """Required forward+backward FLOPs for one token at sequence length
    ``seq_len``: ``macs_per_token`` times 2 FLOPs, times 3 for forward plus
    both gradients (the scan's backward is counted as twice its forward, like
    a matmul's). Attention is charged by the pairs its mask admits. Norms,
    softmax, silu, softplus, the gates' products, lambda and the optimizer are
    not counted; recomputation (``--remat``) never is."""
    return 3 * 2 * sum(macs_per_token(config, seq_len).values())
