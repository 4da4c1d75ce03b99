"""Plain float32 reference of the Nemotron-3-Nano (``nemotron_h``) stack:
layers that are ONE pre-norm residual sublayer each, a Mamba-2 mixer by its
recurrence, a softmax-attention mixer without position encoding, or an expert
layer of ungated relu^2 experts beside a shared one; its loss, its parameter
count and its FLOPs.

Written from the published configuration
(``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16`` ``config.json``, ``model_type``
``nemotron_h``) and, for what that file has no key for, from the family's
published modelling code (``modeling_nemotron_h.py``) and the Mamba-2 paper
(arXiv:2405.21060) as known; each such point is under ``assumed`` in
``configs/nemotron3_nano_30b_a3b.json``. ``N`` is RMSNorm (``x rsqrt(mean(x^2)
+ eps) w``); no biases but the convolution's. Layer ``l`` with input ``h`` (S
x d) is ``h + f_l(N(h; w_l))`` with ``f_l`` by letter ``l`` of
``hybrid_override_pattern`` and ``a = N(h)``:

    M:  [z | xBC | dt] = a W_in          (H P, H P + 2 G N, H)
        xBC = silu(conv(xBC) + bias)     depthwise, causal, conv_kernel taps
        [x | B | C] = xBC                x: H heads of P; B, C: G groups of N; head i reads group i // (H / G)
        delta = softplus(dt + dt_bias)   A = -exp(A_log)        a head
        from h = 0 [P, N] a head, TOKEN BY TOKEN:
            h = exp(delta_t A) h + (delta_t x_t) B_t^T;   y_t = h C_t + D x_t
        y = group_rms(y * silu(z); w_y)  the gate first, then the norm over each of G groups of H P / G
        f = y W_out
    *:  q = a Wq (heads x hd)   k = a Wk (kv x hd)   v = a Wv (kv x hd)
        query head i reads key/value head i // (heads / kv); causal; scale 1/sqrt(hd); no position encoding
        f = concat_heads(softmax(q k^T + mask) v) Wo
    E:  s = sigmoid(a Wr) over all E outputs;  I = top-k of s + b (b = expert_bias: chooses, does not weigh)
        w_i = routed_scaling_factor s_i / sum_{j in I} s_j
        f = shared(a) + sum_{i in I, i held} w_i expert_i(a)     both down(relu(up a)^2)

then a final ``N`` and an untied ``lm_head``. The Mamba-2 layers are the
recurrence itself, one token at a time under ``lax.scan``: the system's chunked
form (``ps_pytorch_tpu/ops/ssd.py``) is held to something that is not a chunked
form.

**A share of the experts.** ``n_routed_experts`` counts the experts HELD
(``reduced``: one chip of the expert-parallel deployment the file states); the
router, its sigmoid, the bias, the top-k and the weights keep the published
width (``n_routed_experts_published``), and what the absent experts would have
added is left out, of this reference as of the program; the shared expert is
whole. ``experts_share`` says which contiguous block is held. A config without
the published key holds every expert.

**The bias's step** (``bias_step``): after each optimizer step every expert
layer's bias moves by ``router_bias_rate`` times the sign of (mean count -
count), the steps centred; there is no auxiliary loss.

Independent of ``ps_pytorch_tpu``: it takes the system's variables only as
named arrays and computes in float32 under ``highest`` matmul precision. K and
V are repeated per query head; attention is a dense masked softmax, one head
and one block of queries at a time against every key; the routed experts are a
loop over the held ones on every token with a dense weight (``w`` or 0): no
sort, no grouped matmul, no kernel. The functions a mistake can be planted in
are module attributes (``controls/nemotron3_nano_30b_a3b.py`` replaces them by
name): ``gated_norm``, ``group_of_head``, ``skip_term``, ``decay_of``,
``input_of``, ``causal_conv``, ``conv_bias_of``, ``expert_act``,
``renormalised``, ``route_scale``, ``shared_expert``, and ``STATE_BITS`` (the
mantissa bits the state keeps across a boundary every ``chunk_size`` tokens). Names it reads, per ``params/block_<i>``: ``RMSNorm_0``; a Mamba-2
layer's ``in_proj``, ``conv_weight`` [taps, channels], ``conv_bias``,
``dt_bias``, ``A_log``, ``D``, ``ssm_norm``, ``out_proj``; an attention layer's
``Dense_0..3`` (q, k, v, o); an expert layer's ``moe/router``,
``moe/experts_up|down`` (BOTH [held, f, d]: the up projection is stored as a
checkpoint stores a linear layer, [out, in]), ``shared/up|down``; at the
top ``tok_embed``, ``ln_f``, ``lm_head``; and
``moe_state/block_<i>/moe/expert_bias`` [E].
"""

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024      # queries scored at a time against every key
GATE_EPS = 1e-20        # under the sum of the chosen sigmoid scores
STATE_BITS = 23         # mantissa bits the recurrence's state keeps: float32's;
#                         where fewer, rounded to them every chunk_size tokens
KINDS = {"M": "mamba2", "*": "attention", "E": "experts"}


def _experts(config):
    """-> (router outputs E, experts held, index of the first held)."""
    held = config.get("experts_held", config["n_routed_experts"])
    e = config.get("n_routed_experts_published", config["n_routed_experts"])
    return e, held, config.get("experts_share", 0) * held


def layer_kind(config, layer):
    return KINDS[config["hybrid_override_pattern"][layer]]


def _rms(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * p["scale"]


def causal_conv(u, weight, bias):
    """out[t] = bias + sum_j weight[j] u[t - (taps - 1) + j], zeros before
    the sequence. u: [S, channels]; weight: [taps, channels]."""
    taps, s = weight.shape[0], u.shape[0]
    padded = jnp.pad(u, ((taps - 1, 0), (0, 0)))
    return bias + sum(weight[j] * padded[j:j + s] for j in range(taps))


def conv_bias_of(bp):
    return bp["conv_bias"]


def group_of_head(t, r):
    """B or C [S, G, N] for every head: head i reads group i // r."""
    return jnp.repeat(t, r, axis=1)


def decay_of(delta, a):
    """The state's decay a token and head: exp(delta A) in (0, 1]."""
    return jnp.exp(delta * a)


def input_of(delta, x):
    """What enters the state with B: the step times the input."""
    return delta[..., None] * x


def skip_term(d, x):
    return d[:, None] * x


def recurrence(x, delta, a, b, c, d, round_every):
    """Mamba-2's recurrence token by token. x: [S, H, P]; delta: [S, H]; a, d:
    [H]; b, c: [S, H, N] (one group for every head: the caller repeats).
    -> y [S, H, P]. ``round_every``: see ``STATE_BITS``."""
    def token(state, xs):
        x_t, dt_t, b_t, c_t, t = xs
        state = state * decay_of(dt_t, a)[:, None, None] \
            + input_of(dt_t, x_t)[:, :, None] * b_t[:, None, :]
        y = jnp.einsum("hpn,hn->hp", state, c_t) + skip_term(d, x_t)
        if STATE_BITS < 23:
            state = jnp.where(
                (t + 1) % round_every == 0,
                jax.lax.reduce_precision(state, exponent_bits=8,
                                         mantissa_bits=STATE_BITS), state)
        return state, y

    state = jnp.zeros((x.shape[1], x.shape[2], b.shape[2]), jnp.float32)
    return jax.lax.scan(token, state,
                        (x, delta, b, c, jnp.arange(x.shape[0])))[1]


def gated_norm(y, z, scale, eps, groups):
    """The gate FIRST, then RMSNorm over each of ``groups`` groups of the
    features, then one scale [H P] (norm_before_gate false)."""
    s = y.shape[0]
    g = (y * jax.nn.silu(z)).reshape(s, groups, -1)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return g.reshape(s, -1) * scale


def _mamba2(bp, a, config):
    """The Mamba-2 mixer's contribution on the normed stream ``a``."""
    heads, p = config["mamba_num_heads"], config["mamba_head_dim"]
    groups, n = config["n_groups"], config["ssm_state_size"]
    s, inner, bc = a.shape[0], heads * p, groups * n
    assert config["use_conv_bias"] and not config["mamba_proj_bias"]
    assert bp["conv_weight"].shape[0] == config["conv_kernel"]
    assert config["mamba_hidden_act"] == "silu"
    zxbcdt = a @ bp["in_proj"]["kernel"]
    z, xbc, dt = (zxbcdt[:, :inner], zxbcdt[:, inner:2 * inner + 2 * bc],
                  zxbcdt[:, 2 * inner + 2 * bc:])
    xbc = jax.nn.silu(causal_conv(xbc, bp["conv_weight"], conv_bias_of(bp)))
    x = xbc[:, :inner].reshape(s, heads, p)
    b, c = (group_of_head(t.reshape(s, groups, n), heads // groups)
            for t in (xbc[:, inner:inner + bc], xbc[:, inner + bc:]))
    delta = jax.nn.softplus(dt + bp["dt_bias"])     # time_step_limit (0, inf): no clamp
    y = recurrence(x, delta, -jnp.exp(bp["A_log"]), b, c, bp["D"],
                   config["chunk_size"])
    y = gated_norm(y.reshape(s, inner), z, bp["ssm_norm"]["scale"],
                   config["layer_norm_epsilon"], groups)
    return y @ bp["out_proj"]["kernel"]


def _attention(bp, a, config):
    """The attention mixer's contribution on the normed stream: no position
    encoding, no q/k norm, no gate."""
    heads, kv_heads, hd = (config["num_attention_heads"],
                           config["num_key_value_heads"], config["head_dim"])
    assert not config["attention_bias"]
    s = a.shape[0]
    by_head = lambda t, n: t.reshape(s, n, hd).transpose(1, 0, 2)
    q = by_head(a @ bp["Dense_0"]["kernel"], heads)
    k = by_head(a @ bp["Dense_1"]["kernel"], kv_heads)
    v = by_head(a @ bp["Dense_2"]["kernel"], kv_heads)
    k = jnp.repeat(k, heads // kv_heads, axis=0)          # head i <- i // group
    v = jnp.repeat(v, heads // kv_heads, axis=0)
    block = min(s, QUERY_BLOCK)
    assert s % block == 0, (s, block)
    key_pos = jnp.arange(s)

    def head(qkv):
        qh, kh, vh = qkv                                  # each [S, hd]

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
    return o.transpose(1, 0, 2).reshape(s, heads * hd) @ bp["Dense_3"]["kernel"]


def expert_act(x):
    """relu2: the square of relu."""
    return jnp.square(jax.nn.relu(x))


def _ffn(p, m):
    """down(act(up m)): no gate projection."""
    return expert_act(m @ p["up"]["kernel"]) @ p["down"]["kernel"]


def shared_expert(bp, m):
    return _ffn(bp["shared"], m)


def renormalised(w):
    """The chosen scores over their sum (norm_topk_prob)."""
    return w / (jnp.sum(w, axis=-1, keepdims=True) + GATE_EPS)


def route_scale(config):
    return config["routed_scaling_factor"]


def route(m, router, bias, config):
    """-> weights [S, E], zeros off the top-k: sigmoid scores; the top-k of
    score + bias; the chosen scores over their sum, times the scale."""
    assert config["norm_topk_prob"]
    assert config["n_group"] == config["topk_group"] == 1   # no group limit
    s = jax.nn.sigmoid(m @ router)
    biased = s + bias
    kth = jax.lax.top_k(biased, config["num_experts_per_tok"])[0][:, -1:]
    return route_scale(config) * renormalised(jnp.where(biased >= kth, s, 0.0))


def _expert_layer(bp, bias, m, config):
    """-> (the expert layer's contribution on the normed stream ``m``: the
    held experts' part and the shared expert; the weights [S, E])."""
    moe = bp["moe"]
    assert config["mlp_hidden_act"] == "relu2" and not config["mlp_bias"]
    assert config["n_shared_experts"] == 1
    assert bp["shared"]["up"]["kernel"].shape[1] \
        == config["moe_shared_expert_intermediate_size"]
    w = route(m, moe["router"]["kernel"], bias, config)
    _, held, first = _experts(config)

    def expert(f, x):
        w_up, w_down, w_e = x                             # one expert's, w_e [S]
        return f + w_e[:, None] * (expert_act(m @ w_up.T) @ w_down), None

    f = jax.lax.scan(expert, shared_expert(bp, m),
                     (moe["experts_up"], moe["experts_down"],
                      w[:, first:first + held].T))[0]
    return f, w


def _forward(variables, tokens, config):
    """-> (logits [B, S, V], {block name: weights [B, S, E]} of the expert
    layers)."""
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    p, state = f32(variables["params"]), f32(variables.get("moe_state", {}))
    eps = config["layer_norm_epsilon"]
    assert not config["tie_word_embeddings"]
    with jax.default_matmul_precision("highest"):
        rows, routed = [], {}
        for b in range(tokens.shape[0]):
            h = p["tok_embed"]["embedding"][tokens[b]]
            for i in range(config["num_hidden_layers"]):
                name, kind = f"block_{i}", layer_kind(config, i)
                bp = p[name]
                a = _rms(h, bp["RMSNorm_0"], eps)
                if kind == "mamba2":
                    h = h + _mamba2(bp, a, config)
                elif kind == "attention":
                    h = h + _attention(bp, a, config)
                else:
                    f, w = _expert_layer(
                        bp, state[name]["moe"]["expert_bias"], a, config)
                    h = h + f
                    routed.setdefault(name, []).append(w)
            h = _rms(h, p["ln_f"], eps)
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
    delta = config["router_bias_rate"] * jnp.sign(jnp.mean(counts) - counts)
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
    heads = config["mamba_num_heads"]
    inner = heads * config["mamba_head_dim"]
    conv = inner + 2 * config["n_groups"] * config["ssm_state_size"]
    e, held, _ = _experts(config)
    return {"norm": d,
            "mamba2": d * (inner + conv + heads) + inner * d,
            "mamba2_conv": (config["conv_kernel"] + 1) * conv,     # taps and bias
            "mamba2_vectors": 3 * heads + inner,    # dt_bias, A_log, D; the gated norm's scale
            "attention": 2 * d * q + 2 * d * kv,    # q, o; k, v
            "shared": 2 * d * config["moe_shared_expert_intermediate_size"],
            "router": d * e, "experts": held * 2 * d * f}


def layer_counts(config):
    """-> {kind: layers of it} over the configuration's depth."""
    kinds = [layer_kind(config, i)
             for i in range(config["num_hidden_layers"])]
    return {k: kinds.count(k) for k in KINDS.values()}


def params_by_kind(config):
    """-> {kind: one layer's parameters, its norm included}."""
    lp = _layer_params(config)
    return {"mamba2": lp["norm"] + lp["mamba2"] + lp["mamba2_conv"]
            + lp["mamba2_vectors"],
            "attention": lp["norm"] + lp["attention"],
            "experts": lp["norm"] + lp["router"] + lp["shared"]
            + lp["experts"]}


def param_count(config, **_):
    """Parameters held (the bias is state, not a parameter): a Mamba-2
    layer's two projections, its convolution with its bias, dt_bias, A_log,
    D and the gated norm's scale; an attention layer's q, k, v, o; an expert
    layer's router over all E outputs, shared expert and held experts; every
    layer's one norm; embedding, head, final norm."""
    by_kind, n = params_by_kind(config), layer_counts(config)
    d = config["hidden_size"]
    return 2 * config["vocab_size"] * d + d \
        + sum(n[k] * by_kind[k] for k in n)


def recurrence_macs_per_token(config):
    """The recurrence's work a token and Mamba-2 layer BY THE RECURRENCE,
    whatever chunk size or kernel computes it: for each of the P x N state
    elements of each head the decay's multiply (half a multiply-add) and two
    multiply-adds (the rank-one update, h C); and a head's P features times
    delta (half) and times D (one)."""
    hp = config["mamba_num_heads"] * config["mamba_head_dim"]
    return 2.5 * hp * config["ssm_state_size"] + 1.5 * hp


def macs_per_token(config, seq_len):
    """Required forward multiply-adds for one token, by part: the Mamba-2
    layers' projections and convolution, and their recurrence; the attention
    layers' q, k, v, o projections, and attention by the keys the causal mask
    admits (two products of heads x head_dim a key); the shared expert whole;
    the router over all E outputs; the routed experts at balance over the
    share held (k x held / E experts a token, TWO d x f matmuls each); the
    head."""
    lp, n = _layer_params(config), layer_counts(config)
    e, _, _ = _experts(config)
    q = config["num_attention_heads"] * config["head_dim"]
    return {"mamba2_projections": n["mamba2"] * (lp["mamba2"]
                                                 + lp["mamba2_conv"]),
            "mamba2_recurrence": n["mamba2"]
            * recurrence_macs_per_token(config),
            "projections": n["attention"] * lp["attention"],
            "attention": n["attention"] * 2 * q * (seq_len + 1) / 2,
            "shared": n["experts"] * lp["shared"],
            "router": n["experts"] * lp["router"],
            "experts": n["experts"] * config["num_experts_per_tok"] / e
            * lp["experts"],
            "head": config["hidden_size"] * config["vocab_size"]}


def train_flops_per_sample(config, seq_len, **_):
    """Required forward+backward FLOPs for one token at sequence length
    ``seq_len``: ``macs_per_token`` times 2 FLOPs, times 3 for forward plus
    both gradients. Embedding look-ups, norms, softmax, sigmoid, top-k, the
    sort, the bias's step and the optimizer are not counted; recomputation
    (``--remat``) never is, and neither is what a chunked form of the
    recurrence computes beyond the recurrence."""
    return 3 * 2 * sum(macs_per_token(config, seq_len).values())
