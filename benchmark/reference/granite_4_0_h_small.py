"""Plain float32 reference of the Granite-4.0-H-Small (``granitemoehybrid``)
stack: layers that are a mixer AND an expert half each, the mixer a Mamba-2
layer by its recurrence (B, C and the gated norm in ONE group over all heads)
or softmax attention without position encoding at the scores' own multiplier,
the expert half routed SwiGLU experts under a softmax over the chosen logits
beside one shared SwiGLU expert; a head tied to the embedding; four scalar
multipliers; its loss, its parameter count and its FLOPs, for the whole model
or for ONE chip's share of every layer.

Written from the published configuration (``ibm-granite/granite-4.0-h-small``
``config.json``, ``model_type`` ``granitemoehybrid``) and, for what that file
has no key for, from the family's modelling code (``modeling_
granitemoehybrid.py``) and the Mamba-2 paper (arXiv:2405.21060) as known; each
such point is under ``assumed`` in ``configs/granite_4_0_h_small.json``. ``N``
is RMSNorm (``x rsqrt(mean(x^2) + eps) w``); no biases but the convolution's.
With ``e`` the embedding_multiplier, ``r`` the residual_multiplier, ``a`` the
attention_multiplier and ``l`` logits_scaling:

    h = e E[ids]
    layer i:  h = h + r mixer_i(N(h; w1_i));   h = h + r (routed(m) + shared(m)),  m = N(h; w2_i)
    logits = N(h; w_f) E^T / l          (tie_word_embeddings: E the held rows)

    mamba (layer_types[i]):
        [z | xBC | dt] = u W_in          (H P, H P + 2 G N, H),  G = mamba_n_groups = 1
        xBC = silu(conv(xBC) + bias)     depthwise, causal, mamba_d_conv taps
        [x | B | C] = xBC                x: H heads of P; B, C: one group of N that EVERY head reads
        delta = softplus(dt + dt_bias)   A = -exp(A_log)        a head
        from s = 0 [P, N] a head, TOKEN BY TOKEN:
            s = exp(delta_t A) s + (delta_t x_t) B_t^T;   y_t = s C_t + D x_t
        y = rms(y * silu(z); w_y)        the gate first, then ONE norm over all H P channels
        mixer = y W_out
    attention:
        q = u Wq (heads x hd)   k = u Wk (kv x hd)   v = u Wv (kv x hd)
        query head j reads key/value head j // (heads / kv); causal; no position encoding
        mixer = concat_heads(softmax(a q k^T + mask) v) Wo          a = 1/128, NOT hd^-0.5
    expert half:
        t = m Wr over all E outputs;  I = the top-k of t;  g = softmax(t_I)   over the chosen k alone
        routed = sum_{i in I, i held} g_i down_i(silu(gate_i m) * up_i m);   shared = down(silu(gate m) * up m)

The Mamba-2 layers are the recurrence itself, one token at a time under
``lax.scan``: the system's chunked form (``ps_pytorch_tpu/ops/ssd.py``) is held
to something that is not a chunked form.

**One chip's share of every layer** (``mixer_share`` = [index, of]; [0, 1] or
no key is the uncut model). ``of`` chips share each layer, column-parallel in
and row-parallel out, and the variables handed in are ONE chip's: the
configuration's ``mamba_n_heads``, ``num_attention_heads`` and
``num_key_value_heads`` count the heads HELD (``published`` has the model's),
``in_proj`` holds their columns of z, x and dt with B and C whole, the
convolution their channels, q/k/v their columns, ``out_proj`` and ``o`` their
rows; the shared expert holds ``shared_intermediate_size / of`` channels (the
key keeps the published width); ``num_local_experts`` counts the experts held,
the router keeps ``num_local_experts_published`` outputs and its top-k. Each
sublayer's result is this chip's PART of the sum its ``of`` chips would form;
what the absent chips would add is left out, here as in the program, and that
partial sum times ``r`` goes on. The gated norm's statistic is over the held
channels alone (``held_channels``): one chip runs no all-reduce, so the
reference given a share computes what the one-chip program computes, not an
eighth of the uncut layer. ``share_of`` cuts an uncut model's variables to a
share by those rules (the tests' tie: the shares' parts, with the statistic
reduced across them, add up to the uncut layer).

Independent of ``ps_pytorch_tpu``: it takes the system's variables only as
named arrays and computes in float32 under ``highest`` matmul precision. K and
V are repeated per query head; attention is a dense masked softmax, one head
and one block of queries at a time against every key; the routed experts are a
loop over the held ones on every token with a dense weight (``g`` or 0): no
sort, no grouped matmul, no kernel. The functions a mistake can be planted in
are module attributes (``controls/granite_4_0_h_small.py`` replaces them by
name): ``embedding_multiplier``, ``attention_multiplier``,
``residual_multiplier``, ``logits_scaling``, ``gated_norm``, ``b_c_of_head``,
``positions_on``, ``chosen_gates``, ``shared_expert``, ``head_table``, and
``STATE_BITS`` (the mantissa bits the state keeps across a boundary every
``mamba_chunk_size`` tokens). Names it reads, per ``params/block_<i>``:
``RMSNorm_0``, ``RMSNorm_1``; a Mamba-2 layer's ``in_proj``, ``conv_weight``
[taps, channels], ``conv_bias``, ``dt_bias``, ``A_log``, ``D``, ``ssm_norm``,
``out_proj``; an attention layer's ``Dense_0..3`` (q, k, v, o); ``moe/router``,
``moe/experts_gate|up`` [held, d, f], ``moe/experts_down`` [held, f, d],
``shared/gate|up|down``; at the top ``tok_embed``, ``ln_f``.
"""

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024      # queries scored at a time against every key
STATE_BITS = 23         # mantissa bits the recurrence's state keeps: float32's;
#                         where fewer, rounded to them every mamba_chunk_size tokens


def _experts(config):
    """-> (router outputs E, experts held, index of the first held)."""
    held = config.get("experts_held", config["num_local_experts"])
    e = config.get("num_local_experts_published", config["num_local_experts"])
    return e, held, config.get("experts_share", 0) * held


def mixer_share(config):
    """-> (index, of): which of how many chips' share of each layer's mixers
    and shared expert the configuration (and the variables) describe."""
    index, of = config.get("mixer_share", (0, 1))
    return int(index), int(of)


def is_mamba(config, layer):
    return config["layer_types"][layer] == "mamba"


def _rms(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * p["scale"]


# ---- the four multipliers --------------------------------------------------

def embedding_multiplier(config):
    return config["embedding_multiplier"]


def attention_multiplier(config, head_dim):
    """The scores' scale: the configuration's, whatever the head's size."""
    return config["attention_multiplier"]


def residual_multiplier(config):
    return config["residual_multiplier"]


def logits_scaling(config):
    return config["logits_scaling"]


# ---- the Mamba-2 mixer ------------------------------------------------------

def causal_conv(u, weight, bias):
    """out[t] = bias + sum_j weight[j] u[t - (taps - 1) + j], zeros before
    the sequence. u: [S, channels]; weight: [taps, channels]."""
    taps, s = weight.shape[0], u.shape[0]
    padded = jnp.pad(u, ((taps - 1, 0), (0, 0)))
    return bias + sum(weight[j] * padded[j:j + s] for j in range(taps))


def b_c_of_head(t, heads):
    """B or C [S, N] of the ONE group, for every head: [S, heads, N]."""
    return jnp.broadcast_to(t[:, None, :], (t.shape[0], heads, t.shape[1]))


def recurrence(x, delta, a, b, c, d, round_every):
    """Mamba-2's recurrence token by token. x: [S, H, P]; delta: [S, H]; a, d:
    [H]; b, c: [S, H, N]. -> y [S, H, P]. ``round_every``: ``STATE_BITS``."""
    def token(state, xs):
        x_t, dt_t, b_t, c_t, t = xs
        state = state * jnp.exp(dt_t * a)[:, None, None] \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        y = jnp.einsum("hpn,hn->hp", state, c_t) + d[:, None] * x_t
        if STATE_BITS < 23:
            state = jnp.where(
                (t + 1) % round_every == 0,
                jax.lax.reduce_precision(state, exponent_bits=8,
                                         mantissa_bits=STATE_BITS), state)
        return state, y

    state = jnp.zeros((x.shape[1], x.shape[2], b.shape[2]), jnp.float32)
    return jax.lax.scan(token, state,
                        (x, delta, b, c, jnp.arange(x.shape[0])))[1]


def gated_norm(y, z, scale, eps, heads):
    """The gate FIRST, then ONE RMSNorm over all the channels handed in (the
    held ones: every head's, mamba_n_groups 1), then the scale."""
    g = y * jax.nn.silu(z)
    return g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps) \
        * scale


def _mamba2(bp, u, config):
    """The Mamba-2 mixer's contribution (this share's part) on the normed
    stream ``u``."""
    heads, p = config["mamba_n_heads"], config["mamba_d_head"]
    n = config["mamba_d_state"]
    assert config["mamba_n_groups"] == 1, "B and C are one group"
    assert config["mamba_conv_bias"] and not config["mamba_proj_bias"]
    assert bp["conv_weight"].shape[0] == config["mamba_d_conv"]
    s, inner = u.shape[0], heads * p
    zxbcdt = u @ bp["in_proj"]["kernel"]
    assert zxbcdt.shape[1] == 2 * inner + 2 * n + heads
    z, xbc, dt = (zxbcdt[:, :inner], zxbcdt[:, inner:2 * inner + 2 * n],
                  zxbcdt[:, 2 * inner + 2 * n:])
    xbc = jax.nn.silu(causal_conv(xbc, bp["conv_weight"], bp["conv_bias"]))
    x = xbc[:, :inner].reshape(s, heads, p)
    b, c = (b_c_of_head(t, heads)
            for t in (xbc[:, inner:inner + n], xbc[:, inner + n:]))
    delta = jax.nn.softplus(dt + bp["dt_bias"])
    y = recurrence(x, delta, -jnp.exp(bp["A_log"]), b, c, bp["D"],
                   config["mamba_chunk_size"])
    y = gated_norm(y.reshape(s, inner), z, bp["ssm_norm"]["scale"],
                   config["rms_norm_eps"], heads)
    return y @ bp["out_proj"]["kernel"]


# ---- the attention mixer ----------------------------------------------------

def positions_on(q, k):
    """``position_embedding_type`` nope: q and k as they are."""
    return q, k


def _attention(bp, u, config):
    """The attention mixer's contribution (this share's part) on the normed
    stream: no position encoding, no q/k norm, no gate."""
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    assert not config["attention_bias"]
    assert config["position_embedding_type"] == "nope"
    s = u.shape[0]
    hd = bp["Dense_0"]["kernel"].shape[1] // heads
    assert hd * heads * mixer_share(config)[1] == config["hidden_size"]
    by_head = lambda t, n: t.reshape(s, n, hd).transpose(1, 0, 2)
    q = by_head(u @ bp["Dense_0"]["kernel"], heads)
    k = by_head(u @ bp["Dense_1"]["kernel"], kv_heads)
    v = by_head(u @ bp["Dense_2"]["kernel"], kv_heads)
    q, k = positions_on(q, k)
    k = jnp.repeat(k, heads // kv_heads, axis=0)          # head j <- j // group
    v = jnp.repeat(v, heads // kv_heads, axis=0)
    block = min(s, QUERY_BLOCK)
    assert s % block == 0, (s, block)
    key_pos = jnp.arange(s)
    scale = attention_multiplier(config, hd)

    def head(qkv):
        qh, kh, vh = qkv                                  # each [S, hd]

        def queries(args):
            qb, first = args                              # [block, hd], its first position
            seen = (first + jnp.arange(block))[:, None] >= key_pos[None, :]
            att = qb @ kh.T * scale
            att = jax.nn.softmax(jnp.where(seen, att, -jnp.inf), axis=-1)
            return att @ vh

        out = jax.lax.map(queries, (qh.reshape(s // block, block, hd),
                                    jnp.arange(0, s, block)))
        return out.reshape(s, hd)

    o = jax.lax.map(head, (q, k, v))                      # [heads, S, hd]
    return o.transpose(1, 0, 2).reshape(s, heads * hd) @ bp["Dense_3"]["kernel"]


# ---- the expert half --------------------------------------------------------

def _swiglu(p, m):
    return (jax.nn.silu(m @ p["gate"]["kernel"]) * (m @ p["up"]["kernel"])) \
        @ p["down"]["kernel"]


def shared_expert(bp, m):
    """(This share's channels of) the shared expert, on every token."""
    return _swiglu(bp["shared"], m)


def chosen_gates(t, chosen):
    """A softmax over the chosen logits alone; 0 elsewhere. t: [S, E]."""
    return jax.nn.softmax(jnp.where(chosen, t, -jnp.inf), axis=-1)


def route(m, router, config):
    """-> weights [S, E], zeros off the top-k: the k largest logits, a
    softmax over those k."""
    t = m @ router
    kth = jax.lax.top_k(t, config["num_experts_per_tok"])[0][:, -1:]
    return chosen_gates(t, t >= kth)


def expert_half(bp, m, config):
    """-> (the expert half's contribution on the normed stream ``m``: the held
    experts' part and the shared expert's held channels; [2, E]: the
    assignments to every router output and the sum over the tokens of the
    router's softmax over ALL outputs, what ``balance`` is made of)."""
    moe = bp["moe"]
    assert config["hidden_act"] == "silu"
    assert bp["shared"]["up"]["kernel"].shape[1] * mixer_share(config)[1] \
        == config["shared_intermediate_size"]
    w = route(m, moe["router"]["kernel"], config)
    _, held, first = _experts(config)

    def expert(f, x):
        w_gate, w_up, w_down, w_e = x                     # one expert's, w_e [S]
        return f + w_e[:, None] * ((jax.nn.silu(m @ w_gate) * (m @ w_up))
                                   @ w_down), None

    f = jax.lax.scan(expert, shared_expert(bp, m),
                     (moe["experts_gate"], moe["experts_up"],
                      moe["experts_down"], w[:, first:first + held].T))[0]
    probs = jax.nn.softmax(m @ moe["router"]["kernel"], axis=-1)
    return f, jnp.stack([jnp.sum(w > 0, axis=0).astype(jnp.float32),
                         jnp.sum(probs, axis=0)])


def balance(sums, tokens):
    """A layer's load-balance term over ``tokens`` tokens: E sum_e f_e P_e,
    f_e the assignments to output e a token (sum_e f_e = k), P_e the mean
    router probability: HF's ``load_balancing_loss_func``."""
    return sums.shape[-1] * jnp.sum(sums[0] * sums[1]) / tokens ** 2


# ---- the stack --------------------------------------------------------------

def head_table(p):
    """The head's matrix [V, d]: the embedding (tie_word_embeddings)."""
    return p["tok_embed"]["embedding"]


def mixer(bp, u, config, layer):
    return _mamba2(bp, u, config) if is_mamba(config, layer) \
        else _attention(bp, u, config)


def _layer(bp, h, config, layer):
    """One block on one sequence h [S, d]; -> (h, ``expert_half``'s sums)."""
    eps, r = config["rms_norm_eps"], residual_multiplier(config)
    h = h + r * mixer(bp, _rms(h, bp["RMSNorm_0"], eps), config, layer)
    f, sums = expert_half(bp, _rms(h, bp["RMSNorm_1"], eps), config)
    return h + r * f, sums


def _forward(variables, tokens, config):
    """-> (logits [B, S, V], the load-balance term: the layers' mean)."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), variables["params"])
    assert config["tie_word_embeddings"]
    assert config["normalization_function"] == "rmsnorm"
    with jax.default_matmul_precision("highest"):
        rows, sums = [], 0.0
        for b in range(tokens.shape[0]):
            h = embedding_multiplier(config) \
                * p["tok_embed"]["embedding"][tokens[b]]
            per_layer = []
            for i in range(config["num_hidden_layers"]):
                h, layer_sums = _layer(p[f"block_{i}"], h, config, i)
                per_layer.append(layer_sums)
            sums = sums + jnp.stack(per_layer)          # [layers, 2, E]
            h = _rms(h, p["ln_f"], config["rms_norm_eps"])
            rows.append(h @ head_table(p).T / logits_scaling(config))
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


# ---- a share of an uncut model ------------------------------------------------

def share_config(config, index, of):
    """An UNCUT configuration (``mixer_share`` [0, 1]) as share ``index`` of
    ``of`` of each layer's mixers and shared expert; the experts as they
    were."""
    assert mixer_share(config) == (0, 1)
    cut = {k: config[k] // of for k in ("mamba_n_heads",
                                        "num_attention_heads",
                                        "num_key_value_heads")}
    assert all(cut[k] * of == config[k] for k in cut)
    return dict(config, **cut, mixer_share=[index, of])


def share_of(params, config, index, of):
    """An UNCUT model's ``params`` cut to share ``index`` of ``of`` by the
    module's rules: the held heads' columns of z, x, dt (B and C whole) and
    their convolution channels, dt_bias, A_log, D and norm scale; their rows
    of out_proj; the held heads' q/k/v columns and o rows; the shared
    expert's held channels. Norms, router, experts, embedding: as they are."""
    heads, p, n = (config["mamba_n_heads"], config["mamba_d_head"],
                   config["mamba_d_state"])
    inner = heads * p
    cut = lambda a, width, axis: jax.lax.slice_in_dim(
        a, index * width // of, (index + 1) * width // of, axis=axis)

    def xbc_dt(a, axis, with_z_dt):
        """[z | x | B | C | dt] (or [x | B | C]) along ``axis``."""
        at, parts = 0, []
        for width, share in ((inner, True),) * (2 if with_z_dt else 1) \
                + ((n, False), (n, False)) \
                + (((heads, True),) if with_z_dt else ()):
            part = jax.lax.slice_in_dim(a, at, at + width, axis=axis)
            parts.append(cut(part, width, axis) if share else part)
            at += width
        assert at == a.shape[axis]
        return jnp.concatenate(parts, axis=axis)

    def block(bp, layer):
        bp = dict(bp)
        if is_mamba(config, layer):
            bp["in_proj"] = {"kernel": xbc_dt(bp["in_proj"]["kernel"], 1,
                                              True)}
            bp["conv_weight"] = xbc_dt(bp["conv_weight"], 1, False)
            bp["conv_bias"] = xbc_dt(bp["conv_bias"], 0, False)
            for name in ("dt_bias", "A_log", "D"):
                bp[name] = cut(bp[name], heads, 0)
            bp["ssm_norm"] = {"scale": cut(bp["ssm_norm"]["scale"], inner, 0)}
            bp["out_proj"] = {"kernel": cut(bp["out_proj"]["kernel"], inner,
                                            0)}
        else:
            for name in ("Dense_0", "Dense_1", "Dense_2"):
                k = bp[name]["kernel"]
                bp[name] = {"kernel": cut(k, k.shape[1], 1)}
            k = bp["Dense_3"]["kernel"]
            bp["Dense_3"] = {"kernel": cut(k, k.shape[0], 0)}
        sh = bp["shared"]
        width = sh["up"]["kernel"].shape[1]
        bp["shared"] = {"gate": {"kernel": cut(sh["gate"]["kernel"], width, 1)},
                        "up": {"kernel": cut(sh["up"]["kernel"], width, 1)},
                        "down": {"kernel": cut(sh["down"]["kernel"], width, 0)}}
        return bp

    return {k: block(v, int(k.split("_")[1])) if k.startswith("block_") else v
            for k, v in params.items()}


# ---- parameters and FLOPs ------------------------------------------------------

def _layer_params(config):
    """One layer's parameters AS HELD (the configuration's share), by part."""
    d, f = config["hidden_size"], config["intermediate_size"]
    of = mixer_share(config)[1]
    hd = d // (config["num_attention_heads"] * of)
    q = config["num_attention_heads"] * hd
    kv = config["num_key_value_heads"] * hd
    heads, n = config["mamba_n_heads"], config["mamba_d_state"]
    inner = heads * config["mamba_d_head"]
    conv = inner + 2 * n
    e, held, _ = _experts(config)
    return {"norms": 2 * d,
            "mamba2": d * (inner + conv + heads) + inner * d,
            "mamba2_conv": (config["mamba_d_conv"] + 1) * conv,     # taps and bias
            "mamba2_vectors": 3 * heads + inner,    # dt_bias, A_log, D; the gated norm's scale
            "attention": 2 * d * q + 2 * d * kv,    # q, o; k, v
            "shared": 3 * d * (config["shared_intermediate_size"] // of),
            "router": d * e, "experts": held * 3 * d * f}


def layer_counts(config):
    """-> {"mamba": layers of it, "attention": ...} over the depth."""
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    return {k: kinds.count(k) for k in ("mamba", "attention")}


def params_by_kind(config):
    """-> {kind of layer: one layer's parameters, both halves and both norms;
    "expert_half": the part of it outside the mixer}."""
    lp = _layer_params(config)
    half = lp["norms"] // 2 + lp["router"] + lp["shared"] + lp["experts"]
    return {"mamba": lp["norms"] // 2 + lp["mamba2"] + lp["mamba2_conv"]
            + lp["mamba2_vectors"] + half,
            "attention": lp["norms"] // 2 + lp["attention"] + half,
            "expert_half": half}


def param_count(config, **_):
    """Parameters held: every layer's mixer (its share), two norms, router
    over all E outputs, shared expert (its share) and held experts; the tied
    embedding's held rows once; the final norm."""
    by_kind, n = params_by_kind(config), layer_counts(config)
    d = config["hidden_size"]
    return config["vocab_size"] * d + d + sum(n[k] * by_kind[k] for k in n)


def recurrence_macs_per_token(config):
    """The recurrence's work a token and Mamba-2 layer BY THE RECURRENCE,
    whatever chunk size or kernel computes it, over the heads held: for each
    of the P x N state elements of each head the decay's multiply (half a
    multiply-add) and two multiply-adds (the rank-one update, s C); and a
    head's P features times delta (half) and times D (one)."""
    hp = config["mamba_n_heads"] * config["mamba_d_head"]
    return 2.5 * hp * config["mamba_d_state"] + 1.5 * hp


def macs_per_token(config, seq_len):
    """Required forward multiply-adds for one token BY THE SHARE HELD, by
    part: the Mamba-2 layers' projections and convolution, and their
    recurrence; the attention layers' q, k, v, o projections, and attention
    by the keys the causal mask admits (two products of held heads x head_dim
    a key); the shared expert's held channels; the router over all E outputs;
    the routed experts at balance over the share held (k x held / E experts a
    token, three d x f matmuls each); the head over the held rows."""
    lp, n = _layer_params(config), layer_counts(config)
    e, _, _ = _experts(config)
    layers = n["mamba"] + n["attention"]
    d = config["hidden_size"]
    q = config["num_attention_heads"] * (
        d // (config["num_attention_heads"] * mixer_share(config)[1]))
    return {"mamba2_projections": n["mamba"] * (lp["mamba2"]
                                                + lp["mamba2_conv"]),
            "mamba2_recurrence": n["mamba"]
            * recurrence_macs_per_token(config),
            "projections": n["attention"] * lp["attention"],
            "attention": n["attention"] * 2 * q * (seq_len + 1) / 2,
            "shared": layers * lp["shared"],
            "router": layers * lp["router"],
            "experts": layers * config["num_experts_per_tok"] / e
            * lp["experts"],
            "head": d * config["vocab_size"]}


def train_flops_per_sample(config, seq_len, **_):
    """Required forward+backward FLOPs for one token at sequence length
    ``seq_len``: ``macs_per_token`` times 2 FLOPs, times 3 for forward plus
    both gradients. Embedding look-ups, norms, the multipliers, softmax,
    top-k, the sort and the optimizer are not counted; recomputation
    (``--remat``) never is, and neither is what a chunked form of the
    recurrence computes beyond the recurrence."""
    return 3 * 2 * sum(macs_per_token(config, seq_len).values())
