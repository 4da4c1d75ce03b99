"""Plain float32 reference of the GPT-2 block stack, and its FLOPs.

Written from the published architecture (Radford et al. 2019;
``openai-community/gpt2-medium`` ``config.json``): learned token and position
embeddings, ``n_layer`` pre-LayerNorm blocks of causal multi-head attention
and a 4x MLP with the tanh GELU (``gelu_new``), a final LayerNorm and a
linear head over the vocabulary.

It implements the same departures from the published block as
``ps_pytorch_tpu/models/transformer.py`` does (they are listed in
``configs/gpt2_medium.json`` under ``departures``): no bias on the q, k, v and
output projections, an ``lm_head`` that is not tied to the token embedding,
LayerNorm eps 1e-6, no dropout, and a position table with as many rows as the
run's sequence length. Independent of ``ps_pytorch_tpu/models``: it takes the
system's parameter tree only as named arrays and computes in float32 under
``highest`` matmul precision, attention as a dense masked softmax.
"""

import jax
import jax.numpy as jnp


def _ln(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def forward(variables, tokens, config):
    """variables: {"params"}; tokens: [B, S] int32; -> float32 logits
    [B, S, vocab]."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), variables["params"])
    eps = config["layer_norm_epsilon_as_run"]
    h = config["n_head"]
    with jax.default_matmul_precision("highest"):
        b, s = tokens.shape
        x = p["tok_embed"]["embedding"][tokens] + \
            p["pos_embed"]["embedding"][jnp.arange(s)][None]
        d = x.shape[-1]
        causal = jnp.tril(jnp.ones((s, s), bool))
        for i in range(config["n_layer"]):
            bp = p[f"block_{i}"]
            y = _ln(x, bp["LayerNorm_0"], eps)
            q, k, v = (
                (y @ bp[f"Dense_{j}"]["kernel"]).reshape(b, s, h, d // h)
                for j in range(3))
            att = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (d // h) ** -0.5
            att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(b, s, d)
            x = x + o @ bp["Dense_3"]["kernel"]
            y = _ln(x, bp["LayerNorm_1"], eps)
            y = _gelu_new(y @ bp["Dense_4"]["kernel"] + bp["Dense_4"]["bias"])
            x = x + y @ bp["Dense_5"]["kernel"] + bp["Dense_5"]["bias"]
        x = _ln(x, p["ln_f"], eps)
        return x @ p["lm_head"]["kernel"]


def param_count(config, seq_len):
    d, v, n = config["n_embd"], config["vocab_size"], config["n_layer"]
    block = 4 * d * d + 8 * d * d + 5 * d + 4 * d      # matmuls, MLP biases, 2 LayerNorms
    return v * d + seq_len * d + n * block + 2 * d + d * v


def train_flops_per_sample(config, seq_len, **_):
    """Required forward+backward FLOPs for one token at sequence length
    ``seq_len``: per layer 12 d^2 multiply-adds in the six projections and, for
    attention charged dense S x S as PaLM does, 2 S d in the scores and the
    weighted sum; d x vocab in the head; times 2 FLOPs, times 3 for forward
    plus both gradients. Embedding look-ups, LayerNorm, softmax and the
    optimizer are not counted; recomputation (flash attention's backward,
    ``--remat``) never is."""
    d, v, n = config["n_embd"], config["vocab_size"], config["n_layer"]
    per_token_macs = n * (12 * d * d + 2 * seq_len * d) + d * v
    return 3 * 2 * per_token_macs
