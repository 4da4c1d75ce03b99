"""The ``gpt2`` arch (learned positions, pre-LayerNorm blocks, a 4x GELU MLP,
an untied head) against its plain reference
``benchmark/reference/gpt2_medium.py`` at a tiny size: the common suite
(``tests/arch_suite.py``), and the two oldest archs' trees, logits and
gradients, in both LM classes, against golden bytes taken before the later
archs landed: what every arch added since has left alone. (The ``gpt2`` rows'
tree and logits are PR 25's parent's too, 7e40887.)"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import arch_suite as suite
from ps_pytorch_tpu.models.moe import MoETransformerLM
from ps_pytorch_tpu.models.transformer import TransformerLM

# The tiny preset: d=64 in 4 heads of 16, 2 layers, S=32, vocab 97: in the
# reference's (the published config's) keys.
CASE = suite.ArchCase(
    arch="gpt2", parallelism="sp", config="gpt2_medium",
    tiny=dict(n_embd=64, n_layer=2, n_head=4, vocab_size=97, n_positions=32,
              n_ctx=32),
    flags=dict(lm_d_model=64, lm_layers=2, lm_heads=4, lm_vocab=97,
               lm_seq_len=32),
    logit_tol=1e-4,
    tol_reason="float32 both sides, only the order of reductions differs: "
               "measured 3e-6 on logits up to 3",
    scopes=suite.LM_SCOPES | {"ffn"}, another_depth=1)

suite.install(globals(), CASE)


# Taken from PR 29's parent commit (f608fe3) with /root/scratch/golden.py's
# recipe, given in the test below; the bytes on this container's CPU backend.
# ``olmoe_moe`` was taken again in PR 38 from the new code: the dropless layer
# adds a token's k rows in another order (3e-7 of the parent's logits, 6e-7 of
# its gradients; ``tests/dropless_plain.py`` holds the parent's form and the
# tests beside it hold the layer to it). The other three rows are unedited.
GOLDEN = {
    "gpt2_dense": ("3be40a762d856dc549057dc3ed6e97db992f221f77d36cc1589463d83b3f14e4", 29,
                   "d782c65fce5679c9300791f7642e74c11adf5d22dbe02839a0b65168516352d7",
                   "f126957a5e7cab3eb9b48cc830687d5631886a5c38e546997ce52003b4359dac"),
    "gpt2_moe": ("f1122c029a32e174e9d178ecc63412746fbffdc1727dbbbe83b421cc37c5f1de", 31,
                 "77787e920386921c41f0237880906242845f371b6328107671fc794f0c92ee6d",
                 "f05bbe878f110b6731108557d226926deead65c6a0e1d84660dc3c8cc0c6d447"),
    "olmoe_dense": ("5355e4f2818573bf3ba245c7226c39f3ac2205422a0210b4f2386126cca130db", 27,
                    "85481556b45fdb9360fe921d1fb0fcd7a9f4454fcbec42a6ebc2b9a11e3a2568",
                    "5b95ac6bafd75f9a4a9fb14c569daf1d0a41747e2f22ac251e3aefe94e036aba"),
    "olmoe_moe": ("77b2683f8475c0519f84a406291b689e21152ad8dc5e40bf1c68139d1f09adaf", 27,
                  "94a34c2b7ef8697010c307af958d181d1b7aa93ddf6b95758415c69738d8d839",
                  "581e68b6e8e6b6c72babf8b9e099e3c7dd162a42d1104702393a262c70bbf97e"),
}


def _sha(arrays):
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.asarray(a, np.float32).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("family", sorted(GOLDEN))
def test_the_older_archs_are_the_parents_bit_for_bit(family):
    """``gpt2`` and ``olmoe``, dense and MoE class: the parameter tree (names,
    order, shapes), the logits and the gradients of ``1e-3 * sum(logits^2)``
    (+ the routing terms the MoE class returns) are the parent commit's
    bytes: ``init(key(0))`` on tokens ``default_rng(7).integers(0, 97, (2,
    32))``, vocab 97, 2 layers, 4 heads, d=64, S=32; the olmoe rows with
    ffn_dim 32, the MoE class with 8 experts top-2 (gpt2) or top-4 (olmoe).
    (``olmoe_moe``: PR 38's bytes, as the comment over ``GOLDEN`` says.)"""
    arch, cls = family.split("_")
    kw = dict(vocab_size=97, n_layers=2, n_heads=4, d_model=64, max_seq_len=32)
    if arch == "olmoe":
        kw.update(arch="olmoe", ffn_dim=32)
    model = TransformerLM(**kw) if cls == "dense" else MoETransformerLM(
        n_experts=8, top_k=2 if arch == "gpt2" else 4, **kw)
    tokens = jnp.asarray(
        np.random.default_rng(7).integers(0, 97, (2, 32)), jnp.int32)
    params = model.init(jax.random.key(0), tokens)["params"]

    def loss(p):
        out = model.apply({"params": p}, tokens)
        logits, extra = out if isinstance(out, tuple) else (out, 0.0)
        if isinstance(extra, dict):
            extra = extra["aux"] + extra["z_loss"]
        return jnp.sum(logits.astype(jnp.float32) ** 2) * 1e-3 + extra

    out = model.apply({"params": params}, tokens)
    logits = out[0] if isinstance(out, tuple) else out
    paths = sorted(jax.tree_util.keystr(p) + str(tuple(a.shape)) for p, a in
                   jax.tree_util.tree_flatten_with_path(params)[0])
    tree_sha, n_leaves, logits_sha, grads_sha = GOLDEN[family]
    assert len(paths) == n_leaves
    assert hashlib.sha256("\n".join(paths).encode()).hexdigest() == tree_sha
    assert _sha([logits]) == logits_sha
    assert _sha(jax.tree.leaves(jax.grad(loss)(params))) == grads_sha
