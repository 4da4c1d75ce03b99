#!/usr/bin/env python
"""Generate text from a ``train_lm.py`` checkpoint.

Completes the LM surface beyond the reference (which is training-only,
SURVEY §5.7): load the newest ``model_step_<k>`` from a train dir — the
checkpoint's own config supplies the model geometry — and decode with the
fixed-length k/v cache (``models/generate.py``; the whole prefill+sample
loop is one compiled program). The byte-level LM needs no tokenizer:
prompts are UTF-8 bytes, output is decoded bytes.

    python train_lm.py --lm-corpus-file corpus.txt --train-dir ./lm ...
    python generate.py --train-dir ./lm --prompt "def train(" --n-new 256
"""

import argparse
import json
import sys


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--train-dir", required=True)
    p.add_argument("--step", type=int, default=0,
                   help="checkpoint step (0 = newest)")
    p.add_argument("--prompt", default="\n",
                   help="UTF-8 prompt text (byte-level LM: bytes are the "
                        "vocabulary)")
    p.add_argument("--n-new", type=int, default=256)
    p.add_argument("--temperature", type=float, default=0.8,
                   help="0 = greedy")
    p.add_argument("--top-k", type=int, default=40)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    import numpy as np

    from ps_pytorch_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    from ps_pytorch_tpu.config import TrainConfig
    from ps_pytorch_tpu.models.generate import generate
    from ps_pytorch_tpu.models.transformer import refuse_hybrid
    from ps_pytorch_tpu.runtime import checkpoint as ckpt
    from ps_pytorch_tpu.runtime.lm_eval import (
        build_lm_oracle, build_lm_template,
    )

    step = args.step or ckpt.latest_step(args.train_dir)
    if step is None:
        p.error(f"no model_step_<k> checkpoints in {args.train_dir}")
    with open(f"{ckpt.checkpoint_path(args.train_dir, step)}/config.json") as f:
        cfg = TrainConfig.from_json(f.read())
    try:
        refuse_hybrid(cfg.lm_arch, "generate.py")
    except ValueError as e:
        p.error(str(e))
    if cfg.lm_arch != "gpt2":
        # RoPE at the cache offset and dropless decode are not pinned by a
        # parity test yet, nor a cache for window layers or grouped-query heads;
        # serving the olmoe and smallthinker archs is a later issue.
        p.error(f"generate.py decodes lm_arch=gpt2 checkpoints; this one is "
                f"lm_arch={cfg.lm_arch} (train and evaluate it through "
                f"train_lm.py; decoding it is not built)")
    if cfg.lm_kv_heads not in (0, cfg.lm_heads) or cfg.lm_head_dim:
        p.error(f"generate.py decodes equal head counts of d / heads; this "
                f"checkpoint has lm_kv_heads={cfg.lm_kv_heads}, lm_head_dim="
                f"{cfg.lm_head_dim} (decoding grouped-query heads is not "
                f"built)")
    moe = cfg.network == "MoETransformerLM"
    template = build_lm_template(cfg)
    _, to_tree = build_lm_oracle(cfg)
    state, _, _ = ckpt.load_checkpoint(args.train_dir, step, template)
    params = to_tree(state.params)

    prompt_bytes = args.prompt.encode("utf-8")
    if not prompt_bytes:
        p.error("--prompt must be non-empty")
    if args.n_new < 1:
        p.error(f"--n-new {args.n_new} (need >= 1)")
    if args.top_k < 0:
        p.error(f"--top-k {args.top_k} (need >= 0; 0 = no truncation)")
    if max(prompt_bytes) >= cfg.lm_vocab:
        # Embed would silently clamp out-of-range ids inside jit.
        p.error(f"prompt contains byte {max(prompt_bytes)} but the "
                f"checkpoint's vocabulary is {cfg.lm_vocab}")
    if len(prompt_bytes) + args.n_new > cfg.lm_seq_len:
        p.error(f"prompt ({len(prompt_bytes)} B) + --n-new ({args.n_new}) "
                f"exceeds the checkpoint's sequence length "
                f"({cfg.lm_seq_len})")
    import jax.numpy as jnp
    prompt = jnp.asarray(
        np.frombuffer(prompt_bytes, np.uint8)[None].astype(np.int32))

    out = generate(params, prompt, n_new=args.n_new, vocab=cfg.lm_vocab,
                   d_model=cfg.lm_d_model, n_layers=cfg.lm_layers,
                   n_heads=cfg.lm_heads, max_seq_len=cfg.lm_seq_len,
                   temperature=args.temperature, top_k=args.top_k,
                   seed=args.seed,
                   n_experts=cfg.lm_experts if moe else 0,
                   moe_top_k=cfg.lm_moe_top_k)
    text = bytes(np.asarray(out[0], np.uint8)).decode("utf-8", "replace")
    print(json.dumps({"step": step, "prompt_bytes": len(prompt_bytes),
                      "generated_bytes": args.n_new}))
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
