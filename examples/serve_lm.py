"""Batched LM serving demo: prefill + decode with sharded KV caches on the
host mesh, using any assigned architecture's reduced config.

Run:  PYTHONPATH=src python examples/serve_lm.py --arch qwen2-7b-smoke
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.common.compile_cache import use_compile_cache
from repro.configs import get_config
from repro.models import model
from repro.models.layers import ModelContext
from repro.serve.engine import generate


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b-smoke",
                    help="arch id; -smoke suffix for reduced configs")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=24)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    ctx = ModelContext(q_chunk=64, k_chunk=64)
    params = model.init(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1),
                                (args.batch, args.prompt_len), 0,
                                cfg.vocab_size)
    kw = {}
    if cfg.cross_attn_every:
        kw["image_embeds"] = 0.1 * jax.random.normal(
            jax.random.PRNGKey(2),
            (args.batch, cfg.num_image_tokens, cfg.d_model)
        ).astype(jnp.bfloat16)

    t0 = time.time()
    out = generate(params, prompt, cfg, ctx,
                   max_new_tokens=args.new_tokens, **kw)
    dt = time.time() - t0
    print(f"arch={cfg.name} generated {out.shape} in {dt:.2f}s "
          f"({args.batch * args.new_tokens / dt:.1f} tok/s incl. compile)")
    print("sample token ids:", out[0, :12].tolist())


if __name__ == "__main__":
    use_compile_cache()
    main()
