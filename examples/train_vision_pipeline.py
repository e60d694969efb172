"""End-to-end driver: train a ~100M-param vision transformer for a few
hundred steps, fed by the multi-worker JPEG loader — the deployment scenario
the paper's protocol exists to optimize.

The loader's worker count is AUTOTUNED on this machine first (the paper's
worker-sweep finding as a runtime feature), training checkpoints
asynchronously (model + loader state), and the script reports the achieved
loader occupancy vs step time.

Run:  PYTHONPATH=src python examples/train_vision_pipeline.py \
          [--steps 300] [--model small|100m]
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.codecs import get_decoder
from repro.common.compile_cache import use_compile_cache
from repro.data.autotune import autotune_workers
from repro.data.loader import DataLoader, LoaderConfig
from repro.jpeg.corpus import build_corpus
from repro.models import vision
from repro.models.layers import ModelContext
from repro.train.optimizer import OptimizerConfig, adamw_init, adamw_update


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--model", default="small", choices=["small", "100m"])
    ap.add_argument("--decoder", default="numpy-fast")
    ap.add_argument("--corpus", type=int, default=96)
    ap.add_argument("--ckpt", default="artifacts/ckpt_vision")
    args = ap.parse_args()

    if args.model == "100m":
        cfg = vision.ViTConfig(d_model=768, num_heads=12, num_kv_heads=12,
                               head_dim=64, d_ff=3072, num_layers=12,
                               num_classes=10)   # ~100M params
    else:
        cfg = vision.ViTConfig(d_model=192, num_heads=4, num_kv_heads=4,
                               head_dim=48, d_ff=768, num_layers=6,
                               num_classes=10)

    corpus = build_corpus(args.corpus, seed=5, num_classes=cfg.num_classes)
    decode = get_decoder(args.decoder).fn

    # 1. autotune the worker count on THIS machine (paper §4.3: worker
    # policy is CPU-generation-specific; never hardcode it).
    def factory(w):
        return DataLoader(corpus.files, corpus.labels, decode,
                          LoaderConfig(batch_size=16, num_workers=w))
    tune = autotune_workers(factory, candidates=(0, 2, 4), max_items=48)
    print(f"autotuned workers: {tune['best']} "
          f"(sweep: { {w: round(m, 1) for w, (m, s) in tune['sweep'].items()} })")

    loader = DataLoader(
        corpus.files, corpus.labels, decode,
        LoaderConfig(batch_size=16, num_workers=tune["best"],
                     shuffle=True, straggler_backup=True))

    params = vision.init(jax.random.PRNGKey(0), cfg)
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    print(f"model params: {n_params/1e6:.1f}M")
    state = {"params": params, "opt": adamw_init(params),
             "step": jnp.zeros((), jnp.int32)}
    opt_cfg = OptimizerConfig(lr=1e-3, warmup_steps=20)
    ctx = ModelContext(q_chunk=64, k_chunk=64)
    mgr = CheckpointManager(args.ckpt, keep=2)

    # resume after failure if a checkpoint exists
    step0, restored, extra = mgr.restore_latest(like=state)
    if step0 is not None:
        state = jax.tree_util.tree_map(jnp.asarray, restored)
        loader.restore(extra["loader"])
        print(f"resumed from step {step0}")

    @jax.jit
    def train_step(state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            vision.loss_fn, has_aux=True)(state["params"], batch, cfg, ctx)
        params, opt, om = adamw_update(grads, state["opt"],
                                       state["params"], state["step"],
                                       opt_cfg)
        return (dict(params=params, opt=opt, step=state["step"] + 1),
                dict(metrics, **om))

    done = int(state["step"])
    t_data = t_step = 0.0
    t0 = time.time()
    while done < args.steps:
        tb = time.time()
        for batch in loader:
            t_data += time.time() - tb
            batch = {"image": jnp.asarray(batch["image"]),
                     "label": jnp.asarray(batch["label"])}
            ts = time.time()
            state, metrics = train_step(state, batch)
            metrics["loss"].block_until_ready()
            t_step += time.time() - ts
            done += 1
            if done % 50 == 0:
                print(f"step {done:4d} loss={float(metrics['loss']):.4f} "
                      f"acc={float(metrics['acc']):.3f}")
                mgr.save_async(done, state,
                               extra={"loader": loader.state()})
            if done >= args.steps:
                break
            tb = time.time()
    mgr.wait()
    mgr.save(done, state, extra={"loader": loader.state()})
    wall = time.time() - t0
    share = (100 * t_data / (t_data + t_step)) if t_data + t_step else 0.0
    print(f"\n{done} steps in {wall:.1f}s; loader time {t_data:.1f}s, "
          f"step time {t_step:.1f}s -> input-pipeline share "
          f"{share:.0f}%")
    print("(when that share is large, the paper's loader protocol — not a "
          "single-thread decoder table — is the evidence that matters)")


if __name__ == "__main__":
    use_compile_cache()
    main()
