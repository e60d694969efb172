"""The ``loader`` entry: a thread-mode ``DataLoader`` behind
``prefetch_to_device``, feeding one normalise op on the device.

Set-up decodes one epoch of stand-ins (``corpus.stand_ins``: flat images
with the pool's headers) through a loader, prefetch and consumer built
like the timed ones, then builds the pool from the seed. The window opens
as the timed loader is created, so it holds the pipeline's start: with
``num_workers`` threads taking chunks together, deliveries come in waves,
and a window that opens where the first wave starts reads whole waves.
Every epoch of the timed loader runs in the configuration's one shuffled
order, the order set-up warmed: a chunk's batched launch compiles per
mix of headers, so that order fixes the shapes (see ``reduced`` in the
configuration).
"""
from __future__ import annotations

import threading
import time

import numpy as np

from chipbench import corpus, work
from chipbench import reference as R
from chipbench.harness import checks_of, compare_images, memory_peak, sample

IMAGENET_MEAN = np.array([0.485, 0.456, 0.406], np.float32) * 255
IMAGENET_STD = np.array([0.229, 0.224, 0.225], np.float32) * 255


def _epochs(dl, n=None):
    """The loader's epochs one after another, each in epoch 0's order;
    ``n`` of them, or forever."""
    e = 0
    while n is None or e < n:
        dl.restore({"epoch": 0, "cursor": 0, "skips": dl.ledger.state()})
        yield from dl
        e += 1


def _stop_prefetch(it, gen) -> None:
    """Close the prefetch generator, wait for its producer thread, then
    close the epochs under it so the loader cancels its queued work."""
    it.close()
    deadline = time.monotonic() + 300
    while any(t.name == "prefetch-producer" and t.is_alive()
              for t in threading.enumerate()):
        if time.monotonic() > deadline:
            raise RuntimeError("prefetch producer did not stop")
        time.sleep(0.01)
    gen.close()


def run(cfg: dict, traffic: dict, seed: int, seconds: float, tracing,
        counter, t_start: float):
    import jax
    import jax.numpy as jnp
    from repro.data.loader import DataLoader, LoaderConfig, prefetch_to_device

    lcfg = LoaderConfig(batch_size=cfg["batch_size"],
                        num_workers=traffic["num_workers"],
                        mode=cfg["mode"], decode_batch=traffic["decode_batch"],
                        target_hw=tuple(cfg["target_hw"]), shuffle=True,
                        seed=cfg["shuffle_seed"])
    mean, std = jnp.asarray(IMAGENET_MEAN), jnp.asarray(IMAGENET_STD)
    consumer = jax.jit(lambda x: (x.astype(jnp.float32) - mean) / std)
    labels = np.arange(cfg["pool_images"], dtype=np.int32)

    parts = {"init_s": time.monotonic() - t_start}
    t = time.monotonic()
    shadow = [s[0] for s in corpus.stand_ins(cfg)]
    dl = DataLoader(shadow, labels, cfg=lcfg, path_name=cfg["path"])
    for b in prefetch_to_device(_epochs(dl, 1), size=cfg["device_prefetch"]):
        consumer(b["image"]).block_until_ready()
    parts["warm_s"] = time.monotonic() - t
    t = time.monotonic()
    pool = corpus.build(cfg, seed)
    parts["pool_s"] = time.monotonic() - t

    c0 = counter.snapshot()
    tracing.start()
    t0 = time.monotonic()
    setup_s = t0 - t_start
    dl = DataLoader(pool, labels, cfg=lcfg, path_name=cfg["path"])
    gen = _epochs(dl)
    it = prefetch_to_device(gen, size=cfg["device_prefetch"])
    done = []                          # (completion time, batch)
    while True:
        b = next(it)
        consumer(b["image"]).block_until_ready()
        t = time.monotonic()
        if t - t0 > seconds:
            break
        done.append((t, b))
    tracing.stop()
    c1 = counter.snapshot()
    _stop_prefetch(it, gen)
    peak = memory_peak()

    # what the window produced, to the host; device arrays dropped
    th, tw = cfg["target_hw"]
    shape = (cfg["batch_size"], th, tw, 3)
    delivered, bad_batches = {}, 0
    for _, b in done:
        img, lab = b["image"], np.asarray(b["label"])
        if (img.shape != shape or img.dtype != jnp.uint8
                or lab.shape != (shape[0],)):
            bad_batches += 1
            continue
        for j, i in enumerate(lab.tolist()):
            delivered[int(i)] = (img, j)
    in_window = [i for _, b in done for i in np.asarray(b["label"]).tolist()]
    record = {
        "kind": "loader", "setup_s": setup_s, "setup_parts": parts,
        "window_s": seconds,
        "images": len(in_window),
        "elapsed_s": (done[-1][0] - t0) if done else None,
        "setup_compile_requests": c0[0],
        "compile_requests": c1[0] - c0[0], "compiles": c1[1] - c0[1],
        "compiled": counter.names[c0[0]:c1[0]][:10],
        "skipped": dl.ledger.count, "memory_peak_bytes": peak,
        "delivery_s": [t - t0 for t, _ in done],
    }
    rng = np.random.default_rng([seed, 1])
    rare = corpus.rare_index(cfg)
    picked = sample(rng, sorted(delivered), cfg["check_images"],
                    [rare] if rare in delivered else [])
    got = [np.asarray(delivered[i][0][delivered[i][1]]) for i in picked]
    record.update(zip(("work_ops", "work_bytes"),
                      work.total_work(pool[i] for i in in_window)))
    del done, delivered, b, it, gen, dl
    want = [R.center_fit(R.decode(pool[i]), th, tw) for i in picked]
    compared = compare_images(got, want)
    checks = checks_of({"skipped": record["skipped"],
                        "bad_batches": bad_batches}, compared, cfg["limits"])
    record["sample"] = len(picked)
    record["rare_checked"] = rare in picked
    failed = (bad_batches * cfg["batch_size"] + compared["bad_images"]
              + record["skipped"])
    return record, checks, len(in_window), failed
