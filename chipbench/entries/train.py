"""The ``train`` entry: a training input pipeline. The ``loader`` entry's
thread-mode ``DataLoader`` with per-sample RandomResizedCrop and flip: the
loader draws each sample's crop box at collate and pads the decoded images
into one uint8 batch, ``prefetch_to_device`` puts it on the device, and the
consumer resamples it (``augment.resample``: one ``rrc_resample`` launch
per batch) and normalises the 224x224 batch in one op.

Set-up runs one epoch of stand-ins through the same augmentation, so the
resample's one program and every decode shape are compiled before the
window. As in the ``loader`` entry, every epoch of the timed loader is
epoch 0 again, so each epoch repeats its order and its crops. Crops are
drawn from the run seed. The check recomputes 48 delivered crops with
``reference_train.py``: the box the batch carried and its pixels.

The record holds the ``loader`` entry's numbers, the decode work of the
delivered images among them, and the resample's: the work of every batch
resampled in the window (``resample_work.py``) and, traced, the device
time of every ``rrc_resample`` op in the window, summed over all of them
(``breakdown.device_ops`` keeps only the costliest ten ops).
"""
from __future__ import annotations

import time

import numpy as np

from chipbench import corpus, reduce, resample_work, work
from chipbench import reference_train as RT
from chipbench.entries.loader import (IMAGENET_MEAN, IMAGENET_STD, _epochs,
                                      _stop_prefetch)
from chipbench.harness import checks_of, compare_images, memory_peak, sample


def _op_device_s(tracing, name: str):
    """Device seconds of the ops whose name holds ``name`` in the traced
    window, every such op counted; none untraced."""
    if not tracing.on:
        return None
    profile = reduce.load_trace(tracing.log_dir)
    lo = (tracing.t0 - tracing.t_before) * 1e9
    hi = (tracing.t1 - tracing.t_before) * 1e9
    ops = reduce.top_ops(reduce.device_events(profile), lo, hi, n=None)
    return sum(s for op, s in ops if name in op)


def run(cfg: dict, traffic: dict, seed: int, seconds: float, tracing,
        counter, t_start: float):
    t_entry = time.monotonic()
    import jax
    import jax.numpy as jnp
    from repro.data.augment import RandomResizedCrop, resample
    from repro.data.loader import DataLoader, LoaderConfig, prefetch_to_device

    aug = cfg["augment"]
    size = tuple(cfg["target_hw"])
    spec = RandomResizedCrop(scale=tuple(aug["scale"]),
                             ratio=tuple(aug["ratio"]), flip=aug["flip"],
                             seed=seed, pad_hw=tuple(cfg["pad_hw"]))
    lcfg = LoaderConfig(batch_size=cfg["batch_size"],
                        num_workers=traffic["num_workers"],
                        mode=cfg["mode"], decode_batch=traffic["decode_batch"],
                        target_hw=size, shuffle=True,
                        seed=cfg["shuffle_seed"], augment=spec)
    mean, std = jnp.asarray(IMAGENET_MEAN), jnp.asarray(IMAGENET_STD)
    consumer = jax.jit(lambda x: (x.astype(jnp.float32) - mean) / std)
    labels = np.arange(cfg["pool_images"], dtype=np.int32)

    # init_s splits at the entry's call: the process's start, JAX's and
    # the TPU's (before), and the program's imports and first arrays
    parts = {"init_s": time.monotonic() - t_start,
             "before_entry_s": t_entry - t_start}
    t = time.monotonic()
    shadow = [s[0] for s in corpus.stand_ins(cfg)]
    dl = DataLoader(shadow, labels, cfg=lcfg, path_name=cfg["path"])
    for b in prefetch_to_device(_epochs(dl, 1), size=cfg["device_prefetch"]):
        consumer(resample(b, size)["image"]).block_until_ready()
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
    crops = []                         # of every batch resampled
    while True:
        b = resample(next(it), size)
        crops.append(b["crop"])
        consumer(b["image"]).block_until_ready()
        t = time.monotonic()
        if t - t0 > seconds:
            break
        done.append((t, b))
    tracing.stop()
    c1 = counter.snapshot()
    _stop_prefetch(it, gen)
    resample_s = _op_device_s(tracing, "rrc_resample")
    peak = memory_peak()

    # what the window produced, to the host; device arrays dropped
    n = cfg["batch_size"]
    delivered, bad_batches = {}, 0
    for _, b in done:
        img = b["image"]
        lab, crop = np.asarray(b["label"]), np.asarray(b["crop"])
        if (img.shape != (n, *size, 3) or img.dtype != jnp.uint8
                or lab.shape != (n,) or crop.shape != (n, 5)):
            bad_batches += 1
            continue
        for j, i in enumerate(lab.tolist()):
            delivered[int(i)] = (img, j, tuple(crop[j].tolist()))
    in_window = [i for _, b in done for i in np.asarray(b["label"]).tolist()]
    record = {
        "kind": "loader", "setup_s": setup_s, "setup_parts": parts,
        "window_s": seconds, "batch_size": n,
        "images": len(in_window),
        "elapsed_s": (done[-1][0] - t0) if done else None,
        "setup_compile_requests": c0[0],
        "compile_requests": c1[0] - c0[0], "compiles": c1[1] - c0[1],
        "compiled": counter.names[c0[0]:c1[0]][:10],
        "skipped": dl.ledger.count, "memory_peak_bytes": peak,
        "delivery_s": [t - t0 for t, _ in done],
    }
    record.update(zip(("work_ops", "work_bytes"),
                      work.total_work(pool[i] for i in in_window)))
    record.update(zip(("resample_ops", "resample_bytes"),
                      resample_work.total_work(
                          np.concatenate([np.asarray(c) for c in crops]),
                          size[0])))
    record["resample_device_s"] = resample_s
    rng = np.random.default_rng([seed, 1])
    rare = corpus.rare_index(cfg)
    picked = sample(rng, sorted(delivered), cfg["check_images"],
                    [rare] if rare in delivered else [])
    got = [np.asarray(delivered[i][0][delivered[i][1]]) for i in picked]
    got_boxes = [delivered[i][2] for i in picked]
    del done, delivered, b, crops, it, gen, dl
    want_boxes, want = [], []
    for i in picked:
        box, img = RT.expected(pool[i], aug, seed, 0, i, size)
        want_boxes.append(box)
        want.append(img)
    compared = compare_images(got, want)
    bad_crops = sum(g != w for g, w in zip(got_boxes, want_boxes))
    checks = checks_of({"skipped": record["skipped"],
                        "bad_batches": bad_batches, "bad_crops": bad_crops},
                       compared, cfg["limits"])
    record["sample"] = len(picked)
    record["rare_checked"] = rare in picked
    failed = (bad_batches * n + compared["bad_images"] + record["skipped"])
    return record, checks, len(in_window), failed
