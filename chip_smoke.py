#!/usr/bin/env python3
"""Smoke run of the decode system's main path on one TPU chip.

    python chip_smoke.py

Seven phases, all in this one process (a chip belongs to one process at a
time), through the entry points a user calls:

1. device: JAX must find a TPU; anywhere else the script exits non-zero
   and prints no result line;
2. corpus: 64 ImageNet-val-sized JPEGs built from a seed (default quality
   pool, 4:2:0-dominant sampling, one Adobe-YCCK image);
3. reference: every image decoded by ``numpy-ref`` on the host;
4. device paths: ``jnp-fused``, ``jnp-batch``, ``pallas-fused``,
   ``pallas-batch`` and ``strict-pallas`` through ``open_decoder``, every
   image checked against the reference. The ``*-batch`` paths decode
   micro-batches of 8; the others decode image by image, so every kernel
   and both jitted transforms run. ``strict-pallas`` must skip exactly the
   YCCK image;
5. service: ``DecodeService`` with only device arms serves four client
   threads of 32 Zipf-drawn requests each;
6. loader: ``DataLoader`` in thread mode on ``pallas-batch`` behind
   ``prefetch_to_device``; its batches must live on the chip;
7. fork: one epoch of the process-mode ``numpy-fast`` loader, forked from
   this chip-holding process, under a hard timeout.

Any mismatch, failed request or exception exits non-zero. The last line of
standard output is then ``{"ok": true, "device": {...}}`` with the device
as JAX reports it. Per-phase wall times and compile counts are printed on
earlier lines; they are smoke observations, not measurements.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.abspath(__file__))
if os.path.join(ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "src"))
# libtpu would otherwise write its logs under /tmp, outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")

SEED = 0
N_IMAGES = 64
SIZES = [(375, 500), (500, 375), (333, 500), (500, 333), (500, 500)]
TOL, TOL_RARE = 4, 16           # max abs error vs numpy-ref (tests/test_jpeg)
DEVICE_PATHS = ("jnp-fused", "jnp-batch", "pallas-fused", "pallas-batch",
                "strict-pallas")
MICRO_BATCH = 8
SERVICE_PATHS = ("pallas-batch", "jnp-batch")
CLIENTS, REQUESTS_PER_CLIENT, CLIENT_WINDOW = 4, 32, 8
REQUEST_TIMEOUT_S = 600.0
LOADER_BATCH, LOADER_BATCHES = 16, 3
FORK_TIMEOUT_S = 120.0

# JAX records this duration event once per backend compile request
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def log(**fields) -> None:
    print(json.dumps(fields), flush=True)


def check(ok, *what) -> None:
    """A failed check ends the run (unlike ``assert``, also under -O)."""
    if not ok:
        raise AssertionError(what)


class CompileCounter:
    """Backend compiles and persistent-cache hits seen by jax.monitoring."""

    def __init__(self):
        self._lock = threading.Lock()
        self.compiles = 0
        self.cache_hits = 0

    def on_duration(self, event, duration, **kw):
        if event == BACKEND_COMPILE_EVENT:
            with self._lock:
                self.compiles += 1

    def on_event(self, event, **kw):
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits += 1

    def snapshot(self):
        with self._lock:
            return self.compiles, self.cache_hits


def phase(name, counter, fn, *args):
    """Run one phase and log its wall time and compile counts."""
    c0, h0 = counter.snapshot()
    t0 = time.perf_counter()
    out = fn(*args)
    c1, h1 = counter.snapshot()
    log(phase=name, wall_s=time.perf_counter() - t0, compiles=c1 - c0,
        cache_hits=h1 - h0)
    return out


# ------------------------------------------------------------------ phases
def require_tpu() -> dict:
    """Phase 1: a TPU or nothing. Never falls back to the CPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (default device "
                         f"platform is {devices[0].platform!r})")
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def versions() -> dict:
    from importlib import metadata
    out = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            out[pkg] = "not installed"
    return out


def assert_mosaic() -> None:
    """The Pallas kernels lower through Mosaic, not the interpreter."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.kernels.decode_batch import TILE_N
    check(not ops._interpret(), "Pallas kernels would run in interpret mode")
    text = jax.jit(ops.decode_batch).lower(
        jnp.zeros((TILE_N, 64), jnp.float32),
        jnp.zeros((TILE_N,), jnp.int32),
        jnp.ones((3, 64), jnp.float32)).compile().as_text()
    check("tpu_custom_call" in text, "decode_batch has no Mosaic kernel")


def build():
    from repro.jpeg import parser
    from repro.jpeg.corpus import build_corpus, scaled_rare_index
    corpus = build_corpus(N_IMAGES, seed=SEED, sizes=SIZES)
    check(corpus.rare_index == scaled_rare_index(N_IMAGES), corpus.rare_index)
    check(len(parser.parse(corpus.files[corpus.rare_index]).components) == 4,
          "rare image is not 4-component")
    return corpus


def reference(corpus):
    from repro.codecs import open_decoder
    with open_decoder("numpy-ref") as dec:
        outs = [dec.decode(f) for f in corpus.files]
    bad = [i for i, o in enumerate(outs) if not o.ok]
    check(not bad, "numpy-ref failed", bad)
    return [o.image for o in outs]


def max_error(img, ref, i, rare, what) -> int:
    """Max abs pixel error of one image, held to the reference bound."""
    import numpy as np
    check(img.shape == ref.shape and img.dtype == np.uint8,
          what, i, img.shape, img.dtype)
    err = int(np.abs(img.astype(np.int32) - ref.astype(np.int32)).max())
    check(err <= (TOL_RARE if i == rare else TOL), what, i, err)
    return err


def device_paths(corpus, refs, counter):
    from repro.codecs import DecodeOutcome, open_decoder
    assert_mosaic()
    files, rare = corpus.files, corpus.rare_index
    for name in DEVICE_PATHS:
        c0, _ = counter.snapshot()
        t0 = time.perf_counter()
        with open_decoder(name) as dec:
            batched = name.endswith("-batch")
            if batched:
                outs = []
                for k in range(0, len(files), MICRO_BATCH):
                    outs += dec.decode_batch(files[k:k + MICRO_BATCH])
            else:
                outs = [dec.decode(f) for f in files]
            strict = dec.caps.strict
        errors = [(i, o.error) for i, o in enumerate(outs)
                  if o.kind == DecodeOutcome.ERROR]
        check(not errors, name, errors)
        skips = [i for i, o in enumerate(outs) if o.kind == DecodeOutcome.SKIP]
        check(skips == ([rare] if strict else []), name, skips)
        errs = {i: max_error(o.image, refs[i], i, rare, name)
                for i, o in enumerate(outs) if o.ok}
        c1, _ = counter.snapshot()
        log(path=name, mode=f"batch{MICRO_BATCH}" if batched else "single",
            max_err=max(e for i, e in errs.items() if i != rare),
            max_err_rare=errs.get(rare), skips=skips,
            wall_s=time.perf_counter() - t0, compiles=c1 - c0)


def service(corpus, refs):
    from repro.codecs import get_decoder
    from repro.jpeg.corpus import zipf_indices
    from repro.service import DecodeService, ServiceConfig
    files, rare = corpus.files, corpus.rare_index
    svc = DecodeService(ServiceConfig(num_workers=2, max_batch=8,
                                      cache_bytes=0),
                        paths=[get_decoder(n) for n in SERVICE_PATHS])

    def client(c):
        idxs = [int(i) for i in zipf_indices(len(files), REQUESTS_PER_CLIENT,
                                             seed=SEED + c)]
        got = []
        for k in range(0, len(idxs), CLIENT_WINDOW):
            chunk = idxs[k:k + CLIENT_WINDOW]
            futs = [svc.submit(files[i], client=f"client-{c}")
                    for i in chunk]
            got += [(i, f.result(timeout=REQUEST_TIMEOUT_S))
                    for i, f in zip(chunk, futs)]
        return got

    with svc, ThreadPoolExecutor(CLIENTS) as pool:
        results = [r for f in [pool.submit(client, c) for c in range(CLIENTS)]
                   for r in f.result()]
    for i, img in results:
        max_error(img, refs[i], i, rare, "service")
    st = svc.stats()["service"]
    n = CLIENTS * REQUESTS_PER_CLIENT
    check(len(results) == n, len(results))
    check(st["failed"] == 0 and st["shed"] == 0 and st["completed"] == n, st)
    check(set(st["path_hits"]) <= set(SERVICE_PATHS), st["path_hits"])
    check(sum(st["path_hits"].values()) == n, st["path_hits"])
    log(service_path_hits=st["path_hits"], completed=st["completed"],
        failed=st["failed"])


def loader(corpus, refs):
    import itertools

    import jax
    import numpy as np
    from repro.data.loader import (DataLoader, LoaderConfig, center_fit,
                                   prefetch_to_device)
    cfg = LoaderConfig(batch_size=LOADER_BATCH, num_workers=2,
                       mode="thread", decode_batch=16, target_hw=(224, 224))
    dl = DataLoader(corpus.files, corpus.labels, cfg=cfg,
                    path_name="pallas-batch")
    it = prefetch_to_device(iter(dl), size=2)
    batches = list(itertools.islice(it, LOADER_BATCHES))
    it.close()
    check(len(batches) == LOADER_BATCHES, len(batches))
    device = jax.devices()[0]
    for k, batch in enumerate(batches):
        img = batch["image"]
        check(isinstance(img, jax.Array), type(img))
        check(img.devices() == {device}, img.devices())
        check(img.shape == (LOADER_BATCH, 224, 224, 3), img.shape)
        host = np.asarray(img)
        for j in range(LOADER_BATCH):
            i = k * LOADER_BATCH + j
            max_error(host[j], center_fit(refs[i], 224, 224), i,
                      corpus.rare_index, "loader")


def forked_loader(corpus, refs):
    import numpy as np
    from repro.data.loader import DataLoader, LoaderConfig, center_fit
    # the bench's process-mode loader cell: fork-safe numpy workers only
    cfg = LoaderConfig(batch_size=16, num_workers=2, mode="process")
    dl = DataLoader(corpus.files, corpus.labels, cfg=cfg,
                    path_name="numpy-fast")
    box = {}

    def epoch():
        try:
            box["batches"] = [b["image"] for b in dl]
        except Exception as e:        # raised in the main thread below
            box["error"] = e

    t = threading.Thread(target=epoch, name="fork-epoch", daemon=True)
    t.start()
    t.join(FORK_TIMEOUT_S)
    dl.close()
    if t.is_alive():
        raise TimeoutError(f"process-mode loader epoch did not finish in "
                           f"{FORK_TIMEOUT_S} s")
    if "error" in box:
        raise box["error"]
    images = np.concatenate(box["batches"])
    check(len(images) == len(corpus.files), len(images))
    check(dl.ledger.count == 0, dl.ledger.state())
    th, tw = cfg.target_hw
    for i, img in enumerate(images):
        max_error(img, center_fit(refs[i], th, tw), i, corpus.rare_index,
                  "fork")


# ------------------------------------------------------------------ main
def main() -> int:
    import jax
    device = require_tpu()
    log(phase="device", **device, **versions())
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter.on_duration)
    jax.monitoring.register_event_listener(counter.on_event)
    try:
        corpus = phase("corpus", counter, build)
        refs = phase("reference", counter, reference, corpus)
        phase("device_paths", counter, device_paths, corpus, refs, counter)
        phase("service", counter, service, corpus, refs)
        phase("loader", counter, loader, corpus, refs)
        phase("fork", counter, forked_loader, corpus, refs)
    finally:
        jax.monitoring.unregister_event_duration_listener(counter.on_duration)
        jax.monitoring.unregister_event_listener(counter.on_event)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    from repro.common.compile_cache import use_compile_cache
    use_compile_cache()
    sys.exit(main())
