"""Storage-backed training input: ingest -> shard loader -> exact resume.

Walks the full ``repro.store`` loop on a small synthetic corpus:

1. ingest the corpus into crc32'd shards + manifest (``ShardWriter`` via
   ``jpeg.corpus.write_corpus_shards``);
2. stream it through the ``DataLoader`` with forked process workers that
   reopen the shards *by path* (no corpus bytes cross the pool
   boundary) and a window-shuffle sampler;
3. checkpoint mid-epoch with ``CheckpointManager`` and restore into a
   fresh loader — the remainder of the epoch replays exactly;
4. print the memory-vs-shard throughput pair, i.e. the protocol axis the
   bench sweep measures as ``loader/<path>/wN/<mode>[/shard]``.

Run:  PYTHONPATH=src python examples/storage_loader.py
"""
import tempfile
import time

import numpy as np

from repro.checkpoint import CheckpointManager
from repro.common.compile_cache import use_compile_cache
from repro.data.loader import DataLoader, LoaderConfig
from repro.jpeg.corpus import (build_corpus, corpus_fingerprint,
                               load_corpus_shards, write_corpus_shards)

PATH = "numpy-fast"


def run_epoch(loader) -> float:
    t0 = time.perf_counter()
    n = sum(batch["image"].shape[0] for batch in loader)
    return n / (time.perf_counter() - t0)


def main() -> None:
    corpus = build_corpus(32, seed=0)
    with tempfile.TemporaryDirectory(prefix="shard-demo-") as root:
        manifest = write_corpus_shards(corpus, root, shard_size=8)
        source = load_corpus_shards(root)
        print(f"ingested {len(source)} records -> {manifest}")
        print(f"fingerprint {source.fingerprint} "
              f"(corpus: {corpus_fingerprint(corpus)})")

        cfg = LoaderConfig(batch_size=8, num_workers=2, mode="process",
                           shuffle=True, shuffle_window=8, seed=3)
        shard_dl = DataLoader(source, None, cfg=cfg, path_name=PATH)
        handle, _ = shard_dl._proc_initargs()
        print(f"worker handle: {type(handle).__name__} -> {handle.root} "
              "(workers mmap the shards; no bytes in initargs)")

        # -- mid-epoch checkpoint / exact resume ------------------------
        it = iter(shard_dl)
        first = next(it)["label"]
        with tempfile.TemporaryDirectory(prefix="ckpt-") as ck:
            mgr = CheckpointManager(ck)
            mgr.save(1, {"step": np.int32(1)},
                     extra={"loader": shard_dl.state()})
            rest_live = [x for b in it for x in b["label"]]
            _, _, extra = mgr.restore_latest(like={"step": np.int32(0)})
            resumed = DataLoader(load_corpus_shards(root), None,
                                 cfg=cfg, path_name=PATH)
            resumed.restore(extra["loader"])
            rest_resumed = [x for b in resumed for x in b["label"]]
            assert rest_live == rest_resumed
            print(f"resume parity ok: {len(first)} consumed, "
                  f"{len(rest_resumed)} replayed identically")
            resumed.close()

        # -- the source axis, measured ----------------------------------
        mem_dl = DataLoader(corpus.files, corpus.labels, cfg=cfg,
                            path_name=PATH)
        print(f"memory loader: {run_epoch(mem_dl):8.1f} img/s")
        print(f"shard  loader: {run_epoch(shard_dl):8.1f} img/s "
              "(same corpus, mmap-backed)")
        mem_dl.close()
        shard_dl.close()
        source.close()


if __name__ == "__main__":
    use_compile_cache()
    main()
