"""Worker-count autotuning demo (the paper's §4.3 finding as a feature):
sweep worker counts for several decode paths on THIS machine and print the
per-decoder recommendation with the 5% practical-significance rule.

Run:  PYTHONPATH=src python examples/autotune_workers.py
"""
from repro.common.compile_cache import use_compile_cache
from repro.data.autotune import autotune_workers
from repro.data.loader import DataLoader, LoaderConfig
from repro.jpeg.corpus import build_corpus


def main():
    corpus = build_corpus(48, seed=9)
    for name in ["numpy-fast", "numpy-int", "fft-idct"]:
        def factory(w, name=name):
            # decode fns resolve from the codecs registry by path name
            return DataLoader(corpus.files, corpus.labels,
                              cfg=LoaderConfig(batch_size=8, num_workers=w),
                              path_name=name)

        res = autotune_workers(factory, candidates=(0, 2, 4, 8),
                               max_items=32, repeats=1)
        sweep = {w: f"{m:.1f}" for w, (m, s) in res["sweep"].items()}
        print(f"{name:12s} best_w={res['best']} "
              f"(peak_w={res['peak_workers']}) sweep={sweep} img/s")
    print("\nNOTE: this container has 1 vCPU — flat sweeps are the "
          "*correct* measured answer here; on the paper's 16-vCPU nodes "
          "the same protocol returns decoder- and platform-specific peaks "
          "(Zen 4: w=4 for most decoders, Zen 5: w=8).")


if __name__ == "__main__":
    use_compile_cache()
    main()
