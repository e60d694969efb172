"""Quickstart: encode a JPEG corpus, decode it through capability-typed
decoder sessions, benchmark the two protocols, and get an operational
recommendation — the paper's workflow in ~50 lines.

The front door is ``repro.codecs``: ``open_decoder(name, context=...)``
returns a session whose ``decode`` yields a typed outcome
(image | skip | error), and the ``eligible(caps, context)`` resolver —
not scattered booleans — decides which decoder may run where.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

from repro.codecs import ExecContext, eligible, get_decoder, open_decoder
from repro.common.compile_cache import use_compile_cache
from repro.core import decision
from repro.core.protocols import LoaderProtocol, SingleThreadProtocol
from repro.jpeg.corpus import build_corpus


def main():
    # 1. a synthetic ImageNet-like corpus (incl. one rare Adobe-YCCK JPEG)
    corpus = build_corpus(32, seed=0)
    print(f"corpus: {len(corpus.files)} JPEGs, rare index "
          f"{corpus.rare_index}")

    # 2. decode one image through three engines, as decoder sessions
    for name in ["numpy-fast", "jnp-fused", "pallas-idct"]:
        with open_decoder(name, context=ExecContext.INLINE) as dec:
            img = dec.decode(corpus.files[0]).unwrap()
            print(f"  {name:12s} -> {img.shape} {img.dtype} "
                  f"bucket={dec.probe(corpus.files[0])[:2]}")

    # 2b. a strict decoder *skips* the rare mode instead of erroring
    with open_decoder("strict-fast") as dec:
        out = dec.decode(corpus.files[corpus.rare_index])
        print(f"  strict-fast on rare image -> {out.kind}: {out.reason}")

    # 2c. eligibility is a (capabilities, context) question
    caps = get_decoder("jnp-fused").caps
    verdict = eligible(caps, ExecContext.PROCESS_POOL)
    print(f"  jnp-fused in a forked pool? {bool(verdict)} "
          f"({verdict.reason})")

    # 3. the two protocols (run_path takes registered decoder names)
    names = ["numpy-fast", "numpy-int", "fft-idct", "strict-fast"]
    records = SingleThreadProtocol(corpus, repeats=2).run(names)
    loader = LoaderProtocol(corpus, repeats=1)
    for n in names:
        for w in (0, 2):
            records.append(loader.run_path(n, w))

    print("\nsingle-thread img/s:")
    for r in records:
        if r.protocol == "single_thread":
            print(f"  {r.decoder:12s} {r.throughput_mean:7.1f} "
                  f"skips={r.skips}")

    # 4. the decision protocol (zero-skip tier, protocol disagreement)
    rec = decision.recommend(records)
    d = rec["protocol_disagreement"]["live-host"]
    print(f"\nsingle-thread leader: {d['single_leader']}")
    print(f"loader leader:        {d['loader_leader']}")
    print(f"rank correlation:     rho={d['rho']:.2f}")
    print("zero-skip tier:       "
          + ", ".join(t.decoder for t in rec["tier"]))


if __name__ == "__main__":
    use_compile_cache()
    main()
