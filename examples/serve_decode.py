"""Online JPEG decode service demo: concurrent clients against the
bandit-routed micro-batching engine.

Builds the synthetic ImageNet-val-like corpus, starts the service, runs a
few closed-loop client threads with a zipf-ish request mix (hot images
repeat, so the content-hash cache participates), then prints the live
metrics snapshot — including which decode path the router converged on
and the robust tier computed from in-situ measurements (the paper's
Table-4 logic applied to service telemetry instead of offline benchmarks).

Run:  PYTHONPATH=src python examples/serve_decode.py --workers 2

With ``--metrics-port`` the service also serves its live telemetry over
loopback HTTP while the demo runs (and the demo scrapes it once before
shutdown so you see the real response bodies):

  PYTHONPATH=src python examples/serve_decode.py --metrics-port 9100
  curl http://127.0.0.1:9100/metrics   # Prometheus text exposition
  curl http://127.0.0.1:9100/healthz   # liveness JSON
  curl http://127.0.0.1:9100/slo       # SLO burn-rate JSON

Use ``--metrics-port 0`` to bind an ephemeral port (printed at start).
"""
import argparse
import json
import threading
import urllib.request

from repro.codecs import list_decoders
from repro.common.compile_cache import use_compile_cache
from repro.jpeg.corpus import build_corpus, zipf_indices
from repro.service import DecodeService, ServiceConfig, ServiceOverloaded


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--requests", type=int, default=60,
                    help="requests per client")
    ap.add_argument("--corpus", type=int, default=24)
    ap.add_argument("--policy", default="ucb", choices=("ucb", "epsilon"))
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve /metrics, /healthz, /slo on this "
                         "loopback port while running (0 = ephemeral)")
    args = ap.parse_args()

    corpus = build_corpus(args.corpus, seed=11)

    cfg = ServiceConfig(num_workers=args.workers, max_batch=8,
                        max_wait_ms=2.0, policy=args.policy,
                        metrics_port=args.metrics_port)
    # every registered decoder is an arm; strict paths fall back on the
    # rare YCCK image instead of failing the request
    svc = DecodeService(cfg, paths=list_decoders())

    def client(cid: str, seed: int):
        served = shed = 0
        for i in zipf_indices(len(corpus.files), args.requests, seed):
            try:
                img = svc.decode(corpus.files[i], client=cid)
                assert str(img.dtype) == "uint8"
                served += 1
            except ServiceOverloaded:
                shed += 1
        print(f"  client {cid}: served={served} shed={shed}")

    with svc:
        if svc.telemetry is not None:
            print(f"telemetry: {svc.telemetry.url}/metrics  /healthz  /slo")
        threads = [threading.Thread(target=client, args=(f"c{k}", 100 + k))
                   for k in range(args.clients)]
        print(f"serving {args.clients} clients x {args.requests} requests "
              f"({args.workers} workers, policy={args.policy}) ...")
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stats = svc.stats()
        tier = svc.router.tier()
        if svc.telemetry is not None:
            # one live scrape before shutdown: what an operator's
            # Prometheus job would see
            body = urllib.request.urlopen(
                svc.telemetry.url + "/metrics", timeout=5).read().decode()
            served_lines = [ln for ln in body.splitlines()
                            if ln.startswith("service_") and "{" not in ln]
            print("\n-- /metrics (unlabeled service series) --")
            print("\n".join(served_lines))

    print("\n-- service stats --")
    print(json.dumps(stats, indent=1, default=str))
    print("\n-- live robust tier (zero-skip + 90% floor, measured in situ) --")
    for t in tier:
        print(f"  {t.decoder:<14} mean_norm={t.mean_norm:.3f} "
              f"min_norm={t.min_norm:.3f}")
    print(f"\nrouter converged on: {stats['router_best']}")


if __name__ == "__main__":
    use_compile_cache()
    main()
