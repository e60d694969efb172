#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell's configuration, traffic mix and
metrics are found by name from ``BENCHMARK.json`` (see ``harness.py``).
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result. The last line of standard output is the
result as one JSON object; the numbers the correctness check compared,
each beside its limit, are the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
# libtpu would otherwise write its logs under /tmp, outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness
    bench = harness.load_bench(ROOT)
    cell = harness.resolve_cell(bench, args.workload)
    device = harness.require_tpu(cell["chips"])
    harness.use_compile_cache()
    result, _ = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                                 trace=bool(args.trace), t_start=T_START,
                                 device=device)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
