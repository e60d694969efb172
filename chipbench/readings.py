#!/usr/bin/env python3
"""Readings that set the benchmark's limits and rates, on the chip.

    python3 chipbench/readings.py --workload <cell> --seeds 1,2,3 --seconds 20
    python3 chipbench/readings.py --workload <cell> --seeds 1,2,3 --control

All in one process, which holds the chip. The first form runs the cell
once per seed and prints, per run, the numbers the correctness check
compares (the program's readings). ``--control`` prints, per seed, what
the control reads on a sample of the same size: the reference with its
IDCT in int8, the next precision below the program's, put in the
program's place; it runs on the host alone. One JSON object per line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def control_reading(cell: dict, seed: int) -> dict:
    import numpy as np
    from chipbench import corpus
    from chipbench import reference as R
    from chipbench.harness import compare_images
    cfg = cell["config"]
    pool = corpus.build(cfg, seed)
    rng = np.random.default_rng([seed, 1])
    rare = corpus.rare_index(cfg)
    sample = [rare] + [i for i in rng.choice(
        len(pool), size=cfg["check_images"] + 1, replace=False).tolist()
        if i != rare][:cfg["check_images"]]
    got, want = [], []
    for i in sample:
        a, b = R.decode(pool[i], precision="int8"), R.decode(pool[i])
        if "target_hw" in cfg:
            a, b = (R.center_fit(x, *cfg["target_hw"]) for x in (a, b))
        got.append(a)
        want.append(b)
    return {"seed": seed, "control": compare_images(got, want),
            "sample": len(sample)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]

    from chipbench import harness
    bench = harness.load_bench(ROOT)
    cell = harness.resolve_cell(bench, args.workload)
    if args.control:
        for seed in seeds:
            print(json.dumps(control_reading(cell, seed)), flush=True)
        return 0
    device = harness.require_tpu(cell["chips"])
    harness.use_compile_cache()
    for seed in seeds:
        t = time.monotonic()
        out, rec = harness.run_cell(cell, seed=seed, seconds=args.seconds,
                                    trace=False, t_start=t, device=device)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "checks": out["checks"], "metrics": out["metrics"],
                          "record": out["record"],
                          "delivery_s": rec["delivery_s"],
                          "wall_s": time.monotonic() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
