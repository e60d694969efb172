#!/usr/bin/env python3
"""The controls' readings of a training cell, on the host alone.

    python3 chipbench/readings_train.py --workload train-rrc-w8 --seeds 1,2,3 --control int8
    python3 chipbench/readings_train.py --workload train-rrc-w8 --seeds 1,2,3 --control bilinear

Per seed, what a control reads against ``reference_train.py`` on a sample
of the cell's size (the rare image and ``check_images`` others of the
seed's pool, each with its crop for epoch 0): ``int8`` decodes with an
int8 IDCT, ``bilinear`` resamples without the antialias filter. The
program's own readings come from ``readings.py`` without ``--control``.
One JSON object per line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CONTROLS = {"int8": {"precision": "int8"}, "bilinear": {"antialias": False}}


def control_reading(cell: dict, seed: int, control: str) -> dict:
    import numpy as np
    from chipbench import corpus
    from chipbench import reference_train as RT
    from chipbench.harness import compare_images
    cfg = cell["config"]
    pool = corpus.build(cfg, seed)
    rng = np.random.default_rng([seed, 1])
    rare = corpus.rare_index(cfg)
    sample = [rare] + [i for i in rng.choice(
        len(pool), size=cfg["check_images"] + 1, replace=False).tolist()
        if i != rare][:cfg["check_images"]]
    size = tuple(cfg["target_hw"])
    got, want = [], []
    for i in sample:
        args = (pool[i], cfg["augment"], seed, 0, i, size)
        want.append(RT.expected(*args)[1])
        got.append(RT.expected(*args, **CONTROLS[control])[1])
    return {"seed": seed, "control": control,
            "reading": compare_images(got, want), "sample": len(sample)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", required=True, choices=sorted(CONTROLS))
    args = ap.parse_args(argv)
    from chipbench import harness
    cell = harness.resolve_cell(harness.load_bench(ROOT), args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(control_reading(cell, seed, args.control)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
