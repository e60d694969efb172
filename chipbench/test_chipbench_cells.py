"""Tiny loader cells driven end to end on the CPU through the harness's
own functions (the command itself refuses a machine without a TPU)."""
from __future__ import annotations

import os
import time

import pytest

from chipbench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
SMALL = {"pool_images": 12, "sizes": [[32, 48], [48, 48]],
         "batch_size": 4, "target_hw": [24, 24], "check_images": 4}


def _cell(name, path=None, **traffic):
    cell = harness.resolve_cell(harness.load_bench(ROOT), name)
    cell["config"].update(SMALL)
    cell["traffic"].update(traffic)
    if path is not None:
        cell["config"]["path"] = path
    return cell


def _run(cell, trace=False, seconds=1.5, seed=2 ** 33 + 11):
    out, _ = harness.run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                              t_start=time.monotonic(), device=CPU,
                              on_device=False)
    return out


@pytest.mark.parametrize("cell", ["loader-val-w8", "loader-val-w0"])
def test_loader_cell_end_to_end(cell):
    out = _run(_cell(cell))
    assert out["correct"], out["checks"]
    assert out["metrics"]["images_per_s"]["value"] > 0
    assert out["metrics"]["setup_s"]["value"] > 0
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["record"]["rare_checked"]
    # set-up warmed every shape, and each epoch repeats the warmed order
    assert out["record"]["compile_requests"] == 0
    assert out["record"]["setup_compile_requests"] > 0
    assert list(out)[-1] == "checks"


def test_traced_loader_cell_reports_its_layers():
    out = _run(_cell("loader-val-w8"), trace=True)
    assert out["correct"]
    m = out["metrics"]
    for name in ("entropy_ms.loader", "assemble_ms.loader",
                 "queue_wait_ms.loader", "compiles_in_window.loader",
                 "compiles_in_setup.loader", "device_idle_share.loader"):
        assert name in m, name
    assert "images_per_s" not in m
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    assert out["breakdown"]["idle_gaps"]
