"""The readers of the device round trips' spans (``jpeg.transform``,
``jpeg.color``, ``prefetch.device_put``), on a synthetic record and in a
tiny traced cell on the CPU."""
from __future__ import annotations

import os
import time

import pytest

from chipbench import harness, reduce

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NEW = ("transform_ms.loader", "color_ms.loader",
       "device_calls_per_image.loader", "device_put_ms.loader")


def _record(**spans):
    rec = {"kind": "loader", "setup_s": 12.5, "images": 32,
           "elapsed_s": 8.0, "window_s": 10.0,
           "spans": {"jpeg.parse": {"total_s": 1.0, "count": 32},
                     "jpeg.entropy": {"total_s": 3.0, "count": 32},
                     "jpeg.assemble": {"total_s": 0.64, "count": 32},
                     "jpeg.transform": {"total_s": 0.32, "count": 3},
                     "jpeg.color": {"total_s": 0.16, "count": 31},
                     "prefetch.device_put": {"total_s": 0.01, "count": 2}}}
    rec["spans"].update(spans)
    return rec


@pytest.mark.parametrize("name, value", [
    ("transform_ms.loader", 10.0),          # 320 ms over 32 images
    ("color_ms.loader", 5.0),
    ("device_calls_per_image.loader", 34 / 32),
    ("device_put_ms.loader", 5.0),          # 10 ms over 2 batches
])
def test_span_reader(name, value):
    assert reduce.metric_reader(name)(_record()) == pytest.approx(value)


@pytest.mark.parametrize("name", NEW)
def test_span_reader_finds_nothing_in_an_untraced_run(name):
    rec = _record()
    del rec["spans"]
    assert reduce.metric_reader(name)(rec) is None


@pytest.mark.parametrize("name", NEW)
def test_span_reader_finds_nothing_in_a_program_without_the_spans(name):
    """A traced run of a program that has none of the new spans: each
    reader returns None, so the result line leaves the metric out."""
    rec = _record()
    for n in ("jpeg.transform", "jpeg.color", "prefetch.device_put"):
        del rec["spans"][n]
    assert reduce.metric_reader(name)(rec) is None


@pytest.mark.parametrize("cell", ["loader-val-w0", "loader-val-w8"])
def test_traced_cell_reports_the_round_trips(cell):
    c = harness.resolve_cell(harness.load_bench(ROOT), cell)
    c["config"].update({"pool_images": 12, "sizes": [[32, 48], [48, 48]],
                        "batch_size": 4, "target_hw": [24, 24],
                        "check_images": 4})
    out, rec = harness.run_cell(c, seed=2 ** 33 + 17, seconds=4.0,
                                trace=True, t_start=time.monotonic(),
                                device={"platform": "cpu", "kind": "cpu",
                                        "count": 1},
                                on_device=False)
    assert out["correct"], out["checks"]
    m = out["metrics"]
    for name in NEW:
        assert m[name]["value"] > 0, name
    assert m["color_ms.loader"]["value"] <= m["assemble_ms.loader"]["value"]
    n = {k: rec["spans"][k]["count"]
         for k in ("jpeg.entropy", "jpeg.transform", "jpeg.color")}
    assert n["jpeg.color"] <= n["jpeg.entropy"]
    if cell == "loader-val-w0":
        # one launch per image, and a colour round trip per 3-component
        # image: all but the YCCK one, which comes once per epoch of the
        # pool; the last image may straddle the window's end
        ycck = -(-n["jpeg.entropy"] // c["config"]["pool_images"])
        assert n["jpeg.entropy"] - 1 <= n["jpeg.transform"] \
            <= n["jpeg.entropy"]
        assert n["jpeg.color"] >= n["jpeg.entropy"] - ycck - 1
    else:                                # one per sampling group of a chunk
        assert n["jpeg.transform"] < n["jpeg.entropy"]
    assert m["device_calls_per_image.loader"]["value"] == pytest.approx(
        (n["jpeg.transform"] + n["jpeg.color"]) / n["jpeg.entropy"])
