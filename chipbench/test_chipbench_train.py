"""A tiny ``train-rrc-w8`` (``test_chipbench_cells.SMALL``: 32-48 px images
onto 24x24) driven end to end on the CPU through the harness's own
functions, and each control put in the program's place."""
from __future__ import annotations

import numpy as np
import pytest

from chipbench import reference as R
from chipbench import reference_train as RT
from chipbench.test_chipbench_cells import _cell, _run
from chipbench.test_chipbench_faults import path_in_place  # noqa: F401

CELL = "train-rrc-w8"


def test_train_cell_end_to_end():
    out = _run(_cell(CELL))
    assert out["correct"], out["checks"]
    assert out["metrics"]["images_per_s"]["value"] > 0
    assert out["metrics"]["setup_s"]["value"] > 0
    assert out["failed"] == 0 and out["attempted"] > 0
    rec = out["record"]
    assert rec["rare_checked"]
    assert out["checks"]["bad_crops"]["value"] == 0
    # set-up compiled the resample once; no crop compiles in the window
    assert rec["compile_requests"] == 0
    assert rec["setup_compile_requests"] > 0
    assert rec["resample_ops"] > 0 and rec["resample_bytes"] > 0


def test_traced_train_cell_reports_augment_ms():
    cell = _cell(CELL)
    out = _run(cell, trace=True)
    assert out["correct"], out["checks"]
    m = out["metrics"]
    assert m["augment_ms.train"]["value"] > 0
    assert "images_per_s" not in m
    # every layer the cell lists, the loader's included; no chip, so no
    # peaks and no device op to read a roofline from
    rooflines = {"transform_roofline.loader", "resample_roofline.train"}
    listed = {x["name"] for x in cell["per_layer"]}
    assert rooflines <= listed and "compiles_in_window.loader" in listed
    assert set(m) == listed - rooflines, listed - set(m)
    assert m["compiles_in_window.loader"]["value"] == 0
    rec = out["record"]
    assert rec["work_ops"] > 0 and rec["resample_ops"] > 0
    # traced on the CPU: the trace has no device plane to read
    assert rec["resample_device_s"] == 0


PEAKS = {"ops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


@pytest.mark.parametrize("record, want", [
    # 2e9 operations at 1e12/s: 2 ms least, over 4 ms of kernel time
    ({"resample_ops": 2e9, "resample_bytes": 1e6,
      "resample_device_s": 4e-3, "peaks": PEAKS}, 50.0),
    # memory-bound: 1e8 bytes at 1e11/s, 1 ms over 2 ms
    ({"resample_ops": 1e6, "resample_bytes": 1e8,
      "resample_device_s": 2e-3, "peaks": PEAKS}, 50.0),
    # untraced, a trace with no such op, or no peaks: nothing to read
    ({"resample_ops": 2e9, "resample_bytes": 1e6,
      "resample_device_s": None, "peaks": PEAKS}, None),
    ({"resample_ops": 2e9, "resample_bytes": 1e6,
      "resample_device_s": 0.0, "peaks": PEAKS}, None),
    ({"resample_ops": 2e9, "resample_bytes": 1e6,
      "resample_device_s": 4e-3, "peaks": None}, None),
])
def test_resample_roofline_reader(record, want):
    from chipbench.reduce import metric_reader
    got = metric_reader("resample_roofline.train")(record)
    assert got == (None if want is None else pytest.approx(want))


def _bilinear_in_place(batch, size):
    """The control resample: plain two-tap bilinear, no antialias."""
    import jax.numpy as jnp
    imgs, crops = np.asarray(batch["image"]), np.asarray(batch["crop"])
    out = np.stack([RT.resample(im, tuple(int(v) for v in c), size,
                                antialias=False)
                    for im, c in zip(imgs, crops)])
    return dict(batch, image=jnp.asarray(out))


@pytest.mark.parametrize("control", ["int8", "bilinear"])
def test_each_control_is_not_correct(control, path_in_place,  # noqa: F811
                                     monkeypatch):
    if control == "int8":
        path = path_in_place("test-control-int8", lambda datas: [
            R.decode(d, precision="int8") for d in datas])
        cell = _cell(CELL, path=path)
    else:
        from repro.data import augment
        monkeypatch.setattr(augment, "resample", _bilinear_in_place)
        cell = _cell(CELL)
    out = _run(cell)
    assert not out["correct"]
    assert out["checks"]["bad_crops"]["value"] == 0
    assert any(c["value"] > c["limit"] for k, c in out["checks"].items()
               if k in ("max_abs_err", "mismatch_share"))


def test_resample_device_time_sums_every_op_in_the_window(monkeypatch):
    """All ``rrc_resample`` ops count, past the ten costliest and clipped
    to the traced window; none untraced."""
    from types import SimpleNamespace

    from chipbench import reduce
    from chipbench.entries import train
    ns = 1e9
    # twelve costlier ops, then two resample launches, one cut by the
    # window's end at 10 s
    events = {"/device:TPU:0": [(f"%fusion.{k} = f32[8]{{0}} fusion(%a)",
                                 1 * ns, 2 * ns) for k in range(12)]
              + [("%rrc_resample.1 = s8[4]{0} custom-call(%x)",
                  3 * ns, 3 * ns + 2e6),
                 ("%rrc_resample.1 = s8[4]{0} custom-call(%x)",
                  10 * ns - 1e6, 10 * ns + 5e6)]}
    monkeypatch.setattr(reduce, "load_trace", lambda log_dir: None)
    monkeypatch.setattr(reduce, "device_events", lambda profile: events)
    assert len(reduce.top_ops(events, 0, 10 * ns)) == 10
    traced = SimpleNamespace(on=True, log_dir="x", t_before=100.0,
                             t0=100.0, t1=110.0)
    assert train._op_device_s(traced, "rrc_resample") == pytest.approx(3e-3)
    assert train._op_device_s(SimpleNamespace(on=False),
                              "rrc_resample") is None
