"""The benchmark's reductions, readers and work count, on the CPU."""
from __future__ import annotations

import io
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from chipbench import corpus, reduce, work
from chipbench import reference as R

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _jpeg(h, w, sampling="420", quality=85, seed=0):
    return corpus.encode(corpus.synth(np.random.default_rng(seed), h, w),
                         sampling, quality)


# ------------------------------------------------------------ intervals
def test_union_and_clip():
    iv = reduce.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)])
    assert iv == [[0, 4], [5, 7]]
    assert reduce.clip(iv, 1, 6) == [[1, 4], [5, 6]]


class _Ev:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class _Profile:
    def __init__(self, planes):
        self.planes = planes


def test_busy_idle_and_breakdown_on_a_synthetic_trace():
    ops = [_Ev("fusion", 0, 100), _Ev("copy", 50, 100), _Ev("fusion", 400, 100)]
    prof = _Profile([
        _Plane("/device:TPU:0", [_Line("XLA Ops", ops),
                                 _Line("XLA Modules", [_Ev("jit", 0, 900)])]),
        _Plane("/host:CPU", [_Line("python", [_Ev("x", 0, 1000)])])])
    events = reduce.device_events(prof)
    assert list(events) == ["/device:TPU:0"]
    busy_s, iv = reduce.busy(events, 0, 1000)
    assert busy_s == pytest.approx(250e-9)
    assert iv == [[0, 150], [400, 500]]
    top = reduce.top_ops(events, 0, 1000)
    assert top[0] == ["fusion", pytest.approx(200e-9)]
    assert reduce.op_name("%copy.1 = f32[8]{0} copy(f32[8]{0} %x)") == \
        "%copy.1 = f32[8]{0} copy"
    # the container span loses to the leaf span inside the longest gap
    spans = [("loader.decode", 0, 1000, 1), ("jpeg.entropy", 150, 400, 1),
             ("jpeg.assemble", 500, 1000, 1),
             ("service.admission", 160, 170, 2)]
    gaps = reduce.idle_gaps(iv, 0, 1000, spans)
    assert gaps[0] == ["jpeg.assemble", pytest.approx(500e-9)]
    assert gaps[1] == ["jpeg.entropy", pytest.approx(250e-9)]


def test_busy_from_a_trace_recorded_on_the_cpu(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda a: (a @ a).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(5):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    prof = reduce.load_trace(str(tmp_path))
    events = reduce.device_events(
        prof, planes=lambda p: p.name == "/host:CPU",
        lines=lambda p: [ln for ln in p.lines
                         if ln.name.startswith("tf_XLA")])
    names = {n for evs in events.values() for n, _, _ in evs}
    assert any(n.startswith("dot") for n in names)
    hi = max(b for evs in events.values() for _, _, b in evs)
    busy_s, iv = reduce.busy(events, 0, hi)
    assert 0 < busy_s <= hi / 1e9
    assert busy_s == pytest.approx(sum(b - a for a, b in iv) / 1e9)


# ------------------------------------------------------------ readers
def _record(**kw):
    rec = {"kind": "loader", "setup_s": 12.5, "images": 96,
           "elapsed_s": 24.0, "window_s": 25.0, "busy_s": 0.5,
           "work_ops": 1e9, "work_bytes": 8.19e6,
           "peaks": {"ops_per_s": 1e12, "hbm_bytes_per_s": 8.19e8},
           "compile_requests": 3, "setup_compile_requests": 40,
           "spans": {"jpeg.parse": {"total_s": 1.0, "count": 10},
                     "jpeg.entropy": {"total_s": 3.0, "count": 10},
                     "jpeg.assemble": {"total_s": 0.5, "count": 10},
                     "loader.queue_wait": {"total_s": 2.0, "count": 4}}}
    rec.update(kw)
    return rec


@pytest.mark.parametrize("name, value", [
    ("images_per_s", 4.0),
    ("setup_s", 12.5),
    ("entropy_ms.loader", 400.0),
    ("assemble_ms.loader", 50.0),
    ("queue_wait_ms.loader", 500.0),
    ("compiles_in_window.loader", 3.0),
    ("compiles_in_setup.loader", 40.0),
    # least time: max(1e9 / 1e12, 8.19e6 / 8.19e8) = 10 ms of 500 ms busy
    ("transform_roofline.loader", 2.0),
    ("device_idle_share.loader", 98.0),
])
def test_reader(name, value):
    assert reduce.metric_reader(name)(_record()) == pytest.approx(value)


@pytest.mark.parametrize("name", [
    "entropy_ms.loader", "queue_wait_ms.loader", "compiles_in_window.loader",
    "compiles_in_setup.loader", "transform_roofline.loader",
    "device_idle_share.loader"])
def test_reader_finds_nothing_in_an_untraced_run(name):
    rec = _record()
    for k in ("spans", "busy_s"):
        del rec[k]
    assert reduce.metric_reader(name)(rec) is None


def test_every_metric_has_a_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(reduce.metric_reader(m["name"]))


# ------------------------------------------------------------ work
@pytest.mark.parametrize("h, w, sampling, blocks", [
    (375, 500, "420", 24 * 32 * 6),      # 4 luma + 2 chroma per 16x16 MCU
    (333, 500, "444", 42 * 63 * 3),
    (500, 500, "420", 32 * 32 * 6),
])
def test_header_work(h, w, sampling, blocks):
    ops, nbytes = work.header_work(_jpeg(h, w, sampling))
    assert ops == blocks * work.OPS_PER_BLOCK + h * w * work.OPS_PER_PIXEL
    assert nbytes == (blocks * work.BYTES_PER_BLOCK
                      + h * w * work.BYTES_PER_PIXEL)


def test_header_work_of_the_ycck_image():
    with open(corpus.YCCK_FILE, "rb") as f:
        data = f.read()
    ops, _ = work.header_work(data)
    assert ops == 4 * 47 * 63 * work.OPS_PER_BLOCK + 375 * 500 * 10


def test_least_seconds_names_its_bound():
    peak = {"ops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    assert work.least_seconds(1e12, 1e8, peak) == (1.0, "compute")
    assert work.least_seconds(1e9, 1e9, peak) == (1.0, "memory")


# ------------------------------------------------------------ reference
def test_reference_matches_pillow_closely():
    from PIL import Image
    data = _jpeg(48, 64, "444", 95, seed=3)
    ours = R.decode(data).astype(int)
    pil = np.asarray(Image.open(io.BytesIO(data)).convert("RGB")).astype(int)
    assert np.abs(ours - pil).mean() < 1.0


def test_reference_zigzag_and_center_fit():
    assert R.ZIGZAG[:6].tolist() == [0, 1, 8, 16, 9, 2]
    img = np.arange(5 * 4 * 3, dtype=np.uint8).reshape(5, 4, 3)
    out = R.center_fit(img, 3, 6)
    assert out.shape == (3, 6, 3)
    assert (out[:, :4] == img[1:4]).all() and not out[:, 4:].any()


def test_corpus_is_a_function_of_the_seed():
    cfg = {"pool_images": 6, "layout_seed": 0, "sizes": [[16, 24]],
           "qualities": [75], "share_444": 0.5, "rare_index_of": [1, 2]}
    a, b = corpus.build(cfg, 2 ** 31 + 7), corpus.build(cfg, 2 ** 31 + 7)
    c = corpus.build(cfg, 5)
    assert a == b and a != c
    assert a[3] == c[3]                  # the rare index holds the YCCK file
    assert [R.parse(x).height for x in a] == [R.parse(x).height for x in c]


# ------------------------------------------------------------ found by name
def test_a_new_cell_traffic_and_metric_are_found_by_name(tmp_path):
    shutil.copytree(HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append({
        "name": "loader-val-w3", "config": "imagenet-val-loader",
        "traffic": "loader-w3", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "fill_ms.loader", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "data loader",
        "moves": "images_per_s", "workloads": ["loader-val-w3"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "chipbench" / "traffic" / "loader-w3.json").write_text(
        json.dumps({"num_workers": 3, "decode_batch": 16}))
    (tmp_path / "chipbench" / "metrics" / "fill_ms.loader.py").write_text(
        "def read(record):\n    return 2.0 * record['setup_s']\n")
    code = (
        "import json, sys\n"
        f"sys.path.insert(0, {str(tmp_path)!r})\n"
        "from chipbench import harness, reduce\n"
        f"b = harness.load_bench({str(tmp_path)!r})\n"
        "c = harness.resolve_cell(b, 'loader-val-w3')\n"
        "m = reduce.read_metrics(c['per_layer'], {'setup_s': 1.5})\n"
        "print(json.dumps([harness.__file__, c['traffic'], m]))\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, cwd=str(tmp_path))
    assert out.returncode == 0, out.stderr
    where, traffic, metrics = json.loads(out.stdout.splitlines()[-1])
    assert where.startswith(str(tmp_path))
    assert traffic["num_workers"] == 3
    assert metrics == {"fill_ms.loader": {"value": 3.0, "unit": "ms"}}


def test_a_new_entry_point_is_found_by_name(tmp_path):
    """A configuration whose ``entry`` names a new file under ``entries/``
    is driven by that file, with no edit to the harness."""
    shutil.copytree(HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "probe", "source": "test", "reduced": [], "why": "test",
        "file": "chipbench/configs/probe.json"})
    bench["workloads"].append({
        "name": "probe-cell", "config": "probe", "traffic": "loader-w0",
        "chips": 1, "why": "test"})
    bench["end_to_end"][0]["workloads"].append("probe-cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "chipbench" / "configs" / "probe.json").write_text(
        json.dumps({"entry": "probe"}))
    (tmp_path / "chipbench" / "entries" / "probe.py").write_text(
        "def run(cfg, traffic, seed, seconds, tracing, counter, t0):\n"
        "    rec = {'kind': 'loader', 'setup_s': 1.5, 'images': 3,\n"
        "           'elapsed_s': 1.0, 'memory_peak_bytes': 0}\n"
        "    return rec, {'x': {'value': 0, 'limit': 0}}, 3, 0\n")
    code = (
        "import json, sys\n"
        f"sys.path.insert(0, {str(tmp_path)!r})\n"
        "from chipbench import harness\n"
        f"b = harness.load_bench({str(tmp_path)!r})\n"
        "c = harness.resolve_cell(b, 'probe-cell')\n"
        "out, _ = harness.run_cell(c, seed=1, seconds=1.0, trace=False,\n"
        "    t_start=0.0, device={'kind': 'cpu'}, on_device=False)\n"
        "print(json.dumps(out))\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, cwd=str(tmp_path))
    assert out.returncode == 0, out.stderr
    res = json.loads(out.stdout.splitlines()[-1])
    assert res["correct"] and res["attempted"] == 3
    assert res["metrics"] == {"images_per_s": {"value": 3.0,
                                               "unit": "images/s"},
                              "setup_s": {"value": 1.5, "unit": "s"}}


# ------------------------------------------------------------ the command
def test_command_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "loader-val-w0",
         "--seed", str(2 ** 32 + 3), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"correct"' not in out.stdout


def test_command_fails_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "loader-val-w0",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=str(tmp_path), capture_output=True, text=True, timeout=300,
        env=env)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
