"""Program spans in JAX's profiler trace, and the spans of the device
round trips: a ``Tracer``'s spans land on the ``/host:CPU`` plane of the
``.xplane.pb`` on the device trace's clock; the ``NullTracer`` and pool
workers annotate nothing; importing the tracer never loads JAX; the
Pallas batch path spans each launch and each colour round trip, and
``prefetch_to_device`` each copy to the device."""
import glob
import os
import select
import signal
import subprocess
import sys
import time

import numpy as np

from repro.jpeg import encoder
from repro.jpeg.corpus import natural_image
from repro.jpeg.paths import DECODE_PATHS
from repro.obs import trace

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _profiled(log_dir, body):
    """Run ``body()`` under the JAX profiler -> (monotonic time read just
    before tracing started, the trace's ``ProfileData``)."""
    import jax
    from jax.profiler import ProfileData
    jax.numpy.ones(1).block_until_ready()        # backend up before tracing
    t_before = time.monotonic()
    jax.profiler.start_trace(str(log_dir))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    return t_before, ProfileData.from_file(path)


def _host_events(profile, name):
    return [ev for plane in profile.planes if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events
            if ev.name == name]


def _named(events, name):
    return [e for e in events if e["name"] == name]


# ------------------------------------------------------------ the tracer
def test_span_lands_in_profiler_trace_on_its_clock(tmp_path):
    """A span and its open-time args become a /host:CPU event whose start
    is within 1 ms of the span's monotonic start aligned by the time
    tracing started; args added later with set() stay in the buffer."""
    tracer = trace.Tracer()

    def body():
        with trace.use_tracer(tracer):
            time.sleep(0.02)
            with trace.span("probe.span", batch=3, rows=7) as sp:
                time.sleep(0.005)
                sp.set(late=1)

    t_before, profile = _profiled(tmp_path, body)
    ev, = _host_events(profile, "probe.span")
    assert dict(ev.stats) == {"batch": 3, "rows": 7}
    rec, = _named(tracer.events(), "probe.span")
    assert rec["args"] == {"batch": 3, "rows": 7, "late": 1}
    aligned_ns = (rec["ts"] / 1e6 - t_before) * 1e9
    assert abs(ev.start_ns - aligned_ns) < 1e6
    assert abs(ev.duration_ns - rec["dur"] * 1e3) < 1e6


def test_null_tracer_opens_no_annotation(tmp_path):
    assert not trace.get_tracer().enabled

    def body():
        with trace.span("probe.null", batch=1):
            time.sleep(0.001)

    _, profile = _profiled(tmp_path, body)
    assert _host_events(profile, "probe.null") == []


def test_worker_tracer_writes_shard_and_annotates_nothing(tmp_path):
    shard_dir = str(tmp_path / "shards")
    config = trace.Tracer(shard_dir=shard_dir).worker_config()
    prev = trace.get_tracer()

    def body():
        trace.init_worker(config)
        try:
            with trace.span("probe.worker", chunk=2):
                time.sleep(0.001)
            trace.flush()
        finally:
            trace.set_tracer(prev)

    _, profile = _profiled(tmp_path / "profile", body)
    assert _host_events(profile, "probe.worker") == []
    rec, = _named(trace.merge_shards(shard_dir), "probe.worker")
    assert rec["args"] == {"chunk": 2}


def test_fork_of_a_jax_process_annotates_nothing():
    import jax  # noqa: F401 -- the parent has loaded JAX
    assert trace._trace_annotation() is not None
    r, w = os.pipe()
    ready = []
    pid = os.fork()
    if pid == 0:                                 # the child: no JAX calls
        os.write(w, b"1" if trace._trace_annotation() is None else b"0")
        os._exit(0)
    os.close(w)
    try:
        ready, _, _ = select.select([r], [], [], 60)
        assert ready, "the forked child did not answer in 60 s"
        assert os.read(r, 1) == b"1"
    finally:
        os.close(r)
        if not ready:
            os.kill(pid, signal.SIGKILL)
        os.waitpid(pid, 0)


def test_importing_and_tracing_leaves_jax_unloaded():
    """The tracer is a leaf: importing it and recording spans from a
    numpy-only process never pulls JAX in."""
    code = ("import sys\n"
            "from repro.obs import trace\n"
            "t = trace.Tracer()\n"
            "with t.span('numpy.only', args={'batch': 1}):\n"
            "    pass\n"
            "assert len(t.events()) == 2\n"
            "sys.exit(3 if 'jax' in sys.modules else 0)\n")
    env = dict(os.environ, PYTHONPATH=SRC)
    done = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr


# ------------------------------------------------------------ the seams
def _jpeg(seed, subsampling):
    img = natural_image(np.random.RandomState(seed), 40, 48)
    if subsampling == "ycck":
        return encoder.encode_jpeg_ycck(img, quality=85)
    return encoder.encode_jpeg(img, quality=85, subsampling=subsampling)


def test_pallas_batch_spans_each_launch_and_colour_round_trip():
    """A mixed micro-batch: one jpeg.transform per structure group (4:2:0,
    4:4:4, YCCK), one jpeg.color per 3-component image, each inside a
    jpeg.assemble of its own thread."""
    files = [_jpeg(1, "420"), _jpeg(2, "444"), _jpeg(3, "420"),
             _jpeg(4, "ycck"), _jpeg(5, "444")]
    with trace.use_tracer(trace.Tracer()) as tracer:
        out = DECODE_PATHS["pallas-batch"].decode_batch(files)
    assert all(not isinstance(r, BaseException) for r in out)
    evs = tracer.events()
    launches = _named(evs, "jpeg.transform")
    assert sorted(e["args"]["batch"] for e in launches) == [1, 2, 2]
    assert all(e["args"]["rows"] > 0 for e in launches)
    colours = _named(evs, "jpeg.color")
    assert len(colours) == 4
    assembles = _named(evs, "jpeg.assemble")
    for c in colours:
        assert any(a["tid"] == c["tid"] and a["ts"] <= c["ts"]
                   and c["ts"] + c["dur"] <= a["ts"] + a["dur"] + 0.01
                   for a in assembles), c


def test_prefetch_to_device_spans_each_device_put():
    from repro.data.loader import prefetch_to_device
    batches = [{"image": np.full((2, 4, 4, 3), k, np.uint8)}
               for k in range(5)]
    with trace.use_tracer(trace.Tracer()) as tracer:
        got = [np.asarray(b["image"]) for b in
               prefetch_to_device(iter(batches), size=2)]
    assert [int(g[0, 0, 0, 0]) for g in got] == list(range(5))
    assert len(_named(tracer.events(), "prefetch.device_put")) == 5
