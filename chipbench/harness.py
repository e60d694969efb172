"""The benchmark harness, driven by data.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix. The
harness reads ``configs/<config>.json`` and ``traffic/<traffic>.json``,
drives the entry point the configuration names with
``entries/<entry>.py``, and reads the metrics the cell reports with
``metrics/<metric>.py``; adding a cell, a mix, an entry or a metric adds
files and edits none. A mix holds only parameters.

A run, in the entry: set-up decodes flat stand-ins with the pool's
headers through the same entry point, so every shape the window uses is
compiled before it (``corpus.stand_ins``), and builds the pool from the
seed; then the window is measured; after it the device memory peak is
read, the program's arrays are dropped, and a sample of what the window
produced, drawn from the seed, is compared with ``reference.py``.
"""
from __future__ import annotations

import json
import os
import shutil
import tempfile
import threading
import time
from typing import List, Optional

import numpy as np

from chipbench import reduce

HERE = os.path.dirname(os.path.abspath(__file__))
CACHE_DIR = os.path.join(HERE, ".jax_cache")
# JAX records this duration event once per compile request that misses
# the in-process cache, whether the persistent cache then has it or not
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


# ------------------------------------------------------------ the spec
def load_bench(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return bench


def _load(kind: str, name: str) -> dict:
    with open(os.path.join(HERE, kind, f"{name}.json")) as f:
        return json.load(f)


def resolve_cell(bench: dict, name: str) -> dict:
    """The cell with its configuration and traffic files read, and the
    end-to-end and per-layer metrics it reports."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(cells)}")
    w = cells[name]

    def reports(m):
        return name in m.get("workloads", [name])

    return {"name": name, "chips": w["chips"],
            "config": _load("configs", w["config"]),
            "traffic": _load("traffic", w["traffic"]),
            "end_to_end": [m for m in bench["end_to_end"] if reports(m)],
            "per_layer": [m for m in bench["per_layer"] if reports(m)]}


# ------------------------------------------------------------ the chip
def require_tpu(chips: int) -> dict:
    """The device as JAX reports it; exits without a result unless JAX
    finds at least ``chips`` TPU chips. Never falls back to the CPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chipbench: JAX found no TPU (platform "
                         f"{devices[0].platform!r})")
    if len(devices) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, JAX "
                         f"found {len(devices)}")
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": chips}


def use_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    whatever the environment says, so that only a cell's first run in a
    checkout compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no size limit: with one, every read and write takes a file lock,
    # and loader threads compiling at once time out on it
    jax.config.update("jax_compilation_cache_max_size", -1)


def memory_peak() -> Optional[int]:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return int(peak) if peak is not None else None


def load_peaks(kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


class CompileCounter:
    """Compile requests and real compiles seen by jax.monitoring."""

    def __init__(self):
        self._lock = threading.Lock()
        self.requests = 0
        self.misses = 0
        self.names: List[str] = []

    def on_duration(self, event, duration, **kw):
        if event == BACKEND_COMPILE_EVENT:
            with self._lock:
                self.requests += 1
                self.names.append(str(kw.get("fun_name")))

    def on_event(self, event, **kw):
        if event == CACHE_MISS_EVENT:
            with self._lock:
                self.misses += 1

    def snapshot(self):
        with self._lock:
            return self.requests, self.misses

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self.on_duration)
        jax.monitoring.unregister_event_listener(self.on_event)
        return False


class Tracing:
    """In a ``--trace 1`` run: the program's span tracer and the JAX
    profiler over the window; both off otherwise."""

    def __init__(self, on: bool):
        self.on = on
        self.tracer = None
        self.log_dir = None

    def start(self):
        if not self.on:
            return
        import jax
        from repro.obs import trace
        self.tracer = trace.Tracer(maxlen=1 << 21)
        trace.set_tracer(self.tracer)
        self.log_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        self.t_before = time.monotonic()
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self.t0 = time.monotonic()

    def stop(self):
        if not self.on:
            return
        import jax
        from repro.obs import trace
        self.t1 = time.monotonic()
        jax.profiler.stop_trace()
        trace.set_tracer(None)

    def reduce(self, on_device: bool) -> dict:
        """Span totals, device busy time and the breakdown of the traced
        window [t0, t1]."""
        if not self.on:
            return {}
        spans = reduce.span_events(self.tracer.events(), self.t0, self.t1)
        out = {"spans": reduce.span_totals(spans),
               "window_s": self.t1 - self.t0}
        try:
            profile = reduce.load_trace(self.log_dir)
            lo = (self.t0 - self.t_before) * 1e9
            hi = (self.t1 - self.t_before) * 1e9
            if on_device:
                events = reduce.device_events(profile)
            else:                              # tests on the CPU backend
                events = reduce.device_events(
                    profile, planes=lambda p: p.name == "/host:CPU",
                    lines=lambda p: [ln for ln in p.lines if
                                     ln.name.startswith("tf_XLA")])
            busy_s, busy_iv = reduce.busy(events, lo, hi)
            host = [(n, (s - self.t_before) * 1e9,
                     (e - self.t_before) * 1e9, tid)
                    for n, s, e, tid in spans]
            out.update(busy_s=busy_s, breakdown={
                "device_ops": reduce.top_ops(events, lo, hi),
                "idle_gaps": reduce.idle_gaps(busy_iv, lo, hi, host)})
        finally:
            shutil.rmtree(self.log_dir, ignore_errors=True)
        return out


# ------------------------------------------------------------ checks
def compare_images(got: List[np.ndarray], want: List[np.ndarray]) -> dict:
    """The numbers compared with the reference, each with its limit
    filled in by the caller: images of the wrong shape or type, the
    largest absolute pixel error, and the share of values that differ."""
    diff = total = bad = worst = 0
    for g, w in zip(got, want):
        if g is None or g.shape != w.shape or g.dtype != np.uint8:
            bad += 1
            continue
        d = np.abs(g.astype(np.int16) - w.astype(np.int16))
        worst = max(worst, int(d.max()))
        diff += int(np.count_nonzero(d))
        total += w.size
    return {"bad_images": bad, "max_abs_err": worst,
            "mismatch_share": diff / total if total else 1.0}


def checks_of(counts: dict, compared: dict, limits: dict) -> dict:
    """Exact counts held to 0, the pixel numbers to the configuration's
    limits; each as ``{"value", "limit"}``."""
    out = {k: {"value": v, "limit": 0} for k, v in counts.items()}
    out["bad_images"] = {"value": compared["bad_images"], "limit": 0}
    for k in ("max_abs_err", "mismatch_share"):
        out[k] = {"value": compared[k], "limit": limits[k]}
    return out


def sample(rng, candidates: List[int], k: int, must: List[int]):
    rest = [c for c in candidates if c not in must]
    pick = rng.choice(len(rest), size=min(k, len(rest)), replace=False)
    return sorted(must + [rest[i] for i in pick])


# ------------------------------------------------------------ a run
def run_cell(cell: dict, *, seed: int, seconds: float, trace: bool,
             t_start: float, device: dict, on_device: bool = True):
    """One run of ``cell`` -> (the contract's result object, with the
    compared numbers under ``checks`` as its last key and a summary of
    the run's record under ``record``; the whole record)."""
    cfg, traffic = cell["config"], cell["traffic"]
    tracing = Tracing(trace)
    entry = reduce.load_file(os.path.join(HERE, "entries",
                                          f"{cfg['entry']}.py"), "run")
    with CompileCounter() as counter:
        record, checks, attempted, failed = entry(
            cfg, traffic, seed, seconds, tracing, counter, t_start)
    traced = tracing.reduce(on_device)
    record.update(traced)
    record["peaks"] = load_peaks(device["kind"]) if on_device else None
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    metrics = reduce.read_metrics(
        cell["per_layer"] if trace else cell["end_to_end"], record)
    dev = dict(device, memory_peak_bytes=record["memory_peak_bytes"])
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": dev}
    if trace:
        dev.update(busy_s=traced.get("busy_s"), window_s=traced["window_s"])
        if "breakdown" in traced:
            out["breakdown"] = traced["breakdown"]
    out["record"] = {k: v for k, v in record.items()
                     if k not in ("spans", "breakdown", "delivery_s",
                                  "peaks")}
    out["checks"] = checks
    return out, record
