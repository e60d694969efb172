"""Reductions from what a run recorded to numbers: device busy time from
the profiler's trace, span totals, and the metric readers.

Times on the profiler's trace are nanoseconds from the start of tracing.
Spans of the program are ``time.monotonic`` seconds; the harness gives
the monotonic time at which tracing started, so both meet on one axis.
"""
from __future__ import annotations

import glob
import importlib.util
import os
from typing import Dict, Iterable, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
METRICS_DIR = os.path.join(HERE, "metrics")


# ------------------------------------------------------------ intervals
def union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    """Sorted, merged copy of [start, end) intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def clip(intervals, lo: float, hi: float) -> List[List[float]]:
    return [[max(a, lo), min(b, hi)] for a, b in intervals
            if b > lo and a < hi]


# ------------------------------------------------------------ the trace
def device_lines(plane):
    """The lines of a TPU plane on which each XLA operation is one event."""
    lines = {line.name: line for line in plane.lines}
    for name in ("XLA Ops", "XLA Modules"):
        if name in lines:
            return [lines[name]]
    return []


def is_device_plane(plane) -> bool:
    return plane.name.startswith("/device:TPU:")


def load_trace(log_dir: str):
    """The ``ProfileData`` of the one trace the profiler wrote there."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found "
                           f"{len(paths)}")
    return ProfileData.from_file(paths[0])


def device_events(profile, planes=is_device_plane, lines=device_lines):
    """{plane name: [(op name, start ns, end ns)]} of every device op."""
    out: Dict[str, list] = {}
    for plane in profile.planes:
        if not planes(plane):
            continue
        evs = out.setdefault(plane.name, [])
        for line in lines(plane):
            for ev in line.events:
                if ev.duration_ns > 0:
                    evs.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return out


def busy(events: Dict[str, list], lo_ns: float, hi_ns: float):
    """-> (busy seconds averaged over the chips, merged busy intervals of
    the first chip), inside [lo_ns, hi_ns)."""
    if not events:
        return 0.0, []
    per_chip = []
    first = None
    for name in sorted(events):
        merged = clip(union((a, b) for _, a, b in events[name]),
                      lo_ns, hi_ns)
        per_chip.append(sum(b - a for a, b in merged) / 1e9)
        if first is None:
            first = merged
    return sum(per_chip) / len(per_chip), first


def op_name(name: str) -> str:
    """An op's event name is its HLO text; keep the result and operation,
    ``%fusion.3 = f32[1536,128]{...} fusion``, without the operands."""
    return name.split("(", 1)[0].strip()[:120]


def top_ops(events: Dict[str, list], lo_ns: float, hi_ns: float,
            n: int = 10):
    """The device operations that took most time in the window."""
    tot: Dict[str, float] = {}
    for evs in events.values():
        for name, a, b in evs:
            a, b = max(a, lo_ns), min(b, hi_ns)
            if b > a:
                k = op_name(name)
                tot[k] = tot.get(k, 0.0) + (b - a) / 1e9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])
            [:n]]


def idle_gaps(busy_iv, lo_ns: float, hi_ns: float, spans, n: int = 10):
    """The longest gaps between device operations, each named by the host
    span that overlaps it most; ``spans`` are (name, start, end, thread)
    in ns on the trace's axis. Leaf spans win over the spans that contain
    them in their thread."""
    gaps, t = [], lo_ns
    for a, b in busy_iv:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi_ns > t:
        gaps.append((t, hi_ns))
    gaps.sort(key=lambda g: g[0] - g[1])
    leaf = _leaf_names(spans)
    out = []
    for a, b in gaps[:n]:
        over: Dict[str, float] = {}
        for name, s, e, _ in spans:
            o = min(b, e) - max(a, s)
            if o > 0:
                over[name] = over.get(name, 0.0) + o
        if over:
            name = max(over, key=lambda k: (k in leaf, over[k]))
        else:
            name = "no span"
        out.append([name, (b - a) / 1e9])
    return out


def _leaf_names(spans):
    """Names none of whose spans contains a span of another name in the
    same thread."""
    by_start = sorted(spans, key=lambda s: (s[3] or 0, s[1], -s[2]))
    container = set()
    open_: List[tuple] = []
    for name, s, e, tid in by_start:
        open_ = [o for o in open_ if o[3] == tid and o[2] > s]
        for o in open_:
            if o[0] != name and o[2] >= e:
                container.add(o[0])
        open_.append((name, s, e, tid))
    return {sp[0] for sp in spans} - container


# ------------------------------------------------------------ spans
def span_events(events, lo: float, hi: float):
    """(name, start s, end s) of complete spans that end inside [lo, hi]
    (monotonic seconds)."""
    out = []
    for ev in events:
        if ev.get("ph") != "X":
            continue
        s = ev["ts"] / 1e6
        e = s + ev.get("dur", 0.0) / 1e6
        if lo <= e <= hi:
            out.append((ev["name"], s, e, ev.get("tid")))
    return out


def span_totals(spans) -> Dict[str, dict]:
    tot: Dict[str, dict] = {}
    for name, s, e, _ in spans:
        t = tot.setdefault(name, {"total_s": 0.0, "count": 0})
        t["total_s"] += e - s
        t["count"] += 1
    return tot


# ------------------------------------------------------------ metrics
def load_file(path: str, attr: str):
    """The function ``attr`` of the Python file at ``path``, a file found
    by name (a metric's reader, an entry point's driver)."""
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no file at {path}")
    stem = os.path.basename(path)[:-3].replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(f"chipbench_{stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return getattr(mod, attr)


def metric_reader(name: str):
    """``read(record) -> float | None`` of ``metrics/<name>.py``."""
    return load_file(os.path.join(METRICS_DIR, f"{name}.py"), "read")


def read_metrics(metrics: List[dict], record: dict) -> Dict[str, dict]:
    """Each metric its reader finds a number for, with its unit."""
    out = {}
    for m in metrics:
        value: Optional[float] = metric_reader(m["name"])(record)
        if value is None:
            continue
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


# ------------------------------------------------------------ readers
def span_ms_per(record: dict, names, per: str) -> Optional[float]:
    """Milliseconds of the ``names`` spans in the traced window, per span
    of ``per`` (one per image for ``jpeg.entropy``)."""
    spans = record.get("spans")
    if not spans or per not in spans or not spans[per]["count"]:
        return None
    total = sum(spans[n]["total_s"] for n in names if n in spans)
    return 1e3 * total / spans[per]["count"]


def roofline_pct(record: dict) -> Optional[float]:
    """The least time of the window's decode work at the chip's peaks, as
    a share of the device's busy time in the window."""
    from chipbench.work import least_seconds
    if not record.get("busy_s") or not record.get("peaks") \
            or not record.get("work_bytes"):
        return None
    t, _ = least_seconds(record["work_ops"], record["work_bytes"],
                         record["peaks"])
    return 100.0 * t / record["busy_s"]


def idle_pct(record: dict) -> Optional[float]:
    if record.get("busy_s") is None or not record.get("window_s"):
        return None
    return 100.0 * (1.0 - record["busy_s"] / record["window_s"])
