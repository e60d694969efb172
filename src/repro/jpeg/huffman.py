"""Entropy (Huffman) decode: scan bytes -> per-component DCT coefficients.

This stage is bit-serial *within* a restart segment (each symbol's
position depends on the previous), so it runs on the host CPU —
mirroring the paper's CPU-decode scope; the parallel transform stages
(dequant/IDCT/color) are JAX/Pallas. Baseline decode looks up a 16-bit
window of the stream per symbol (libjpeg-style) rather than walking bits:
for AC, one lookup in ``_ac_table`` yields the code's length, run and the
coefficient itself whenever code and magnitude bits fit the window, and
only the rare pairs longer than 16 bits read their magnitude bits apart
(the slow path, counted as ``ac_slow``). The bit buffer is three local
ints, and coefficients go straight into one flat buffer per component.
Progressive decode keeps ``BitReader`` and the plain ``T.decode_lut``
tables.

Restart intervals (DRI/RSTn) break that serial chain: each segment is
byte-aligned and starts with DC predictors at 0 (F.2.2.4), so per-segment
decode is a **pure function** of (segment bytes, Huffman tables,
component layout, MCU count) — the self-synchronization property
Weißenberger & Schmidt exploit for GPU entropy decode. ``decode_segment``
is that pure function; serial and parallel decode both compose it, so
parallel output is byte-identical to serial by construction.

Parallel decode fans segments out to a shared fork-based
``ProcessPoolExecutor`` (the inner decode loop is pure Python and
GIL-bound — threads cannot speed it up). The worker count is an ambient
knob: ``REPRO_ENTROPY_WORKERS`` sets the process default, and the
``entropy_workers(n)`` context manager overrides it per call site (it is
a ContextVar — wrap at the decode call, pool worker threads do not
inherit a parent thread's override). Images without restart intervals
fall back to serial decode, recorded via the ``jpeg.entropy`` span args,
a ``jpeg.entropy.fallback`` instant, and the ``entropy_stats()``
counters — never silently. See DESIGN.md §10.
"""
from __future__ import annotations

import contextlib
import contextvars
import multiprocessing
import os
import threading
import time
from array import array
from concurrent.futures import ProcessPoolExecutor
from functools import lru_cache
from itertools import chain, repeat
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.jpeg import tables as T
from repro.jpeg.parser import CorruptJpeg, DecodeSpec
from repro.obs import trace


class BitReader:
    __slots__ = ("data", "pos", "acc", "nbits", "n")

    def __init__(self, data: bytes):
        # destuff 0xFF00 -> 0xFF; restart markers are split out *before*
        # the reader sees the bytes (see _restart_segments), so the only
        # 0xFF sequences left inside a segment are stuffed data bytes.
        # mmap-backed sources hand us memoryviews; destuffing copies
        # regardless, so materializing here costs nothing extra.
        if not isinstance(data, (bytes, bytearray)):
            data = bytes(data)
        self.data = data.replace(b"\xff\x00", b"\xff")
        self.n = len(self.data)
        self.pos = 0
        self.acc = 0
        self.nbits = 0

    def peek16(self) -> int:
        while self.nbits < 16:
            b = self.data[self.pos] if self.pos < self.n else 0
            self.pos += 1
            self.acc = ((self.acc << 8) | b) & 0xFFFFFF
            self.nbits += 8
        return (self.acc >> (self.nbits - 16)) & 0xFFFF

    def drop(self, k: int) -> None:
        self.nbits -= k

    def get(self, k: int) -> int:
        if k == 0:
            return 0
        while self.nbits < k:
            b = self.data[self.pos] if self.pos < self.n else 0
            self.pos += 1
            self.acc = ((self.acc << 8) | b) & 0xFFFFFF
            self.nbits += 8
        v = (self.acc >> (self.nbits - k)) & ((1 << k) - 1)
        self.nbits -= k
        return v

    def bits_consumed(self) -> int:
        """Bits actually decoded so far. ``peek16`` fabricates zero bytes
        past the segment end for lookahead; those stay buffered in
        ``acc``/``nbits`` until a symbol consumes them, so consumed >
        available is the signature of a truncated segment — the old
        silent-misdecode mode where garbage zero bits decoded as data."""
        return 8 * self.pos - self.nbits


def _extend(bits: int, size: int) -> int:
    if size == 0:
        return 0
    if bits < (1 << (size - 1)):
        return bits - (1 << size) + 1
    return bits


def _restart_segments(scan: bytes) -> list:
    """Split entropy-coded data at RSTn (0xFFD0..D7) marker boundaries.

    The markers themselves are byte-aligned and carry no entropy bits, so
    each returned segment is an independent bit stream: the decoder resets
    DC predictors and bit alignment at every boundary (F.2.2.4). Stuffed
    0xFF00 pairs are data, not markers, and are stepped over whole."""
    segs = []
    start = 0
    i = 0
    n = len(scan)
    while i < n - 1:
        if scan[i] == 0xFF:
            nxt = scan[i + 1]
            if 0xD0 <= nxt <= 0xD7:
                segs.append(scan[start:i])
                start = i + 2
            i += 2               # marker or stuffed pair: step over both
        else:
            i += 1
    segs.append(scan[start:])
    return segs


# ------------------------------------------------------------ ambient knob
def _env_default() -> int:
    try:
        return max(1, int(os.environ.get("REPRO_ENTROPY_WORKERS", "1")))
    except ValueError:
        return 1


_DEFAULT_WORKERS = _env_default()
_WORKERS_VAR: contextvars.ContextVar[int] = contextvars.ContextVar(
    "repro_entropy_workers", default=0)   # 0 = inherit the process default


def current_entropy_workers() -> int:
    """The effective ambient worker count: an ``entropy_workers(n)``
    override if one is active on this thread, else the
    ``REPRO_ENTROPY_WORKERS`` process default (1 = serial)."""
    v = _WORKERS_VAR.get()
    return v if v > 0 else _DEFAULT_WORKERS


@contextlib.contextmanager
def entropy_workers(n: int):
    """Ambient override for the segment-decode worker count. ``n=1``
    forces serial even when ``REPRO_ENTROPY_WORKERS`` requests more —
    that is how the eligibility resolver demotes a decode site. ContextVar
    scope: wrap at the decode call site; pool worker threads do not
    inherit a parent thread's override."""
    token = _WORKERS_VAR.set(max(1, int(n)))
    try:
        yield
    finally:
        _WORKERS_VAR.reset(token)


# ------------------------------------------------------------ mode stats
class EntropyStats:
    """Thread-safe counters for serial/parallel mode decisions — the
    "recorded as such, not silently" half of the fallback contract.
    Consumers snapshot before/after a measured region and report the
    delta (see SingleThreadProtocol.run_path)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counts: Dict[str, int] = {}

    def bump(self, **deltas: int) -> None:
        with self._lock:
            for k, v in deltas.items():
                self._counts[k] = self._counts.get(k, 0) + v

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counts)


STATS = EntropyStats()


def entropy_stats() -> Dict[str, int]:
    """Process-wide counter snapshot: ``parallel_images``,
    ``serial_images``, ``segments_parallel``, ``fallback_*`` reasons, and
    over baseline images ``ac_symbols`` (nonzero AC coefficients decoded)
    and ``ac_slow`` (those whose code and magnitude bits ran past the
    16-bit window, so took the slow path)."""
    return STATS.snapshot()


# ------------------------------------------------------- shared executor
class _ExecutorCell:
    """Owns the process-wide segment-decode executor: one fork-context
    ``ProcessPoolExecutor`` shared by every decode site, created lazily
    and grown (never shrunk) to the largest requested worker count. No
    initializer/initargs: tasks are self-contained (segment bytes +
    hashable tables), so nothing corpus-sized crosses the fork boundary
    and workers rebuild LUTs via a per-process cache."""

    def __init__(self):
        self._lock = threading.Lock()
        self._pool: Optional[ProcessPoolExecutor] = None
        self._size = 0

    def get(self, workers: int) -> ProcessPoolExecutor:
        with self._lock:
            if self._pool is None or self._size < workers:
                if self._pool is not None:
                    self._pool.shutdown(wait=False, cancel_futures=True)
                self._pool = ProcessPoolExecutor(
                    max_workers=workers,
                    mp_context=multiprocessing.get_context("fork"))
                self._size = workers
            return self._pool


_EXECUTOR = _ExecutorCell()


def _reset_executor_after_fork() -> None:
    # a forked child (loader process workers) inherits the cell but not
    # the executor's queue-management threads — its copy is dead pipes.
    # Replace the whole cell so a child can never submit into it; the
    # resolver demotes child decode to serial anyway (daemonic guard).
    global _EXECUTOR
    _EXECUTOR = _ExecutorCell()


os.register_at_fork(after_in_child=_reset_executor_after_fork)


# ------------------------------------------------------ per-segment decode
def hashable_tables(htables) -> tuple:
    """``DecodeSpec.htables`` ({(tc, th): (bits, vals)}) as a hashable,
    picklable key — what ``decode_segment`` takes, so LUTs can be cached
    per process (parent and executor workers alike) instead of rebuilt
    per image (4 x 65536-entry LUT builds per decode before this)."""
    return tuple(sorted(
        (key, (tuple(bits), tuple(vals)))
        for key, (bits, vals) in htables.items()))


@lru_cache(maxsize=16)
def _luts_for(tables_key: tuple) -> dict:
    return {key: T.decode_lut(bits, vals) for key, (bits, vals)
            in tables_key}


def _lut_runs(lut_sym: np.ndarray, lut_len: np.ndarray):
    """(symbol, code length, windows) for each run of equal adjacent
    windows of a ``T.decode_lut``, in window order: a few hundred runs,
    where the 65536 windows would cost a numpy pass each."""
    change = np.flatnonzero((lut_sym[1:] != lut_sym[:-1])
                            | (lut_len[1:] != lut_len[:-1])) + 1
    starts = np.concatenate(([0], change))
    return zip(lut_sym[starts].tolist(), lut_len[starts].tolist(),
               np.diff(starts, append=65536).tolist())


def _expand(heads: list, counts: list) -> list:
    """The 65536-entry table holding ``heads[i]`` ``counts[i]`` times:
    its references share a few thousand objects."""
    return list(chain.from_iterable(map(repeat, heads, counts)))


def _dc_table(lut_sym: np.ndarray, lut_len: np.ndarray) -> list:
    """DC window -> ``size << 5 | code length``, or -1 for no code."""
    heads, counts = [], []
    for sym, length, windows in _lut_runs(lut_sym, lut_len):
        heads.append(sym << 5 | length if sym >= 0 else -1)
        counts.append(windows)
    return _expand(heads, counts)


# kinds of an AC entry that decodes no coefficient in one step (nb == 0)
_AC_BAD, _AC_EOB, _AC_ZRL, _AC_SLOW = -1, 0, 1, 0x100


def _ac_table(lut_sym: np.ndarray, lut_len: np.ndarray) -> list:
    """AC window -> ``(nb, run, value)``: code and magnitude decoded in
    one lookup. Where code length + magnitude size fit the 16-bit window,
    ``nb`` is the bits the pair takes, ``run`` the zeros before the
    coefficient and ``value`` the coefficient. Otherwise ``nb`` is 0,
    ``run`` the kind (``_AC_EOB``, ``_AC_ZRL``, ``_AC_BAD``, or
    ``_AC_SLOW | rs`` when the magnitude bits run past the window) and
    ``value`` the code length."""
    heads, counts = [], []
    for sym, length, windows in _lut_runs(lut_sym, lut_len):
        size = sym & 15
        if sym < 0:
            heads.append((0, _AC_BAD, 0))
        elif sym == 0:
            heads.append((0, _AC_EOB, length))
        elif sym == 0xF0:
            heads.append((0, _AC_ZRL, length))
        elif length + size > 16:
            heads.append((0, _AC_SLOW | sym, length))
        else:
            # per code, the 2**size magnitudes in bit order (F.2.2.1's
            # EXTEND), each over the windows its remaining bits leave
            nb, run = length + size, sym >> 4
            half = (1 << size) >> 1
            code = [(nb, run, b if b >= half else b - (1 << size) + 1)
                    for b in range(1 << size)]
            n_codes = windows >> (16 - length)   # > 1 if a symbol repeats
            heads += code * n_codes
            counts += [1 << (16 - nb)] * (len(code) * n_codes)
            continue
        counts.append(windows)
    return _expand(heads, counts)


@lru_cache(maxsize=16)
def _fast_tables_for(tables_key: tuple) -> dict:
    """{(tc, th): list} of ``_dc_table`` / ``_ac_table`` entries for one
    table set, about 2.3 MB. Cached apart from ``_luts_for``: progressive
    decode reads that one per scan and never needs these."""
    out = {}
    for key, (bits, vals) in tables_key:
        lut = T.decode_lut(bits, vals)
        out[key] = _ac_table(*lut) if key[0] else _dc_table(*lut)
    return out


class _SlowTally(threading.local):
    """AC symbols this thread decoded on the slow path, summed over its
    ``decode_segment`` calls; callers read the difference around theirs."""
    ac_slow = 0


_TALLY = _SlowTally()
_ZZ = T.ZIGZAG.tolist()
# zero bytes past a segment's end: one 32-bit refill of lookahead, so a
# refill that runs off the padded words has consumed past the real end
_PAD = b"\x00" * 4


def component_layout(spec: DecodeSpec) -> tuple:
    """The picklable component spec ``decode_segment`` takes:
    ((cid, h, v, td, ta), ...) in scan order."""
    return tuple((c.cid, c.h, c.v, c.td, c.ta) for c in spec.components)


def decode_segment(seg: bytes, tables_key: tuple, components: tuple,
                   n_mcus: int) -> Dict[int, np.ndarray]:
    """Decode ONE restart segment: a pure function of (segment bytes,
    Huffman tables, component layout, MCU count).

    The restart invariant (F.2.2.4) makes this self-contained: the
    segment is byte-aligned and DC predictors start at 0, so no state
    crosses segment boundaries. Returns ``{cid: int32 [n_mcus, v, h, 64]}``
    natural-order coefficient blocks indexed by segment-relative MCU;
    the caller scatters them into the image's block grid by absolute MCU
    index. Raises ``CorruptJpeg`` on invalid codes, run overflow, or a
    segment too short for its MCU count (truncation).

    One ``_ac_table`` lookup decodes an AC code with its magnitude. The
    bit buffer lives in locals: the ``nbits`` unread bits are the bottom
    of ``acc`` (consumed bits above them are masked off at 48 bits), and
    a 32-bit word comes in whenever fewer than 16 are left. Coefficients
    go straight into one flat buffer per component."""
    tabs = _fast_tables_for(tables_key)
    if not isinstance(seg, (bytes, bytearray)):
        seg = bytes(seg)
    data = seg.replace(b"\xff\x00", b"\xff")    # destuff
    n = len(data)
    words = np.frombuffer(data + b"\x00" * (-n % 4) + _PAD,
                          dtype=">u4").tolist()
    bufs, slots = {}, []
    for ci, (cid, h, v, td, ta) in enumerate(components):
        stride = 64 * h * v
        buf = bufs[cid] = array("i", bytes(4 * stride * n_mcus))
        for j in range(h * v):
            slots.append((ci, tabs[(0, td)], tabs[(1, ta)], buf, 64 * j,
                          stride))
    preds = [0] * len(components)
    zz = _ZZ
    acc = nbits = wi = slow = k = m = 0
    try:
        for m in range(n_mcus):
            for ci, dc, ac, buf, off, stride in slots:
                base = m * stride + off
                k = 1          # set before any refill: see the except
                if nbits < 16:
                    acc = ((acc << 32) | words[wi]) & 0xFFFFFFFFFFFF
                    wi += 1
                    nbits += 32
                e = dc[(acc >> (nbits - 16)) & 0xFFFF]
                if e < 0:
                    raise CorruptJpeg("bad DC code")
                nbits -= e & 31
                s = e >> 5
                if s:
                    while nbits < s:
                        acc = ((acc << 32) | words[wi]) & 0xFFFFFFFFFFFF
                        wi += 1
                        nbits += 32
                    nbits -= s
                    d = (acc >> nbits) & ((1 << s) - 1)
                    if d < 1 << (s - 1):
                        d -= (1 << s) - 1
                    preds[ci] += d
                buf[base] = preds[ci]
                while k < 64:
                    if nbits < 16:
                        acc = ((acc << 32) | words[wi]) & 0xFFFFFFFFFFFF
                        wi += 1
                        nbits += 32
                    nb, run, val = ac[(acc >> (nbits - 16)) & 0xFFFF]
                    if nb:
                        nbits -= nb
                        k += run
                        # zz[k] past 63 raises IndexError: a run overflow
                        buf[base + zz[k]] = val
                        k += 1
                    elif run == _AC_EOB:
                        nbits -= val
                        break
                    elif run == _AC_ZRL:
                        nbits -= val
                        k += 16
                    elif run == _AC_BAD:
                        raise CorruptJpeg("bad AC code")
                    else:      # magnitude bits past the window
                        nbits -= val
                        k += (run >> 4) & 15
                        s = run & 15
                        if nbits < s:
                            acc = ((acc << 32) | words[wi]) & 0xFFFFFFFFFFFF
                            wi += 1
                            nbits += 32
                        nbits -= s
                        a = (acc >> nbits) & ((1 << s) - 1)
                        if a < 1 << (s - 1):
                            a -= (1 << s) - 1
                        buf[base + zz[k]] = a
                        k += 1
                        slow += 1
    except IndexError:
        # from zz[k] with k > 63 (a run overflow), or from words[wi] once
        # the lookahead padding is spent; a run past 63 is refused even
        # where its magnitude bits would run off the end, as ever
        if k > 63:
            raise CorruptJpeg("AC run overflow") from None
        raise CorruptJpeg(
            f"truncated entropy segment: MCU {m} of {n_mcus} reads past "
            f"the {8 * n} bits available") from None
    _TALLY.ac_slow += slow
    consumed = 32 * wi - nbits
    if consumed > 8 * n:
        raise CorruptJpeg(
            f"truncated entropy segment: decoded {n_mcus} MCUs consumed "
            f"{consumed} bits of {8 * n} available")
    return {cid: np.frombuffer(bufs[cid], dtype=np.int32).reshape(
                n_mcus, v, h, 64)
            for cid, h, v, _, _ in components}


def _decode_chunk(segs: List[bytes], counts: List[int], tables_key: tuple,
                  components: tuple) -> list:
    """Executor task: decode a contiguous run of segments. Returns
    [(coefficients, t0, dur, ac_slow), ...] with CLOCK_MONOTONIC
    timestamps (system-wide on Linux), so the parent emits
    ``jpeg.entropy.segment`` spans and counts slow-path symbols for work
    that happened in a worker process."""
    out = []
    for seg, n_mcus in zip(segs, counts):
        slow0 = _TALLY.ac_slow
        t0 = time.monotonic()
        coef = decode_segment(seg, tables_key, components, n_mcus)
        out.append((coef, t0, time.monotonic() - t0,
                    _TALLY.ac_slow - slow0))
    return out


# ------------------------------------------------------------ whole image
def _segment_plan(spec: DecodeSpec) -> Tuple[list, List[int], int, int]:
    """-> (segments, per-segment MCU counts, mcu_rows, mcu_cols).

    Validates the segment count against the declared restart interval
    up front: a DRI that promises more segments than the scan carries
    (missing RSTn, or no markers at all) is corrupt — both serial and
    parallel decode must refuse it rather than hang or misdecode.
    Trailing extra segments (stray RSTn) are ignored, matching the
    pre-refactor serial decoder."""
    hmax = max(c.h for c in spec.components)
    vmax = max(c.v for c in spec.components)
    mcu_cols = (spec.width + 8 * hmax - 1) // (8 * hmax)
    mcu_rows = (spec.height + 8 * vmax - 1) // (8 * vmax)
    total = mcu_rows * mcu_cols
    ri = spec.restart_interval
    if not ri:
        return [spec.scan_data], [total], mcu_rows, mcu_cols
    expected = (total + ri - 1) // ri
    segs = _restart_segments(spec.scan_data)
    if len(segs) < expected:
        raise CorruptJpeg(
            f"missing RST marker for interval: DRI={ri} over {total} "
            f"MCUs expects {expected} segments, scan has {len(segs)}")
    counts = [ri] * (expected - 1) + [total - ri * (expected - 1)]
    return segs[:expected], counts, mcu_rows, mcu_cols


def _scatter(out: Dict[int, np.ndarray], coef: Dict[int, np.ndarray],
             m0: int, n_mcus: int, mcu_cols: int,
             components: tuple) -> None:
    """Place one segment's MCU-relative blocks into the global block
    grids by absolute MCU index (row-major my*mcu_cols + mx)."""
    ms = np.arange(m0, m0 + n_mcus)
    my, mx = ms // mcu_cols, ms % mcu_cols
    for cid, h, v, _, _ in components:
        blocks = coef[cid]
        tgt = out[cid]
        for dy in range(v):
            for dx in range(h):
                tgt[my * v + dy, mx * h + dx] = blocks[:, dy, dx]


def _chunk_bounds(n: int, k: int) -> List[Tuple[int, int]]:
    """Split n items into k contiguous near-equal chunks (one executor
    task each: bounds dispatch + pickling to k round trips per image)."""
    base, rem = divmod(n, k)
    bounds, lo = [], 0
    for i in range(k):
        hi = lo + base + (1 if i < rem else 0)
        bounds.append((lo, hi))
        lo = hi
    return bounds


def _resolve_mode(requested: int, n_segments: int) -> Tuple[str, str]:
    """(mode, fallback-reason). Parallel needs >1 requested workers, >1
    restart segments (no-DRI and whole-image-interval scans are a single
    serial bit stream), and a non-daemonic process (multiprocessing.Pool
    workers may not fork children — the loader's process mode decodes
    serially in-worker, which the eligibility resolver also enforces)."""
    if requested <= 1:
        return "serial", ""
    if n_segments <= 1:
        return "serial", "fallback_no_dri"
    if multiprocessing.current_process().daemon:
        return "serial", "fallback_daemonic_worker"
    return "parallel", ""


def _decode_serial(out, segs, counts, tables_key, components,
                   mcu_cols) -> int:
    """-> AC symbols decoded on the slow path."""
    slow0 = _TALLY.ac_slow
    m0 = 0
    multi = len(segs) > 1
    for seg, n_mcus in zip(segs, counts):
        if multi:
            with trace.span("jpeg.entropy.segment", mcus=n_mcus):
                coef = decode_segment(seg, tables_key, components, n_mcus)
        else:
            coef = decode_segment(seg, tables_key, components, n_mcus)
        _scatter(out, coef, m0, n_mcus, mcu_cols, components)
        m0 += n_mcus
    return _TALLY.ac_slow - slow0


def _decode_parallel(out, segs, counts, tables_key, components, workers,
                     mcu_cols) -> int:
    """-> AC symbols decoded on the slow path."""
    pool = _EXECUTOR.get(workers)
    bounds = _chunk_bounds(len(segs), min(workers, len(segs)))
    futs = []
    for lo, hi in bounds:
        chunk = [s if isinstance(s, bytes) else bytes(s)
                 for s in segs[lo:hi]]
        futs.append((lo, pool.submit(_decode_chunk, chunk, counts[lo:hi],
                                     tables_key, components)))
    offsets = [0]
    for n in counts:
        offsets.append(offsets[-1] + n)
    slow = 0
    for lo, fut in futs:
        for k, (coef, t0, dur, ac_slow) in enumerate(fut.result()):
            trace.complete("jpeg.entropy.segment", t0, dur,
                           mcus=counts[lo + k], parallel=True)
            _scatter(out, coef, offsets[lo + k], counts[lo + k],
                     mcu_cols, components)
            slow += ac_slow
    return slow


def decode_coefficients(spec: DecodeSpec,
                        workers: Optional[int] = None
                        ) -> Dict[int, np.ndarray]:
    """-> {cid: int32 [by, bx, 8, 8] natural-order coefficient blocks}
    (by/bx = MCU-padded component block grid).

    ``workers`` > 1 requests interval-parallel decode (None = the ambient
    ``current_entropy_workers()``); the actual mode is resolved per image
    (see ``_resolve_mode``) and recorded on the ``jpeg.entropy`` span,
    with serial fallbacks also counted in ``entropy_stats()`` and marked
    by a ``jpeg.entropy.fallback`` instant. Serial and parallel decode
    run the same ``decode_segment`` pure function, so their coefficient
    output is byte-identical by construction.

    SOF2 streams dispatch to the progressive decoder (multi-scan
    coefficient accumulation, same output layout) — every decode path
    inherits progressive support through this single entry point."""
    if spec.progressive:
        from repro.jpeg import progressive as _progressive
        return _progressive.decode_coefficients_progressive(spec, workers)
    requested = int(workers) if workers else current_entropy_workers()
    components = component_layout(spec)
    tables_key = hashable_tables(spec.htables)
    segs, counts, mcu_rows, mcu_cols = _segment_plan(spec)
    out: Dict[int, np.ndarray] = {}
    for c in spec.components:
        out[c.cid] = np.zeros((mcu_rows * c.v, mcu_cols * c.h, 64),
                              dtype=np.int32)
    mode, fallback = _resolve_mode(requested, len(segs))
    with trace.span("jpeg.entropy") as sp:
        sp.set(mode=mode, segments=len(segs),
               workers=requested if mode == "parallel" else 1)
        if mode == "parallel":
            STATS.bump(parallel_images=1, segments_parallel=len(segs))
            slow = _decode_parallel(out, segs, counts, tables_key,
                                    components, requested, mcu_cols)
        else:
            bumps = {"serial_images": 1}
            if fallback:
                # a parallel request demoted to serial is never silent:
                # span arg + instant event + process-wide counter
                sp.set(fallback=fallback)
                trace.instant("jpeg.entropy.fallback", reason=fallback,
                              workers=requested)
                bumps[fallback] = 1
            STATS.bump(**bumps)
            slow = _decode_serial(out, segs, counts, tables_key,
                                  components, mcu_cols)
        # a decoded AC magnitude is never 0, so nonzero ACs count symbols
        symbols = sum(int(np.count_nonzero(g[..., 1:])) for g in out.values())
        sp.set(ac_symbols=symbols, ac_slow=slow)
        STATS.bump(ac_symbols=symbols, ac_slow=slow)
    for c in spec.components:
        by, bx, _ = out[c.cid].shape
        out[c.cid] = out[c.cid].reshape(by, bx, 8, 8)
    return out
