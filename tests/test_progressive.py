"""Progressive (SOF2) decode subsystem: round-trip byte-identity against
the baseline pipeline, Pillow cross-checks in both directions, malformed
scan-script rejection, unsupported-SOF classification, capability-gated
probe/eligibility flow, the corpus distribution knobs, and the corpus
bench axis (registry cells + single-thread skip records).
"""
import numpy as np
import pytest

from repro.codecs import (Capabilities, ExecContext, eligible, get_decoder,
                          probe_outcome)
from repro.jpeg import encoder, huffman
from repro.jpeg import parser as P
from repro.jpeg.corpus import build_corpus, corpus_fingerprint
from repro.jpeg.parser import CorruptJpeg, Scan, UnsupportedJpeg
from repro.obs import trace


def _img(h=48, w=48, seed=0):
    rng = np.random.RandomState(seed)
    base = (rng.rand(h, w, 3) * 255).astype(np.uint8)
    # low-pass a little so progressive streams look photographic-ish
    return ((base.astype(np.int32) + np.roll(base, 1, 0) +
             np.roll(base, 1, 1)) // 3).astype(np.uint8)


def _prog(img, **kw):
    kw.setdefault("quality", 85)
    return encoder.encode_jpeg(img, progressive=True, **kw)


def _base(img, **kw):
    kw.setdefault("quality", 85)
    return encoder.encode_jpeg(img, **kw)


DEC = get_decoder("numpy-fast").fn


# --------------------------------------------------------------- round-trip
@pytest.mark.parametrize("script", ["spectral", "standard"])
@pytest.mark.parametrize("sub", ["444", "420"])
@pytest.mark.parametrize("ri", [0, 4])
def test_roundtrip_byte_identity(script, sub, ri):
    """A progressive encode of the same coefficients decodes to the SAME
    pixels as the baseline encode — the accumulation invariant, measured
    at the pipeline's output."""
    img = _img(41, 56, seed=3)
    a = DEC(_base(img, subsampling=sub, restart_interval=ri))
    b = DEC(_prog(img, subsampling=sub, restart_interval=ri,
                  scan_script=script))
    np.testing.assert_array_equal(a, b)


def test_roundtrip_odd_dims_420():
    """Luma's padded MCU grid exceeds its ceil-dims block grid here; AC
    scans cover only ceil dims, and the spatial crop must still agree."""
    img = _img(70, 70, seed=5)
    a = DEC(_base(img, subsampling="420"))
    b = DEC(_prog(img, subsampling="420", scan_script="standard"))
    np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("script", ["spectral", "standard"])
def test_roundtrip_ycck_progressive(script):
    img = _img(40, 40, seed=11)
    a = DEC(encoder.encode_jpeg_ycck(img, quality=88))
    b = DEC(encoder.encode_jpeg_ycck(img, quality=88, progressive=True,
                                     scan_script=script))
    np.testing.assert_array_equal(a, b)


def test_all_builtin_nonstrict_paths_inherit_progressive():
    """Every non-strict registered path decodes SOF2 through the shared
    entropy dispatch to the same pixels its own baseline decode yields
    (paths differ from each other only in IDCT arithmetic, so the
    invariant is per-path); strict paths refuse with a typed
    UnsupportedJpeg."""
    from repro.codecs import list_decoders
    img = _img(24, 24, seed=2)
    prog = _prog(img, scan_script="spectral")
    base = _base(img)
    for spec in list_decoders():
        if spec.caps.engine == "pallas":    # interpret-mode: correctness
            continue                        # covered by test_kernels
        if spec.caps.strict:
            with pytest.raises(UnsupportedJpeg, match="progressive"):
                spec.fn(prog)
        elif spec.caps.engine in ("numpy", "jnp") and spec.caps.progressive:
            np.testing.assert_array_equal(
                np.asarray(spec.fn(prog)), np.asarray(spec.fn(base)),
                err_msg=spec.name)


# ---------------------------------------------------------- Pillow parity
def test_pillow_cross_check_both_directions():
    """(a) our progressive bytes through libjpeg == our baseline bytes
    through libjpeg (validates the encoder); (b) a libjpeg-written
    progressive stream through our decoder == its baseline twin through
    our decoder (validates the decoder against optimized-table streams
    with per-scan DHT and real EOBn runs)."""
    Image = pytest.importorskip("PIL.Image")
    import io

    img = _img(56, 72, seed=9)

    def pil_decode(data):
        with Image.open(io.BytesIO(data)) as im:
            return np.asarray(im.convert("RGB"))

    for sub in ("444", "420"):
        np.testing.assert_array_equal(
            pil_decode(_base(img, subsampling=sub)),
            pil_decode(_prog(img, subsampling=sub)))

    def pil_encode(progressive):
        buf = io.BytesIO()
        Image.fromarray(img).save(buf, format="JPEG", quality=90,
                                  progressive=progressive, optimize=True)
        return buf.getvalue()

    sp = P.parse(pil_encode(True))
    assert sp.progressive and len(sp.scans) > 1
    np.testing.assert_array_equal(DEC(pil_encode(True)),
                                  DEC(pil_encode(False)))


# ------------------------------------------------------------------ parsing
def test_parse_progressive_scans_both_modes():
    data = _prog(_img(seed=1), scan_script="standard")
    full = P.parse(data)
    assert full.progressive and len(full.scans) == 10
    for sc in full.scans:
        assert sc.data and sc.htables
    # headers_only stops at the first SOS (a probe never walks entropy
    # bytes) but still classifies the stream and carries that scan header
    heads = P.parse(data, headers_only=True)
    assert heads.progressive and len(heads.scans) == 1
    s0, f0 = heads.scans[0], full.scans[0]
    assert (s0.ss, s0.se, s0.ah, s0.al) == (f0.ss, f0.se, f0.ah, f0.al)
    assert s0.data == b"" and s0.htables


@pytest.mark.parametrize("headers_only", [False, True])
@pytest.mark.parametrize("marker,name", [(0xC9, "SOF9"), (0xC3, "SOF3"),
                                         (0xCB, "SOF11")])
def test_unknown_sof_raises_typed_unsupported(headers_only, marker, name):
    """The old parser fell through unknown SOF markers and misparsed the
    stream downstream; now both modes classify and refuse them."""
    data = _base(_img(seed=4))
    assert data.count(b"\xff\xc0") == 1
    forged = data.replace(b"\xff\xc0", bytes([0xFF, marker]), 1)
    with pytest.raises(UnsupportedJpeg, match=name):
        P.parse(forged, headers_only=headers_only)


def _spec_with_scans(scans):
    data = _prog(_img(seed=6), scan_script="spectral")
    spec = P.parse(data)
    return P.DecodeSpec(
        height=spec.height, width=spec.width,
        components=spec.components, qtables=spec.qtables,
        htables=spec.htables, scan_data=spec.scan_data,
        progressive=True, restart_interval=0,
        scans=[Scan(comps=c, ss=ss, se=se, ah=ah, al=al,
                    data=spec.scans[0].data, htables=spec.scans[0].htables)
               for (c, ss, se, ah, al) in scans])


def test_malformed_scan_scripts_raise_typed():
    from repro.jpeg import progressive as PR
    base = P.parse(_prog(_img(seed=6), scan_script="spectral"))
    dc = [(c.cid, 0, 0) for c in base.components]
    y = [(base.components[0].cid, 0, 0)]
    cases = [
        ([(y, 1, 63, 0, 0)], "AC scan before first DC"),
        ([(dc, 0, 5, 0, 0)], "mixes DC and AC"),
        ([(dc, 0, 0, 0, 0), (dc, 1, 63, 0, 0)], "non-interleaved"),
        ([(dc, 0, 0, 0, 0), (dc, 0, 0, 0, 0)], "sent twice"),
        ([(dc, 0, 0, 0, 15)], "successive approximation out of range"),
        ([(dc, 0, 0, 2, 0)], "refinement must shift one bit"),
        ([(dc, 0, 0, 1, 0)], "expects prior Al"),
        ([(y, 9, 3, 0, 0)], "invalid spectral band"),
    ]
    for scans, msg in cases:
        with pytest.raises(CorruptJpeg, match=msg):
            PR.decode_coefficients_progressive(_spec_with_scans(scans))
    with pytest.raises(CorruptJpeg, match="no scans"):
        PR.decode_coefficients_progressive(_spec_with_scans([]))


def test_truncated_progressive_scan_raises():
    data = _prog(_img(48, 48, seed=8), scan_script="standard")
    eoi = data.rfind(b"\xff\xd9")
    truncated = data[:eoi - 30] + data[eoi:]
    spec = P.parse(truncated)
    with pytest.raises(CorruptJpeg):
        huffman.decode_coefficients(spec)


# ------------------------------------------------------- probe / capability
def test_probe_outcome_classifies_and_traces():
    prog = _prog(_img(seed=2))
    base = _base(_img(seed=2))
    forged = base.replace(b"\xff\xc0", b"\xff\xc9", 1)

    tracer = trace.Tracer()
    with trace.use_tracer(tracer):
        # no caps: progressive inputs get a bucket key like any other
        r = probe_outcome(prog)
        assert not r.skip and r.key is not None and r.progressive
        # baseline-only caps: progressive resolves to a skip, not a throw
        r2 = probe_outcome(prog, caps=Capabilities(engine="numpy"))
        assert r2.skip and "progressive" in r2.skip_reason
        # unsupported frame family: skip regardless of caps
        r3 = probe_outcome(forged)
        assert r3.skip and "SOF9" in r3.skip_reason
        # progressive-capable caps: measured like baseline
        r4 = probe_outcome(prog, caps=Capabilities(engine="numpy",
                                                   progressive=True))
        assert not r4.skip
    skips = [e for e in tracer.collect()
             if e.get("name") == "jpeg.probe.skip"]
    assert len(skips) == 2


def test_eligible_requires_progressive_veto():
    caps = Capabilities(engine="numpy")
    v = eligible(caps, ExecContext.INLINE, requires_progressive=True)
    assert not v and "Capabilities.progressive" in v.reason
    assert eligible(caps, ExecContext.INLINE)       # baseline unaffected
    ok = Capabilities(engine="numpy", progressive=True)
    assert eligible(ok, ExecContext.INLINE, requires_progressive=True)


def test_builtin_capability_split():
    from repro.codecs import list_decoders
    strict = {s.name for s in list_decoders(strict=True)}
    assert strict and all(not s.caps.progressive
                          for s in list_decoders(strict=True))
    assert get_decoder("numpy-fast").caps.progressive
    assert get_decoder("jnp-fused").caps.progressive


# -------------------------------------------------------------- observability
def test_per_scan_entropy_spans():
    data = _prog(_img(seed=7), scan_script="standard")
    spec = P.parse(data)
    tracer = trace.Tracer()
    with trace.use_tracer(tracer):
        huffman.decode_coefficients(spec)
    evs = tracer.collect()
    outer = [e for e in evs if e["name"] == "jpeg.entropy"
             and e["ph"] == "X"]
    assert len(outer) == 1 and outer[0]["args"]["mode"] == "progressive"
    scans = [e for e in evs if e["name"] == "jpeg.entropy.scan"]
    assert len(scans) == len(spec.scans)
    assert [e["args"]["index"] for e in scans] == list(range(len(scans)))


def test_parallel_request_falls_back_recorded():
    """Interval-parallel entropy decode does not apply across scans:
    a workers>1 request on a progressive stream is a recorded serial
    fallback, never silent."""
    data = _prog(_img(48, 48, seed=3), restart_interval=2)
    spec = P.parse(data)
    before = huffman.entropy_stats()
    huffman.decode_coefficients(spec, workers=4)
    delta = {k: v - before.get(k, 0)
             for k, v in huffman.entropy_stats().items()}
    assert delta.get("fallback_progressive_scan") == 1
    assert delta.get("progressive_images") == 1
    assert delta.get("serial_images") == 1
    assert not delta.get("parallel_images")


# ------------------------------------------------------------------- corpus
def test_corpus_knobs_are_rng_neutral_when_unset():
    a = build_corpus(8, seed=42)
    b = build_corpus(8, seed=42, progressive=0.0, qualities=None,
                     subsamplings=None, size_weights=None)
    assert corpus_fingerprint(a) == corpus_fingerprint(b)
    assert a.progressive_indices == []


def test_corpus_progressive_fraction_and_rare_stays_baseline():
    c = build_corpus(10, seed=1, progressive=1.0)
    assert c.rare_index not in c.progressive_indices
    non_rare = [i for i in range(10) if i != c.rare_index]
    assert c.progressive_indices == non_rare
    for i in range(10):
        assert P.parse(c.files[i], headers_only=True).progressive == \
            (i in c.progressive_indices)
    m = build_corpus(10, seed=1, progressive=0.5)
    assert 0 < len(m.progressive_indices) < len(non_rare)


def test_corpus_distribution_knobs():
    c = build_corpus(10, seed=2, qualities=[50], subsamplings=["444"],
                     size_weights=[1, 0, 0, 0, 0])
    assert all(s == (64, 64) for s in c.sizes)
    for i, f in enumerate(c.files):
        if i == c.rare_index:
            continue
        spec = P.parse(f, headers_only=True)
        assert all((co.h, co.v) == (1, 1) for co in spec.components)
    with pytest.raises(ValueError, match="size_weights"):
        build_corpus(4, seed=0, size_weights=[1.0])


# --------------------------------------------------------------- bench axis
def test_registry_emits_corpus_cells_for_every_path():
    from repro.bench.registry import build_registry
    from repro.jpeg.paths import DECODE_PATHS
    reg = build_registry()
    names = {s.name for s in reg}
    for p in DECODE_PATHS:
        for c in ("mixed", "progressive"):
            assert f"single/{p}/corpus-{c}" in names
    # suffixless single cells stay corpus=baseline: compare keys stable
    assert all(s.corpus == "baseline" for s in reg
               if s.kind == "single_thread" and "/corpus-" not in s.name)


def test_smoke_profile_runs_exactly_two_corpus_cells():
    from repro.bench.registry import PROFILES, build_registry
    smoke = PROFILES["smoke"]
    ran = {s.name for s in build_registry()
           if s.corpus != "baseline" and smoke.wants(s)[0]}
    assert ran == {"single/jnp-fused/corpus-mixed",
                   "single/strict-fast/corpus-progressive"}


def test_single_thread_protocol_capability_skip_record():
    from repro.core.protocols import SingleThreadProtocol
    from repro.core.schema import validate_record
    c = build_corpus(6, seed=5, progressive=1.0)
    st = SingleThreadProtocol(c, repeats=1, warmup=False,
                              corpus_kind="progressive")
    rec = st.run_path("strict-fast")
    assert rec.status == "skipped" and rec.samples == []
    assert rec.meta["eligible"] is False
    assert "Capabilities.progressive" in rec.meta["reason"]
    assert rec.meta["corpus"] == "progressive"
    validate_record(rec.to_json())
    ok = st.run_path("numpy-fast")
    assert ok.status == "ok" and ok.meta["delivered"] == len(c.files)


def test_single_thread_protocol_mixed_corpus_counts_delivered():
    """On a mixed corpus a strict (baseline-only) path still runs: it
    delivers the baseline majority and records per-image skips, and
    throughput counts only what was delivered."""
    from repro.core.protocols import SingleThreadProtocol
    c = build_corpus(8, seed=6, progressive=0.5)
    assert c.progressive_indices
    st = SingleThreadProtocol(c, repeats=1, warmup=False,
                              corpus_kind="mixed")
    rec = st.run_path("strict-fast")
    assert rec.status == "ok"
    expect_skips = sorted(c.progressive_indices + [c.rare_index])
    assert rec.skip_indices == expect_skips
    assert rec.meta["delivered"] == len(c.files) - len(expect_skips)


# ------------------------------------------------------------------ service
def test_service_decodes_progressive_and_skips_unsupported():
    """End-to-end through the decode service: progressive inputs decode
    on progressive-capable arms; an unsupported frame family flows
    through probe -> keyless batch -> skip machinery and fails its own
    future with a typed error while batch-mates are served."""
    from repro.codecs import contrib, list_decoders
    from repro.service.engine import DecodeService, ServiceConfig

    prog = _prog(_img(seed=12))
    forged = _base(_img(seed=13)).replace(b"\xff\xc0", b"\xff\xc9", 1)
    want = DEC(prog)
    # the built-in arms: a contrib backend (pillow, opencv) registered by
    # an earlier test in the process would decode the forged SOF9 frame
    arms = [s for s in list_decoders(context=ExecContext.SERVICE)
            if s.name not in contrib.available()]
    cfg = ServiceConfig(num_workers=2, cache_bytes=0, seed=1)
    with DecodeService(cfg, paths=arms) as svc:
        futs = [svc.submit(prog) for _ in range(4)]
        bad = svc.submit(forged)
        for f in futs:
            np.testing.assert_array_equal(f.result(timeout=60), want)
        with pytest.raises(UnsupportedJpeg):
            bad.result(timeout=60)
