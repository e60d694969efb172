"""JPEG codec: roundtrip quality, all 13 decode paths vs oracle, strictness."""
import numpy as np
import pytest

from repro.jpeg import encoder, huffman, pipeline
from repro.jpeg import parser as P
from repro.jpeg.corpus import build_corpus, natural_image, scaled_rare_index
from repro.jpeg.paths import DECODE_PATHS
from repro.jpeg.parser import UnsupportedJpeg


def _img(h=72, w=88, seed=0):
    return natural_image(np.random.RandomState(seed), h, w)


@pytest.mark.parametrize("sub", ["444", "420"])
def test_roundtrip_error_reasonable(sub):
    img = _img()
    data = encoder.encode_jpeg(img, quality=90, subsampling=sub)
    out = DECODE_PATHS["numpy-ref"].decode(data)
    assert out.shape == img.shape and out.dtype == np.uint8
    err = np.abs(out.astype(int) - img.astype(int)).mean()
    assert err < 8.0, err


def test_quality_monotonic():
    img = _img(seed=1)
    errs, sizes = [], []
    for q in [30, 60, 90]:
        data = encoder.encode_jpeg(img, quality=q, subsampling="444")
        out = DECODE_PATHS["numpy-ref"].decode(data)
        errs.append(np.abs(out.astype(int) - img.astype(int)).mean())
        sizes.append(len(data))
    assert errs[0] >= errs[1] >= errs[2]
    assert sizes[0] <= sizes[1] <= sizes[2]


def test_non_multiple_of_8_dims():
    img = _img(h=50, w=67, seed=2)
    for sub in ["444", "420"]:
        data = encoder.encode_jpeg(img, quality=92, subsampling=sub)
        out = DECODE_PATHS["numpy-ref"].decode(data)
        assert out.shape == (50, 67, 3)


def test_all_paths_agree_with_oracle(corpus):
    refs = {}
    oracle = DECODE_PATHS["numpy-ref"]
    for i, f in enumerate(corpus.files):
        refs[i] = oracle.decode(f)
    for name, path in DECODE_PATHS.items():
        if path.engine not in ("numpy", "jnp", "pallas"):
            # contrib real backends (pillow/opencv) implement their own
            # IDCT/upsampling/YCCK choices; their looser agreement bound
            # is pinned in tests/test_codecs.py, not this sweep, which
            # checks that OUR engines implement identical math
            continue
        skips = []
        for i, f in enumerate(corpus.files):
            try:
                out = path.decode(f)
            except UnsupportedJpeg:
                skips.append(i)
                continue
            err = np.abs(out.astype(int) - refs[i].astype(int)).max()
            # the YCCK inversion multiplies clamped CMY by K, which
            # amplifies rounding differences between engines on the rare
            # 4-component image
            tol = 16 if i == corpus.rare_index else 4
            assert err <= tol, (name, i, err)
        if path.strict:
            assert skips == [corpus.rare_index], (name, skips)
        else:
            assert skips == [], (name, skips)


def test_ycck_rare_image_policies():
    img = _img(h=40, w=48, seed=3)
    data = encoder.encode_jpeg_ycck(img, quality=92)
    spec = P.parse(data)
    assert len(spec.components) == 4 and spec.adobe_transform == 2
    with pytest.raises(UnsupportedJpeg):
        P.check_strict(spec)
    out = DECODE_PATHS["numpy-ref"].decode(data)
    err = np.abs(out.astype(int) - img.astype(int)).mean()
    assert err < 10.0, err


def test_parser_rejects_garbage():
    with pytest.raises(P.CorruptJpeg):
        P.parse(b"\x00\x01not a jpeg")


def test_corpus_structure():
    c = build_corpus(25, seed=0)
    assert len(c.files) == 25
    assert c.rare_index == scaled_rare_index(25)
    spec = P.parse(c.files[c.rare_index])
    assert len(spec.components) == 4
    # all others are 1- or 3-component
    for i, f in enumerate(c.files):
        if i != c.rare_index:
            assert len(P.parse(f).components) == 3


# ------------------------------------------------------- restart intervals
@pytest.mark.parametrize("sub", ["444", "420"])
@pytest.mark.parametrize("interval", [1, 2, 3])
def test_restart_interval_roundtrip(sub, interval):
    """encode with DRI -> decode matches the no-DRI decode byte-for-byte
    (pre-fix, RST bytes leaked into the bit reader => garbage pixels)."""
    img = _img(h=56, w=72, seed=4)
    plain = encoder.encode_jpeg(img, quality=88, subsampling=sub)
    dri = encoder.encode_jpeg(img, quality=88, subsampling=sub,
                              restart_interval=interval)
    spec = P.parse(dri)
    assert spec.restart_interval == interval
    assert b"\xff\xdd" in dri and b"\xff\xdd" not in plain
    a = DECODE_PATHS["numpy-ref"].decode(plain)
    b = DECODE_PATHS["numpy-ref"].decode(dri)
    np.testing.assert_array_equal(a, b)


def test_restart_marker_index_wraps_mod8():
    """More than 8 intervals: RSTn cycles D0..D7 and decode still works."""
    img = _img(h=96, w=96, seed=5)             # 4:2:0 -> 36 MCUs, ri=2 -> 17 RSTs
    dri = encoder.encode_jpeg(img, quality=85, subsampling="420",
                              restart_interval=2)
    plain = encoder.encode_jpeg(img, quality=85, subsampling="420")
    np.testing.assert_array_equal(DECODE_PATHS["numpy-ref"].decode(dri),
                                  DECODE_PATHS["numpy-ref"].decode(plain))


def test_restart_interval_all_paths_agree(corpus):
    """Restart handling lives in the shared entropy stage: every path
    (incl. batched) decodes a DRI file identically to its no-DRI twin."""
    img = _img(h=48, w=64, seed=6)
    plain = encoder.encode_jpeg(img, quality=90, subsampling="420")
    dri = encoder.encode_jpeg(img, quality=90, subsampling="420",
                              restart_interval=2)
    for name, path in DECODE_PATHS.items():
        np.testing.assert_array_equal(path.decode(plain), path.decode(dri),
                                      err_msg=name)


# -------------------------------------------------------- parser robustness
def test_parser_tolerates_fill_bytes():
    img = _img(h=24, w=24, seed=7)
    data = encoder.encode_jpeg(img, quality=90, subsampling="444")
    # inject 0xFF fill padding before the SOS marker (B.1.1.2 allows it)
    sos_at = data.index(b"\xff\xda")
    padded = data[:sos_at] + b"\xff\xff\xff" + data[sos_at:]
    np.testing.assert_array_equal(DECODE_PATHS["numpy-ref"].decode(padded),
                                  DECODE_PATHS["numpy-ref"].decode(data))


def test_parser_short_segment_payloads_raise_corrupt_jpeg():
    """Length-consistent but internally short payloads (Adobe APP14, DQT,
    DHT) surface as CorruptJpeg, not bare IndexError/ValueError."""
    def seg(marker, payload):
        import struct
        return struct.pack(">BBH", 0xFF, marker, len(payload) + 2) + payload

    base = b"\xff\xd8"
    with pytest.raises(P.CorruptJpeg):
        P.parse(base + seg(0xEE, b"Adobe\x00") + b"\xff\xd9")
    with pytest.raises(P.CorruptJpeg):
        P.parse(base + seg(0xDB, b"\x00" + b"\x01" * 10) + b"\xff\xd9")
    with pytest.raises(P.CorruptJpeg):
        P.parse(base + seg(0xC4, b"\x00" + b"\x01" * 5) + b"\xff\xd9")
    with pytest.raises(P.CorruptJpeg):         # bit counts promise values
        P.parse(base + seg(0xC4, b"\x00" + b"\x08" * 16) + b"\xff\xd9")


@pytest.mark.parametrize("clip", ["length", "payload", "marker"])
def test_parser_truncation_raises_corrupt_jpeg(clip):
    """Truncated streams raise CorruptJpeg, never bare struct.error or
    IndexError (the loader/service only catch the typed exceptions)."""
    img = _img(h=24, w=24, seed=8)
    data = encoder.encode_jpeg(img, quality=90, subsampling="444")
    sof_at = data.index(b"\xff\xc0")
    if clip == "length":
        bad = data[:sof_at + 3]                 # mid segment-length field
    elif clip == "payload":
        bad = data[:sof_at + 7]                 # declared length overruns
    else:
        bad = data[:sof_at] + b"\xff"           # lone 0xFF at EOF
    with pytest.raises(P.CorruptJpeg):
        P.parse(bad)


# ----------------------------------------------------- header-only parsing
def test_headers_only_parse_equivalence(corpus):
    from repro.service.batcher import bucket_key
    for f in corpus.files:
        full = P.parse(f)
        head = P.parse(f, headers_only=True)
        assert head.scan_data == b""
        assert (head.height, head.width) == (full.height, full.width)
        assert [(c.cid, c.h, c.v, c.tq) for c in head.components] == \
            [(c.cid, c.h, c.v, c.tq) for c in full.components]
        assert head.restart_interval == full.restart_interval
        # bucket_key (which now parses headers only) must key identically
        # to a full parse of the same file
        spec = full
        mcu_rows = -(-spec.height // spec.mcu_h)
        mcu_cols = -(-spec.width // spec.mcu_w)
        want = (((mcu_rows + 3) // 4) * 4, ((mcu_cols + 3) // 4) * 4,
                len(spec.components), tuple((c.h, c.v)
                                            for c in spec.components))
        assert bucket_key(f, granularity=4) == want


# ---------------------------------------------------------- batched decode
BATCHED = ("jnp-batch", "pallas-batch", "jnp-fused", "pallas-fused")


@pytest.mark.parametrize("name", BATCHED)
def test_decode_batch_byte_identical_to_serial(name, corpus):
    """Mixed corpus (sizes, qualities, subsamplings, the rare YCCK image)
    through one decode_batch == per-image decode, byte for byte."""
    path = DECODE_PATHS[name]
    batch = path.decode_batch(list(corpus.files))
    for i, (res, f) in enumerate(zip(batch, corpus.files)):
        np.testing.assert_array_equal(res, path.decode(f),
                                      err_msg=f"{name}[{i}]")


def test_decode_batch_isolates_bad_items(corpus):
    """A corrupt batch member comes back as its exception in place;
    batch-mates decode normally. Strict refusals surface per item too."""
    path = DECODE_PATHS["jnp-batch"]
    datas = [corpus.files[0], b"\x00\x01not-a-jpeg", corpus.files[1]]
    out = path.decode_batch(datas)
    assert isinstance(out[1], P.CorruptJpeg)
    np.testing.assert_array_equal(out[0], path.decode(corpus.files[0]))
    np.testing.assert_array_equal(out[2], path.decode(corpus.files[1]))
    strict = DECODE_PATHS["strict-fast"]
    out = strict.decode_batch([corpus.files[0],
                               corpus.files[corpus.rare_index]])
    assert isinstance(out[1], UnsupportedJpeg)
    assert not isinstance(out[0], BaseException)


def test_decode_batch_one_transform_per_structure_group(corpus):
    """The whole point of bucketing: B same-structure images cost ONE
    fused transform launch, not B."""
    from repro.obs import trace
    files = [encoder.encode_jpeg(_img(h=64, w=64, seed=10 + k),
                                 quality=85, subsampling="420")
             for k in range(4)]
    with trace.use_tracer(trace.Tracer()) as tracer:
        out = DECODE_PATHS["jnp-batch"].decode_batch(files)
    launches = [e for e in tracer.events() if e["name"] == "jpeg.transform"]
    assert len(launches) == 1 and launches[0]["args"] == {"batch": 4}
    assert all(not isinstance(r, BaseException) for r in out)


def test_bitwriter_stuffing_roundtrip():
    bw = encoder.BitWriter()
    bw.write(0xFF, 8)
    bw.write(0xFF, 8)
    out = bw.flush()
    assert out == b"\xff\x00\xff\x00"
    br = huffman.BitReader(out)
    assert br.get(8) == 0xFF and br.get(8) == 0xFF
