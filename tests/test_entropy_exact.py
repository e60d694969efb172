"""The baseline entropy decoder against the loop it replaced.

``decode_segment`` decodes an AC code and its magnitude with one table
lookup, keeps its bit buffer in locals and writes one flat buffer per
component. ``oracle_segment`` below is the loop it replaced, kept here
unchanged: ``BitReader`` windows into ``T.decode_lut`` tables, one symbol
and one ``get`` at a time. Coefficients must match it bit for bit, serial
and interval-parallel, and corrupt streams must raise the same
``CorruptJpeg`` errors, never an ``IndexError``.
"""
import io

import numpy as np
import pytest

from repro.jpeg import encoder, huffman
from repro.jpeg import parser as P
from repro.jpeg import tables as T
from repro.jpeg.huffman import BitReader, _extend, _luts_for
from repro.jpeg.parser import CorruptJpeg


def oracle_segment(seg, tables_key, components, n_mcus):
    luts = _luts_for(tables_key)
    br = BitReader(seg)
    out = {cid: np.zeros((n_mcus, v, h, 64), dtype=np.int32)
           for cid, h, v, _, _ in components}
    preds = {cid: 0 for cid, _, _, _, _ in components}
    inv_zz = T.ZIGZAG  # zigzag index i -> natural position

    for m in range(n_mcus):
        for cid, h, v, td, ta in components:
            dc_sym, dc_len = luts[(0, td)]
            ac_sym, ac_len = luts[(1, ta)]
            grid = out[cid]
            for dy in range(v):
                for dx in range(h):
                    blk = np.zeros(64, dtype=np.int32)
                    w = br.peek16()
                    s = int(dc_sym[w])
                    if s < 0:
                        raise CorruptJpeg("bad DC code")
                    br.drop(int(dc_len[w]))
                    diff = _extend(br.get(s), s)
                    preds[cid] += diff
                    blk[0] = preds[cid]
                    k = 1
                    while k < 64:
                        w = br.peek16()
                        rs = int(ac_sym[w])
                        if rs < 0:
                            raise CorruptJpeg("bad AC code")
                        br.drop(int(ac_len[w]))
                        if rs == 0:          # EOB
                            break
                        if rs == 0xF0:       # ZRL
                            k += 16
                            continue
                        k += rs >> 4
                        size = rs & 0xF
                        if k > 63:
                            raise CorruptJpeg("AC run overflow")
                        blk[inv_zz[k]] = _extend(br.get(size), size)
                        k += 1
                    grid[m, dy, dx] = blk
    if br.bits_consumed() > 8 * br.n:
        raise CorruptJpeg(
            f"truncated entropy segment: decoded {n_mcus} MCUs consumed "
            f"{br.bits_consumed()} bits of {8 * br.n} available")
    return out


def _img(h, w, seed):
    rng = np.random.RandomState(seed)
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
    smooth = 128 + 60 * np.sin(yy / 5.0 + seed) * np.cos(xx / 7.0)
    noise = rng.randn(h, w, 3) * 12
    return np.clip(smooth[..., None] + noise, 0, 255).astype(np.uint8)


def _pillow(img, quality, subsampling=None, optimize=False):
    Image = pytest.importorskip("PIL.Image")
    kw = {} if subsampling is None else {"subsampling": subsampling}
    buf = io.BytesIO()
    Image.fromarray(img).save(buf, format="JPEG", quality=quality,
                              optimize=optimize, **kw)
    return buf.getvalue()


CASES = {
    "420-q60": lambda: _pillow(_img(70, 54, 1), 60, subsampling=2),
    "420-q95": lambda: _pillow(_img(70, 54, 2), 95, subsampling=2),
    "444-q60": lambda: _pillow(_img(54, 70, 3), 60, subsampling=0),
    "444-q95": lambda: _pillow(_img(54, 70, 4), 95, subsampling=0),
    "gray-q60": lambda: _pillow(_img(61, 45, 5)[..., 0], 60),
    "gray-q95": lambda: _pillow(_img(61, 45, 6)[..., 0], 95),
    "dri-serial": lambda: encoder.encode_jpeg(
        _img(64, 80, 7), quality=90, subsampling="420", restart_interval=3),
    "dri-parallel": lambda: encoder.encode_jpeg(
        _img(64, 80, 8), quality=90, subsampling="444", restart_interval=5),
    # optimize=True writes per-image tables whose rare symbols get long
    # codes: code + magnitude past 16 bits takes the slow path
    "long-codes": lambda: _pillow(_img(96, 96, 9) // 2 * 2 + 1, 100,
                                  subsampling=0, optimize=True),
}


def _oracle_coefficients(spec, monkeypatch):
    with monkeypatch.context() as mp:
        mp.setattr(huffman, "decode_segment", oracle_segment)
        return huffman.decode_coefficients(spec, workers=1)


def _nonzero_ac(coef):
    return sum(int(np.count_nonzero(c.reshape(-1, 64)[:, 1:]))
               for c in coef.values())


@pytest.mark.parametrize("case", sorted(CASES))
def test_matches_the_oracle_bit_for_bit(case, monkeypatch):
    spec = P.parse(CASES[case]())
    assert not spec.progressive
    if case.startswith("dri"):
        assert spec.restart_interval
    want = _oracle_coefficients(spec, monkeypatch)
    workers = 2 if case == "dri-parallel" else 1
    before = huffman.entropy_stats()
    got = huffman.decode_coefficients(spec, workers=workers)
    delta = {k: v - before.get(k, 0)
             for k, v in huffman.entropy_stats().items()}
    if case == "dri-parallel":
        assert delta.get("parallel_images") == 1
    assert set(got) == set(want)
    for cid in want:
        assert got[cid].dtype == want[cid].dtype == np.int32
        np.testing.assert_array_equal(got[cid], want[cid],
                                      err_msg=f"{case} cid={cid}")
    assert delta["ac_symbols"] == _nonzero_ac(want) > 0
    if case == "long-codes":
        assert delta["ac_slow"] > 0
    assert 0 <= delta["ac_slow"] <= delta["ac_symbols"]


def test_segment_from_a_memoryview_matches():
    spec = P.parse(CASES["420-q95"]())
    key = huffman.hashable_tables(spec.htables)
    comps = huffman.component_layout(spec)
    segs, counts, _, _ = huffman._segment_plan(spec)
    a = huffman.decode_segment(memoryview(segs[0]), key, comps, counts[0])
    b = oracle_segment(segs[0], key, comps, counts[0])
    for cid in b:
        np.testing.assert_array_equal(a[cid], b[cid])


# ------------------------------------------------------- hand-made streams
_STD = huffman.hashable_tables({(0, 0): (T.DC_LUMA_BITS, T.DC_LUMA_VALS),
                                (1, 0): (T.AC_LUMA_BITS, T.AC_LUMA_VALS)})
_GRAY = ((1, 1, 1, 0, 0),)
_DC = T.canonical_codes(T.DC_LUMA_BITS, T.DC_LUMA_VALS)
_AC = T.canonical_codes(T.AC_LUMA_BITS, T.AC_LUMA_VALS)


def _stream(*blocks, tail_ones=0):
    """Gray blocks, each DC size 0 then its (AC symbol, magnitude bits)
    pairs; ``tail_ones`` 1-bits after them (no code here is all 1s)."""
    bw = encoder.BitWriter()
    for symbols in blocks:
        bw.write(*_DC[0])
        for rs, bits in symbols:
            bw.write(*_AC[rs])
            if rs & 15:
                bw.write(bits, rs & 15)
    for _ in range(tail_ones):
        bw.write(1, 1)
    return bw.flush()


def _scan(keep):
    """The 444-q95 case's scan, cut to its first ``keep`` bytes."""
    spec = P.parse(CASES["444-q95"]())
    segs, counts, _, _ = huffman._segment_plan(spec)
    return (segs[0][:keep], huffman.hashable_tables(spec.htables),
            huffman.component_layout(spec), counts[0])


STREAMS = {
    # (segment bytes, tables, components, MCUs) for decode_segment
    "truncated-by-a-few-bytes": lambda: _scan(-3),
    "truncated-past-the-padding": lambda: _scan(12),
    "bad-dc-code": lambda: (b"\xff\x00\xff\x00\xff\x00", _STD, _GRAY, 1),
    "bad-ac-code": lambda: (_stream([], tail_ones=20), _STD, _GRAY, 1),
    # three (run 15, size 1) symbols reach k = 49; one more overflows
    "run-overflow-fast": lambda: (_stream([(0xF1, 1)] * 4), _STD, _GRAY, 1),
    # 0xFA: a 16-bit code with 10 magnitude bits, so the slow path
    "run-overflow-slow": lambda: (
        _stream([(0xF1, 1)] * 3 + [(0xFA, 1023)]), _STD, _GRAY, 1),
    # a ZRL past index 63 ends the block, as it always has
    "zrl-past-the-end": lambda: (
        _stream([(0xF1, 1)] * 3 + [(0xF0, 0)], [(0x00, 0)]), _STD, _GRAY, 2),
    "slow-path-value": lambda: (
        _stream([(0xFA, 5), (0xFA, 1000), (0x00, 0)]), _STD, _GRAY, 1),
}
ERRORS = {"truncated-by-a-few-bytes": "truncated entropy segment",
          "truncated-past-the-padding": "truncated entropy segment",
          "bad-dc-code": "bad DC code", "bad-ac-code": "bad AC code",
          "run-overflow-fast": "AC run overflow",
          "run-overflow-slow": "AC run overflow"}


@pytest.mark.parametrize("case", sorted(STREAMS))
def test_hand_made_streams_decode_or_fail_as_the_oracle(case):
    args = STREAMS[case]()
    if case in ERRORS:
        for fn in (oracle_segment, huffman.decode_segment):
            with pytest.raises(CorruptJpeg, match=ERRORS[case]):
                fn(*args)
        return
    want = oracle_segment(*args)
    slow0 = huffman._TALLY.ac_slow
    got = huffman.decode_segment(*args)
    np.testing.assert_array_equal(got[1], want[1])
    if case == "slow-path-value":
        assert huffman._TALLY.ac_slow - slow0 == 2
        assert got[1][0, 0, 0, T.ZIGZAG[16]] == 5 - 1023
        assert got[1][0, 0, 0, T.ZIGZAG[32]] == 1000


def test_truncation_past_the_padding_never_raises_index_error():
    for keep in (0, 1, 2, 5, 9, 40):
        with pytest.raises(CorruptJpeg, match="truncated entropy segment"):
            huffman.decode_segment(*_scan(keep))


# ------------------------------------------------------------ the tables
@pytest.mark.parametrize("tc,bits,vals", [
    (0, T.DC_LUMA_BITS, T.DC_LUMA_VALS),
    (0, T.DC_CHROMA_BITS, T.DC_CHROMA_VALS),
    (1, T.AC_LUMA_BITS, T.AC_LUMA_VALS),
    (1, T.AC_CHROMA_BITS, T.AC_CHROMA_VALS),
    # over-subscribed, with a repeated symbol: what decode_lut keeps
    (1, [0, 0, 5, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0],
     [0x22, 0x22, 0x0F, 0x00, 0xF0]),
])
def test_fast_tables_agree_with_decode_lut_window_by_window(tc, bits, vals):
    sym, length = T.decode_lut(bits, vals)
    table = (huffman._ac_table if tc else huffman._dc_table)(sym, length)
    assert len(table) == 65536
    assert len({id(e) for e in table}) < 4096    # shared entries
    for w in range(65536):
        s, n, e = int(sym[w]), int(length[w]), table[w]
        if tc == 0:
            assert e == (-1 if s < 0 else s << 5 | n)
            continue
        size = s & 15
        if s < 0:
            assert e == (0, huffman._AC_BAD, 0)
        elif s in (0, 0xF0):
            assert e == (0, huffman._AC_EOB if s == 0 else huffman._AC_ZRL,
                         n)
        elif n + size > 16:
            assert e == (0, huffman._AC_SLOW | s, n)
        else:
            bits_ = (w >> (16 - n - size)) & ((1 << size) - 1)
            assert e == (n + size, s >> 4, _extend(bits_, size))
