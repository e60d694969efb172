"""Plain reference decoder for baseline JPEG, independent of the program.

It imports nothing from ``repro``: its own marker parser, Huffman decoder,
float64 separable IDCT, nearest-neighbour upsampling, JFIF YCbCr and Adobe
YCCK colour conversion, and the centre crop/pad a loader applies. These
follow the semantics the program documents (``DESIGN.md``): replicated
chroma, ``round`` half to even, clip to [0, 255].

``decode(data, precision="int8")`` is the control: the same decode with
the IDCT computed as an int8 matrix product. The program states float32
arrays with the IDCT dots at DEFAULT precision, which a TPU runs as one
bfloat16 pass (``precision="bf16"`` computes exactly that); int8 is the
next step down, and the one a v5e's int8 matrix units tempt.
"""
from __future__ import annotations

import numpy as np

# zigzag position -> natural (row-major) index in an 8x8 block
ZIGZAG = np.array(
    sorted(range(64), key=lambda i: (i // 8 + i % 8,
                                     (i % 8) if (i // 8 + i % 8) % 2 == 0
                                     else (i // 8))), np.int64)


class ReferenceError(ValueError):
    """A stream this reference does not decode (not baseline, or broken)."""


def _dct_matrix() -> np.ndarray:
    """C[u, x] = a(u) cos((2x + 1) u pi / 16); IDCT of F is C.T @ F @ C."""
    u = np.arange(8)[:, None]
    x = np.arange(8)[None, :]
    c = np.cos((2 * x + 1) * u * np.pi / 16) * np.sqrt(2 / 8)
    c[0] = np.sqrt(1 / 8)
    return c


C = _dct_matrix()


# ------------------------------------------------------------------ parse
class Frame:
    def __init__(self):
        self.qt = {}                 # id -> [64] natural order
        self.dc = {}                 # id -> lookup table
        self.ac = {}
        self.height = self.width = 0
        self.comps = []              # (cid, h, v, tq)
        self.scan = []               # (cid, td, ta) in scan order
        self.restart = 0
        self.adobe_transform = None
        self.data = b""


_LUT_CACHE: dict = {}


def _lut(bits, vals):
    """16-bit peek -> (symbol << 8) | code length; 0 marks no code."""
    key = (bytes(bits), bytes(vals))
    lut = _LUT_CACHE.get(key)
    if lut is not None:
        return lut
    lut = [0] * 65536
    code, k = 0, 0
    for length in range(1, 17):
        for _ in range(bits[length - 1]):
            lo = code << (16 - length)
            n = 1 << (16 - length)
            lut[lo:lo + n] = [(vals[k] << 8) | length] * n
            code += 1
            k += 1
        code <<= 1
    _LUT_CACHE[key] = lut
    return lut


def parse(data: bytes) -> Frame:
    if data[:2] != b"\xff\xd8":
        raise ReferenceError("no SOI")
    f = Frame()
    p = 2
    while p < len(data):
        if data[p] != 0xFF:
            raise ReferenceError(f"expected a marker at {p}")
        m = data[p + 1]
        if m == 0xFF:
            p += 1
            continue
        if m == 0xD9:
            break
        seg_len = int.from_bytes(data[p + 2:p + 4], "big")
        seg = data[p + 4:p + 2 + seg_len]
        p += 2 + seg_len
        if m == 0xDB:                                   # DQT
            q = 0
            while q < len(seg):
                pq, tq = seg[q] >> 4, seg[q] & 15
                n = 128 if pq else 64
                raw = np.frombuffer(seg[q + 1:q + 1 + n],
                                    ">u2" if pq else "u1").astype(np.float64)
                nat = np.zeros(64)
                nat[ZIGZAG] = raw
                f.qt[tq] = nat
                q += 1 + n
        elif m in (0xC0, 0xC1):                         # baseline SOF
            if seg[0] != 8:
                raise ReferenceError("only 8-bit samples")
            f.height = int.from_bytes(seg[1:3], "big")
            f.width = int.from_bytes(seg[3:5], "big")
            for i in range(seg[5]):
                cid, hv, tq = seg[6 + 3 * i:9 + 3 * i]
                f.comps.append((cid, hv >> 4, hv & 15, tq))
        elif 0xC2 <= m <= 0xCF and m not in (0xC4, 0xC8, 0xCC):
            raise ReferenceError(f"SOF{m - 0xC0} is not baseline")
        elif m == 0xC4:                                 # DHT
            q = 0
            while q < len(seg):
                tc, th = seg[q] >> 4, seg[q] & 15
                bits = list(seg[q + 1:q + 17])
                n = sum(bits)
                vals = list(seg[q + 17:q + 17 + n])
                (f.ac if tc else f.dc)[th] = _lut(bits, vals)
                q += 17 + n
        elif m == 0xDD:                                 # DRI
            f.restart = int.from_bytes(seg[0:2], "big")
        elif m == 0xEE and seg[:5] == b"Adobe":         # APP14
            f.adobe_transform = seg[11]
        elif m == 0xDA:                                 # SOS
            ns = seg[0]
            for i in range(ns):
                f.scan.append((seg[1 + 2 * i], seg[2 + 2 * i] >> 4,
                               seg[2 + 2 * i] & 15))
            end = p
            while True:
                end = data.index(b"\xff", end)
                nxt = data[end + 1]
                if nxt == 0 or 0xD0 <= nxt <= 0xD7:
                    end += 2
                    continue
                break
            f.data = data[p:end]
            p = end
    if not f.comps or not f.scan:
        raise ReferenceError("no frame or no scan")
    if len(f.scan) != len(f.comps):
        raise ReferenceError("only one interleaved scan of every component")
    return f


def mcu_grid(f: Frame):
    hmax = max(c[1] for c in f.comps)
    vmax = max(c[2] for c in f.comps)
    return (-(-f.height // (8 * vmax)), -(-f.width // (8 * hmax)),
            hmax, vmax)


# ------------------------------------------------------------------ entropy
def _segments(scan: bytes):
    """Destuffed entropy segments, split at restart markers."""
    out, cur, p = [], bytearray(), 0
    while True:
        q = scan.find(b"\xff", p)
        if q < 0:
            cur += scan[p:]
            break
        cur += scan[p:q]
        nxt = scan[q + 1] if q + 1 < len(scan) else 0
        if nxt == 0:
            cur.append(0xFF)
        elif 0xD0 <= nxt <= 0xD7:
            out.append(bytes(cur))
            cur = bytearray()
        p = q + 2
    out.append(bytes(cur))
    return out


def entropy_decode(f: Frame):
    """-> {cid: int32 [rows*v, cols*h, 8, 8]} natural-order coefficients."""
    rows, cols, _, _ = mcu_grid(f)
    comps = {c[0]: c for c in f.comps}
    units = []                       # per MCU: (cid, dy, dx, dc lut, ac lut)
    for cid, td, ta in f.scan:
        _, h, v, _ = comps[cid]
        for dy in range(v):
            for dx in range(h):
                units.append((cid, dy, dx, h, v, f.dc[td], f.ac[ta]))
    flat = {cid: [0] * (rows * v * cols * h * 64)
            for cid, h, v, _ in f.comps}
    width = {cid: cols * h for cid, h, v, _ in f.comps}
    zz = ZIGZAG.tolist()
    n_mcu = rows * cols
    per_seg = f.restart or n_mcu
    segs = _segments(f.data)
    if len(segs) < -(-n_mcu // per_seg):
        raise ReferenceError("missing restart segments")
    m = 0
    for seg in segs:
        if m >= n_mcu:
            break
        buf = seg + b"\x00" * 8
        bp = 0
        pred = {cid: 0 for cid in flat}
        for _ in range(min(per_seg, n_mcu - m)):
            my, mx = divmod(m, cols)
            for cid, dy, dx, h, v, dc, ac in units:
                row = my * v + dy
                col = mx * h + dx
                base = (row * width[cid] + col) * 64
                out = flat[cid]
                # DC
                w = int.from_bytes(buf[bp >> 3:(bp >> 3) + 4], "big")
                e = dc[(w >> (16 - (bp & 7))) & 0xFFFF]
                if not e:
                    raise ReferenceError("bad DC code")
                bp += e & 255
                s = e >> 8
                if s:
                    w = int.from_bytes(buf[bp >> 3:(bp >> 3) + 4], "big")
                    val = (w >> (32 - (bp & 7) - s)) & ((1 << s) - 1)
                    if val < (1 << (s - 1)):
                        val -= (1 << s) - 1
                    bp += s
                    pred[cid] += val
                out[base] = pred[cid]
                # AC
                k = 1
                while k < 64:
                    w = int.from_bytes(buf[bp >> 3:(bp >> 3) + 4], "big")
                    e = ac[(w >> (16 - (bp & 7))) & 0xFFFF]
                    if not e:
                        raise ReferenceError("bad AC code")
                    bp += e & 255
                    rs = e >> 8
                    s = rs & 15
                    if s == 0:
                        if rs != 0xF0:
                            break                       # EOB
                        k += 16
                        continue
                    k += rs >> 4
                    w = int.from_bytes(buf[bp >> 3:(bp >> 3) + 4], "big")
                    val = (w >> (32 - (bp & 7) - s)) & ((1 << s) - 1)
                    if val < (1 << (s - 1)):
                        val -= (1 << s) - 1
                    bp += s
                    if k > 63:
                        raise ReferenceError("AC index past 63")
                    out[base + zz[k]] = val
                    k += 1
            m += 1
    if m < n_mcu:
        raise ReferenceError("entropy data ended early")
    coef = {}
    for cid, h, v, _ in f.comps:
        arr = np.asarray(flat[cid], np.int32)
        coef[cid] = arr.reshape(rows * v, cols * h, 8, 8)
    return coef


# ------------------------------------------------------------------ pixels
def _round_bf16(x: np.ndarray) -> np.ndarray:
    import ml_dtypes
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


def _int8(x: np.ndarray, axis=None):
    """Symmetric int8 quantisation: (int values, scale) per ``axis``."""
    scale = np.abs(x).max(axis=axis, keepdims=axis is not None) / 127.0
    scale = np.where(scale > 0, scale, 1.0)
    return np.round(x / scale).astype(np.int64), scale


def _idct(deq: np.ndarray, precision: str) -> np.ndarray:
    """[by, bx, 8, 8] dequantised -> spatial samples (level shift added).

    ``bf16``: the dot's inputs rounded to bfloat16 and summed in float32,
    as a TPU runs a float32 dot at DEFAULT precision. ``int8``: each
    block's coefficients and the IDCT matrix quantised to int8 with one
    scale per block and one for the matrix, summed exactly, as an int8
    matrix unit would."""
    if precision == "f64":
        return np.einsum("ux,...uv,vy->...xy", C, deq, C) + 128.0
    by, bx = deq.shape[:2]
    kron = np.einsum("ux,vy->xyuv", C, C).reshape(64, 64)
    rows = deq.reshape(-1, 64)
    if precision == "bf16":
        out = _round_bf16(rows) @ _round_bf16(kron).T
    elif precision == "int8":
        q, s = _int8(rows, axis=1)
        qk, sk = _int8(kron)
        out = (q @ qk.T) * s * sk
    else:
        raise ValueError(precision)
    return out.reshape(by, bx, 8, 8).astype(np.float64) + 128.0


def decode(data: bytes, precision: str = "f64") -> np.ndarray:
    """Baseline JPEG -> RGB uint8 [H, W, 3]."""
    f = parse(data)
    coef = entropy_decode(f)
    _, _, hmax, vmax = mcu_grid(f)
    planes = []
    for cid, h, v, tq in f.comps:
        deq = coef[cid] * f.qt[tq].reshape(8, 8)
        blocks = _idct(deq, precision)
        by, bx = blocks.shape[:2]
        plane = blocks.transpose(0, 2, 1, 3).reshape(by * 8, bx * 8)
        plane = np.repeat(np.repeat(plane, vmax // v, 0), hmax // h, 1)
        planes.append(plane[:f.height, :f.width])
    if len(planes) == 1:
        rgb = np.repeat(planes[0][..., None], 3, axis=-1)
    elif len(planes) == 3:
        rgb = _ycbcr(*planes)
    elif len(planes) == 4 and f.adobe_transform == 2:
        # Adobe YCCK: YCbCr of the inverted CMY, and K as stored
        cmy = 255.0 - _ycbcr(*planes[:3])
        k = planes[3][..., None]
        rgb = ((255.0 - np.clip(cmy, 0, 255)) * (255.0 - np.clip(k, 0, 255))
               / 255.0)
    else:
        raise ReferenceError(f"{len(planes)} components, Adobe transform "
                             f"{f.adobe_transform}")
    return np.clip(np.round(rgb), 0, 255).astype(np.uint8)


def _ycbcr(y, cb, cr):
    return np.stack([y + 1.402 * (cr - 128.0),
                     y - 0.344136 * (cb - 128.0) - 0.714136 * (cr - 128.0),
                     y + 1.772 * (cb - 128.0)], axis=-1)


def center_fit(img: np.ndarray, th: int, tw: int) -> np.ndarray:
    """Centre crop to at most (th, tw), then zero-pad bottom and right."""
    h, w = img.shape[:2]
    y0, x0 = max((h - th) // 2, 0), max((w - tw) // 2, 0)
    out = np.zeros((th, tw, img.shape[2]), img.dtype)
    crop = img[y0:y0 + th, x0:x0 + tw]
    out[:crop.shape[0], :crop.shape[1]] = crop
    return out
