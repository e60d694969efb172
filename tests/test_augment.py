"""RandomResizedCrop + flip: the parameter sampler, the float64 reference
resample against Pillow, the ``rrc_resample`` kernel in interpret mode, and
the loader's augmentation path through ``prefetch_to_device``."""
import math

import numpy as np
import pytest
from PIL import Image

import jax

from repro.data.augment import (RandomResizedCrop, crop_box, resample,
                                 sample_params)
from repro.data.loader import DataLoader, LoaderConfig, prefetch_to_device
from repro.kernels import ops, ref

SCALE, RATIO = (0.08, 1.0), (3 / 4, 4 / 3)


class ScriptedRng:
    """Hands out scripted draws and records each call, in order."""

    def __init__(self, *draws):
        self.draws = list(draws)
        self.calls = []

    def uniform(self, lo, hi):
        self.calls.append(("uniform", lo, hi))
        return self.draws.pop(0)

    def integers(self, lo, hi):
        self.calls.append(("integers", lo, hi))
        return self.draws.pop(0)


# ------------------------------------------------------------ the sampler
def test_crop_box_normal_draw():
    """100x200, area share 0.5 and log-ratio 0: target 10,000 px at ratio
    1, so a 100x100 box; top from [0, 1), left from [0, 101)."""
    rng = ScriptedRng(0.5, 0.0, 0, 37)
    assert crop_box(rng, 100, 200, SCALE, RATIO) == (0, 37, 100, 100, False)
    assert rng.calls == [("uniform", 0.08, 1.0),
                         ("uniform", math.log(3 / 4), math.log(4 / 3)),
                         ("integers", 0, 1), ("integers", 0, 101)]


def test_crop_box_retries_until_a_box_fits():
    """100x100, share 1.0 at ratio 4/3: w = round(sqrt(10000 * 4/3)) = 115
    does not fit, so a second attempt; share 0.25 at ratio 1 gives 50x50."""
    rng = ScriptedRng(1.0, math.log(4 / 3), 0.25, 0.0, 10, 20)
    assert crop_box(rng, 100, 100, SCALE, RATIO) == (10, 20, 50, 50, False)
    assert [c[0] for c in rng.calls] == ["uniform"] * 4 + ["integers"] * 2


@pytest.mark.parametrize("hw, want", [
    # 1x40: every attempt wants h >= 2; the ratio clamps to 4/3:
    # h = 1, w = round(4/3) = 1, centred
    ((1, 40), (0, 19, 1, 1, True)),
    # 40x1: ratio 1/40 clamps to 3/4: w = 1, h = round(1 / 0.75) = 1
    ((40, 1), (19, 0, 1, 1, True)),
])
def test_crop_box_falls_back_to_the_centre(hw, want):
    rng = np.random.default_rng(3)
    assert crop_box(rng, *hw, SCALE, RATIO) == want


def test_crop_box_stays_in_bounds_over_10000_draws():
    rng = np.random.default_rng(11)
    flips = fallbacks = 0
    spec = RandomResizedCrop(seed=5)
    for n in range(10_000):
        height, width = (int(v) for v in rng.integers(1, 600, size=2))
        top, left, h, w, flip, fb = sample_params(spec, 0, n, height, width)
        assert 0 < h <= height and 0 < w <= width
        assert 0 <= top <= height - h and 0 <= left <= width - w
        if not fb:
            # the drawn share and ratio, up to rounding each side to pixels
            assert (h + 0.5) * (w + 0.5) >= SCALE[0] * height * width
            assert (w - 0.5) / (h + 0.5) <= RATIO[1]
            assert (w + 0.5) / (h - 0.5) >= RATIO[0]
        flips += flip
        fallbacks += fb
    assert 4700 < flips < 5300
    assert fallbacks < 2000


def test_params_are_a_function_of_the_key():
    spec = RandomResizedCrop(seed=9)
    a = sample_params(spec, 2, 17, 375, 500)
    assert a == sample_params(spec, 2, 17, 375, 500)
    others = {sample_params(spec, e, i, 375, 500)
              for e, i in ((3, 17), (2, 18))}
    assert a not in others


# ------------------------------------------------------------ reference
def _pil(img, box, size):
    """torchvision's PIL path: crop, then resize with antialiased
    bilinear (``resize(..., box=...)`` on the cropped image), then flip."""
    top, left, h, w, flip = box
    crop = Image.fromarray(img).crop((left, top, left + w, top + h))
    out = np.asarray(crop.resize(size[::-1], Image.BILINEAR,
                                 box=(0, 0, w, h)))
    return out[:, ::-1] if flip else out


def _image(rng, h, w):
    """Texture over smooth waves, like the benchmark's pool."""
    y, x = np.mgrid[:h, :w]
    base = 128 + 60 * np.sin(x / 7.0)[..., None] * np.cos(y / 5.0)[..., None]
    img = base + rng.normal(0, 25, (h, w, 3))
    return np.clip(img, 0, 255).astype(np.uint8)


@pytest.mark.parametrize("kind", ["down", "up", "flipped"])
def test_reference_matches_pillow_within_1(kind):
    rng = np.random.default_rng({"down": 1, "up": 2, "flipped": 3}[kind])
    size = (40, 48)
    for _ in range(12):
        img = _image(rng, 90, 120)
        if kind == "up":
            h, w = (int(v) for v in rng.integers(5, 40, size=2))
        else:
            h, w = int(rng.integers(41, 91)), int(rng.integers(49, 121))
        box = (int(rng.integers(0, 91 - h)), int(rng.integers(0, 121 - w)),
               h, w, int(kind == "flipped"))
        got = ref.rrc_resample(img, box, size)
        want = _pil(img, box, size)
        assert got.shape == want.shape == (*size, 3)
        assert np.abs(got.astype(int) - want).max() <= 1, box


def test_reference_weights_follow_pil():
    """Rows sum to 1; a 2x downscale spreads each output pixel over four
    taps (support 2); a same-size axis is the identity."""
    w = ref.pil_bilinear_weights(5, 10)
    np.testing.assert_allclose(w.sum(axis=1), 1.0)
    np.testing.assert_allclose(w[2, 3:7], [1 / 8, 3 / 8, 3 / 8, 1 / 8])
    np.testing.assert_allclose(ref.pil_bilinear_weights(7, 7), np.eye(7))


# ------------------------------------------------------------ the kernel
def _padded_batch(seed, n=8, hw=(48, 64), pad=(56, 72)):
    rng = np.random.default_rng(seed)
    imgs = np.zeros((n, *pad, 3), np.uint8)
    crops = []
    for k in range(n):
        imgs[k, :hw[0], :hw[1]] = _image(rng, *hw)
        h, w = int(rng.integers(4, hw[0] + 1)), int(rng.integers(4, hw[1] + 1))
        crops.append((int(rng.integers(0, hw[0] - h + 1)),
                      int(rng.integers(0, hw[1] - w + 1)), h, w, k % 2))
    return imgs, np.asarray(crops, np.int32)


def test_rrc_resample_kernel_matches_reference():
    imgs, crops = _padded_batch(21)
    out = np.asarray(ops.rrc_resample(imgs, crops, size=(32, 32)))
    assert out.shape == (8, 32, 32, 3) and out.dtype == np.uint8
    for k in range(8):
        want = ref.rrc_resample(imgs[k], crops[k], (32, 32))
        assert np.abs(out[k].astype(int) - want).max() <= 1, crops[k]


def test_rrc_resample_compiles_once_for_every_crop():
    events = []

    def on_duration(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            events.append(kw.get("fun_name"))

    imgs, crops_a = _padded_batch(31, n=4)
    _, crops_b = _padded_batch(32, n=4)
    assert (crops_a != crops_b).any()
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        ops.rrc_resample(imgs, crops_a, size=(24, 24)).block_until_ready()
        first = len(events)
        ops.rrc_resample(imgs, crops_b, size=(24, 24)).block_until_ready()
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert first >= 1
    assert len(events) == first, events[first:]


# ------------------------------------------------------------ the loader
AUG = RandomResizedCrop(seed=4, pad_hw=(128, 128))
SIZE = (24, 24)


def _loader(corpus, path, **kw):
    cfg = LoaderConfig(batch_size=4, target_hw=SIZE, augment=AUG, **kw)
    return DataLoader(corpus.files, np.arange(len(corpus.files)), cfg=cfg,
                      path_name=path)


@pytest.mark.parametrize("path, workers", [("numpy-ref", 0),
                                           ("pallas-batch", 2)])
def test_loader_augments_against_decode_plus_reference(corpus, path,
                                                       workers):
    from repro.codecs import get_decoder
    decode = get_decoder(path).fn
    dl = _loader(corpus, path, num_workers=workers,
                 decode_batch=4 if workers else 0)
    seen = 0
    for b in prefetch_to_device(dl):
        assert b["image"].shape[1:] == (128, 128, 3)
        b = resample(b, SIZE)
        assert isinstance(b["image"], jax.Array)
        image, crop = np.asarray(b["image"]), np.asarray(b["crop"])
        assert image.shape[1:] == (24, 24, 3) and image.dtype == np.uint8
        for k, i in enumerate(np.asarray(b["label"]).tolist()):
            img = np.asarray(decode(corpus.files[i]))
            box = sample_params(AUG, 0, i, *img.shape[:2])[:5]
            assert tuple(crop[k]) == box
            want = ref.rrc_resample(img, box, SIZE)
            assert np.abs(image[k].astype(int) - want).max() <= 1, (i, box)
            seen += 1
    assert seen == len(corpus.files)


def test_restore_mid_epoch_replays_the_same_crops(corpus):
    dl = _loader(corpus, "numpy-fast", shuffle=True, seed=2)
    dl.epoch = 3
    it = iter(dl)
    next(it)
    state = dl.state()
    rest = [(b["label"].tolist(), b["crop"].tolist()) for b in it]

    dl2 = _loader(corpus, "numpy-fast", shuffle=True, seed=2)
    dl2.restore(state)
    assert [(b["label"].tolist(), b["crop"].tolist()) for b in dl2] == rest
    # the next epoch draws other crops for the same items
    dl3 = _loader(corpus, "numpy-fast", shuffle=True, seed=2)
    dl3.epoch = 4
    by_item = {}
    for b in dl3:
        by_item.update(zip(b["label"].tolist(), b["crop"].tolist()))
    again = {lab: c for labs, cs in rest for lab, c in zip(labs, cs)}
    assert any(by_item[lab] != c for lab, c in again.items())


def test_augment_collate_spans(corpus):
    from repro.codecs import get_decoder
    from repro.obs import trace
    FAST = get_decoder("numpy-fast").fn
    tracer = trace.Tracer()
    dl = _loader(corpus, "numpy-fast")
    with trace.use_tracer(tracer):
        batches = [resample(b, SIZE) for b in prefetch_to_device(dl)]
    evs = [e for e in tracer.events() if e.get("ph") == "X"]
    collate = [e for e in evs if e["name"] == "augment.collate"]
    assert len(collate) == len(batches) == 3
    assert sum(e["args"]["images"] for e in collate) == len(corpus.files)
    boxes = [sample_params(AUG, 0, i, *FAST(f).shape[:2])
             for i, f in enumerate(corpus.files)]
    assert sum(e["args"]["upscaled"] for e in collate) == sum(
        b[2] < 24 or b[3] < 24 for b in boxes)
    assert sum(e["args"]["fallback"] for e in collate) == sum(
        b[5] for b in boxes)
    assert len([e for e in evs if e["name"] == "augment.resample"]) == 3
    assert not [e for e in evs if e["name"] == "loader.collate"]


def test_an_image_larger_than_the_padding_is_refused(corpus):
    cfg = LoaderConfig(batch_size=4, target_hw=SIZE,
                       augment=RandomResizedCrop(pad_hw=(64, 64)))
    dl = DataLoader(corpus.files, np.arange(len(corpus.files)), cfg=cfg,
                    path_name="numpy-fast")
    with pytest.raises(ValueError, match="pad_hw"):
        list(dl)
