"""The timed path broken underneath, and replaced by the int8 control:
each run drives the rest of a cell on the CPU and must come out not
correct."""
from __future__ import annotations

import numpy as np
import pytest

from chipbench import reference as R
from chipbench.test_chipbench_cells import _cell, _run


@pytest.fixture
def path_in_place():
    """Registers a decode path, from a batch function, for one test."""
    from repro.codecs import Capabilities, register_decoder
    from repro.codecs.registry import unregister_decoder
    names = []

    def register(name, batch):
        register_decoder(name, lambda d: batch([d])[0],
                         caps=Capabilities(engine="numpy"), batch_fn=batch)
        names.append(name)
        return name

    yield register
    for n in names:
        unregister_decoder(n)


def _broken(base, alter):
    """``base``'s batch decode with each answer passed through ``alter``."""
    from repro.codecs import get_decoder
    spec = get_decoder(base)
    return lambda datas: [alter(k, r)
                          for k, r in enumerate(spec.decode_batch(datas))]


def _shift(k, img):
    """An answer altered where it is produced: every value one level up."""
    return np.minimum(img.astype(np.int16) + 1, 255).astype(np.uint8)


# ------------------------------------------------------------ faults
@pytest.mark.parametrize("cell", ["loader-val-w8", "loader-val-w0"])
def test_an_altered_answer_is_not_correct(cell, path_in_place):
    name = path_in_place("test-altered", _broken("pallas-batch", _shift))
    out = _run(_cell(cell, path=name))
    assert not out["correct"]
    assert out["checks"]["mismatch_share"]["value"] > 0.5


def test_a_loader_that_drops_half_its_images_is_not_correct(path_in_place):
    from repro.jpeg.parser import CorruptJpeg

    def drop(k, img):
        return CorruptJpeg("dropped") if k % 2 else img

    name = path_in_place("test-dropping", _broken("pallas-batch", drop))
    out = _run(_cell("loader-val-w8", path=name))
    assert not out["correct"]
    assert out["checks"]["skipped"]["value"] > 0


# ------------------------------------------------------------ the control
@pytest.mark.parametrize("cell", ["loader-val-w0", "loader-val-w8"])
def test_the_int8_control_is_not_correct(cell, path_in_place):
    """The reference with an int8 IDCT, put in the program's place."""
    name = path_in_place("test-control-int8", lambda datas: [
        R.decode(d, precision="int8") for d in datas])
    out = _run(_cell(cell, path=name))
    assert not out["correct"]
    share = out["checks"]["mismatch_share"]
    assert share["value"] > share["limit"]
