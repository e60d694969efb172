"""chip_smoke.py on the CPU: it refuses to run, and its phases hold at a
tiny size when the test steers past the device checks."""
import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _ok_lines(text):
    out = []
    for line in text.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and "ok" in obj:
            out.append(obj)
    return out


def test_refuses_without_tpu_in_process(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main()
    assert exc.value.code not in (0, None)
    assert _ok_lines(capsys.readouterr().out) == []


def test_refuses_without_tpu_as_script():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert _ok_lines(proc.stdout) == []
    assert "no TPU" in proc.stderr


def test_phases_run_at_tiny_size(monkeypatch, capsys):
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    monkeypatch.setattr(chip_smoke, "require_tpu", lambda: device)
    monkeypatch.setattr(chip_smoke, "assert_mosaic", lambda: None)
    monkeypatch.setattr(chip_smoke, "N_IMAGES", 6)
    monkeypatch.setattr(chip_smoke, "SIZES", [(64, 96)])
    monkeypatch.setattr(chip_smoke, "REQUESTS_PER_CLIENT", 6)
    monkeypatch.setattr(chip_smoke, "LOADER_BATCH", 2)
    assert chip_smoke.main() == 0
    lines = [json.loads(s) for s in capsys.readouterr().out.splitlines()]
    assert lines[-1] == {"ok": True, "device": device}
    phases = [ln["phase"] for ln in lines if "phase" in ln]
    assert phases == ["device", "corpus", "reference", "device_paths",
                      "service", "loader", "fork"]
    paths = {ln["path"]: ln for ln in lines if "path" in ln}
    assert set(paths) == set(chip_smoke.DEVICE_PATHS)
    assert paths["strict-pallas"]["skips"] == [2]      # scaled_rare_index(6)
    assert all(p["skips"] == [] for n, p in paths.items()
               if n != "strict-pallas")
    svc = next(ln for ln in lines if "service_path_hits" in ln)
    assert svc["completed"] == 4 * 6 and svc["failed"] == 0
