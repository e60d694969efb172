"""Bench history store + stage-level regression attribution: JSONL
round-trip and torn-line accounting, per-stage normalization, the
attribute_stages naming rules, compare --attribute wiring (including
the injected-slowdown acceptance path: a sleep inside entropy decode
must make the compare verdict name the entropy stage), and the
benchmarks/run.py history CLI."""
import json
import os
import subprocess
import sys
import time

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from repro.bench import (HistoryStore, attribute_result, attribute_stages,
                         compare_records, run_sweep)
from repro.bench.compare import summary_markdown
from repro.bench.history import MIN_STAGE_S, stage_per_image
from repro.common.hw import host_fingerprint
from repro.core.schema import RunRecord, SchemaError, save_records

REPO = os.path.join(os.path.dirname(__file__), "..")


def _rec(scenario, thr=100.0, stage_s=None, num_images=10, status="ok",
         decoder="numpy-fast"):
    meta = {"status": status, "scenario": scenario}
    if stage_s is not None:
        meta["stage_s"] = dict(stage_s)
    samples = [thr - 1, thr, thr + 1] if status == "ok" else []
    return RunRecord(platform="live-host", decoder=decoder,
                     protocol="single_thread", workers=0, mode="",
                     throughput_mean=thr if status == "ok" else 0.0,
                     throughput_std=1.0, samples=samples,
                     num_images=num_images, skip_indices=[], meta=meta)


# ------------------------------------------------------------------ store
def test_history_append_scan_roundtrip(tmp_path):
    store = HistoryStore(str(tmp_path / "nested" / "history.jsonl"))
    r1 = store.append([_rec("single/numpy-fast")], profile="smoke",
                      t=100.0)
    r2 = store.append([_rec("single/numpy-fast", thr=90.0),
                       _rec("single/jnp-fused")], profile="quick",
                      t=200.0)
    assert r1.fingerprint == r2.fingerprint == \
        host_fingerprint()["fingerprint"]
    runs, dropped = store.scan()
    assert dropped == 0 and [r.run_id for r in runs] == \
        [r1.run_id, r2.run_id]
    assert runs[0].t == 100.0 and runs[0].profile == "smoke"
    assert len(runs[1].records) == 2
    back = runs[1].record_for("single/numpy-fast")
    assert back is not None and back.throughput_mean == 90.0
    assert runs[1].record_for("nope") is None
    # append-only: one JSON line per run
    lines = open(store.path).read().splitlines()
    assert len(lines) == 2 and all(json.loads(ln) for ln in lines)


def test_history_append_rejects_empty_and_fingerprintless(tmp_path):
    store = HistoryStore(str(tmp_path / "h.jsonl"))
    with pytest.raises(SchemaError, match="empty run"):
        store.append([])
    with pytest.raises(SchemaError, match="no fingerprint"):
        store.append([_rec("s")], host={"cpus": 4})
    assert not os.path.exists(store.path)      # nothing was written


def test_history_fingerprint_filter_and_latest(tmp_path):
    store = HistoryStore(str(tmp_path / "h.jsonl"))
    store.append([_rec("s")], host={"fingerprint": "aaa111aaa111"},
                 t=1.0, run_id="run-a")
    store.append([_rec("s")], host={"fingerprint": "bbb222bbb222"},
                 t=2.0, run_id="run-b")
    store.append([_rec("s")], host={"fingerprint": "aaa111aaa111"},
                 t=3.0, run_id="run-a2")
    assert [r.run_id for r in store.runs("aaa111aaa111")] == \
        ["run-a", "run-a2"]
    assert store.latest("bbb222bbb222").run_id == "run-b"
    assert store.latest().run_id == "run-a2"
    assert store.latest("ccc333ccc333") is None
    # payload-host shape (host_metadata: fingerprint is a nested dict)
    store.append([_rec("s")], t=4.0, run_id="run-c",
                 host={"cpus": 2, "fingerprint": {"cpu_model": "x",
                                                  "fingerprint":
                                                  "ddd444ddd444"}})
    assert store.latest("ddd444ddd444").run_id == "run-c"


def test_history_torn_line_dropped_and_counted(tmp_path):
    store = HistoryStore(str(tmp_path / "h.jsonl"))
    store.append([_rec("s")], t=1.0)
    with open(store.path, "a") as f:
        f.write('{"run_id": "torn", "t": 2.0, "records": [{"bro')
    runs, dropped = store.scan()
    assert len(runs) == 1 and dropped == 1     # counted, never absorbed
    assert HistoryStore(str(tmp_path / "absent.jsonl")).scan() == ([], 0)


def test_stage_baseline_wants_newest_ok_traced(tmp_path):
    store = HistoryStore(str(tmp_path / "h.jsonl"))
    traced = {"jpeg.parse": 0.01, "jpeg.entropy": 0.10}
    store.append([_rec("s", stage_s=traced)], t=1.0, run_id="old-traced")
    store.append([_rec("s")], t=2.0, run_id="untraced")
    store.append([_rec("s", status="error")], t=3.0, run_id="broken")
    hit = store.stage_baseline("s")
    assert hit is not None
    run, rec = hit
    # newest run with stage data wins, not merely the newest run
    assert run.run_id == "old-traced"
    assert rec.meta["stage_s"] == traced
    assert store.stage_baseline("other") is None


# ------------------------------------------------------------ attribution
def test_stage_per_image_normalizes_and_folds_terminal_names():
    rec = _rec("s", num_images=10,
               stage_s={"jpeg.entropy": 0.10, "loader.decode": 0.05,
                        "svc.pipeline.decode": 0.05})
    per = stage_per_image(rec)
    assert per["entropy"] == pytest.approx(0.010)
    # two dotted names sharing the terminal component sum together
    assert per["decode"] == pytest.approx(0.010)
    assert stage_per_image(_rec("s")) == {}
    zero = _rec("s", num_images=0, stage_s={"jpeg.parse": 0.02})
    assert stage_per_image(zero)["parse"] == pytest.approx(0.02)


def test_attribute_stages_names_the_moved_stage():
    old = _rec("s", stage_s={"jpeg.parse": 0.05, "jpeg.entropy": 0.02})
    new = _rec("s", stage_s={"jpeg.parse": 0.05, "jpeg.entropy": 0.05})
    assert attribute_stages(old, new) == \
        "entropy 2.5x (2.00→5.00 ms/img)"


def test_attribute_stages_noise_floor_and_min_ratio():
    tiny = {"jpeg.parse": MIN_STAGE_S}          # 1e-5 s/img at 10 images
    old = _rec("s", stage_s=tiny)
    new = _rec("s", stage_s={"jpeg.parse": MIN_STAGE_S * 5})
    assert attribute_stages(old, new) == ""     # both under the floor
    old = _rec("s", stage_s={"jpeg.parse": 0.10})
    new = _rec("s", stage_s={"jpeg.parse": 0.11})
    assert attribute_stages(old, new) == ""     # 1.1x < min_ratio
    assert attribute_stages(_rec("s"), new) == ""       # no baseline data
    assert attribute_stages(old, _rec("s")) == ""       # no candidate data


def test_attribute_stages_new_stage_and_largest_wins():
    old = _rec("s", stage_s={"jpeg.entropy": 0.02})
    new = _rec("s", stage_s={"jpeg.entropy": 0.02,
                             "loader.queue_wait": 0.08})
    assert attribute_stages(old, new) == \
        "queue_wait new (+8.00 ms/img vs baseline)"
    # two movers: the larger ratio is the one named
    old = _rec("s", stage_s={"jpeg.parse": 0.02, "jpeg.entropy": 0.02})
    new = _rec("s", stage_s={"jpeg.parse": 0.04, "jpeg.entropy": 0.10})
    assert attribute_stages(old, new).startswith("entropy 5.0x")


def test_attribute_result_prefers_history_then_falls_back(tmp_path):
    host = host_fingerprint()
    store = HistoryStore(str(tmp_path / "h.jsonl"))
    store.append([_rec("single/numpy-fast",
                       stage_s={"jpeg.entropy": 0.02,
                                "jpeg.parse": 0.05})], t=1.0)
    # compare baseline is UNtraced: only the history store can attribute
    old = [_rec("single/numpy-fast")]
    new = [_rec("single/numpy-fast", thr=30.0,
                stage_s={"jpeg.entropy": 0.08, "jpeg.parse": 0.05})]
    res = compare_records(old, new, new_host=host)
    assert res.n_fail == 1
    named = attribute_result(res, old, new, history=store)
    assert named == 1
    e = res.by_verdict("fail")[0]
    assert e.attribution == "entropy 4.0x (2.00→8.00 ms/img)"
    # without the store, the untraced compare baseline is explicit about
    # why it cannot attribute
    res2 = compare_records(old, new, new_host=host)
    assert attribute_result(res2, old, new) == 0
    assert res2.by_verdict("fail")[0].attribution == \
        "unattributed: no stage_s rollup (run sweep --trace)"
    # traced on both sides but nothing moved: the other explicit note
    same = {"jpeg.entropy": 0.02, "jpeg.parse": 0.05}
    old3 = [_rec("single/numpy-fast", stage_s=same)]
    new3 = [_rec("single/numpy-fast", thr=30.0, stage_s=same)]
    res3 = compare_records(old3, new3, new_host=host)
    assert attribute_result(res3, old3, new3) == 0
    assert res3.by_verdict("fail")[0].attribution == \
        "unattributed: no single stage moved enough"
    # ok/improved entries are never attributed
    assert all(not e.attribution for e in res3.entries
               if e.verdict not in ("fail", "warn"))


def test_summary_markdown_gains_stage_column_when_attributed():
    old = [_rec("single/numpy-fast", stage_s={"jpeg.entropy": 0.02})]
    new = [_rec("single/numpy-fast", thr=30.0,
                stage_s={"jpeg.entropy": 0.08})]
    res = compare_records(old, new)
    attribute_result(res, old, new)
    md = summary_markdown(res)
    assert "| ratio | gate | stage |" in md
    assert "entropy 4.0x" in md
    # an unattributed compare renders the historical five-column table
    res_plain = compare_records(old, new)
    assert "| stage |" not in summary_markdown(res_plain)


# ----------------------------------------------- acceptance: injected lag
def test_injected_entropy_slowdown_is_attributed(tmp_path, monkeypatch):
    """The ISSUE acceptance test: slow one stage artificially (a sleep
    inside entropy segment decode), re-sweep, and compare --attribute
    must blame that stage — not just report the cell got slower."""
    from repro.jpeg import huffman
    cell = "single/numpy-fast"
    # a process's first sweep can start on cold thread pools and read
    # several times slower; the baseline is the warm sweep after it
    run_sweep("smoke", only=[cell], trace=True,
              out_dir=str(tmp_path / "warm"))
    base = run_sweep("smoke", only=[cell], trace=True,
                     out_dir=str(tmp_path / "base"))
    store = HistoryStore(str(tmp_path / "history.jsonl"))
    store.append(base.records, profile="smoke")

    real = huffman.decode_segment

    def laggy(seg, tables_key, components, n_mcus):
        time.sleep(0.01)                       # inside the entropy span
        return real(seg, tables_key, components, n_mcus)

    monkeypatch.setattr(huffman, "decode_segment", laggy)
    slow = run_sweep("smoke", only=[cell], trace=True,
                     out_dir=str(tmp_path / "slow"))

    host = host_fingerprint()
    res = compare_records(base.records, slow.records,
                          old_host=host, new_host=host)
    regressed = {e.scenario: e for e in res.entries
                 if e.verdict in ("fail", "warn")}
    assert cell in regressed, [
        (e.scenario, e.verdict, e.ratio) for e in res.entries]
    named = attribute_result(res, base.records, slow.records,
                             history=store)
    assert named >= 1
    note = regressed[cell].attribution
    assert note.startswith("entropy "), note   # the right stage, by name
    assert "ms/img" in note
    md = summary_markdown(res)
    assert "entropy " in md and "| stage |" in md


# --------------------------------------------------------------- run.py
def test_history_cli_append_and_show(tmp_path):
    records = str(tmp_path / "records.json")
    save_records([_rec("single/numpy-fast",
                       stage_s={"jpeg.entropy": 0.02})], records)
    store = str(tmp_path / "history.jsonl")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(REPO, "src"), REPO,
         env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    run_py = os.path.join(REPO, "benchmarks", "run.py")
    proc = subprocess.run(
        [sys.executable, run_py, "history", "append", records,
         "--store", store, "--profile", "smoke"],
        env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert "appended run" in proc.stdout
    assert "1 records, 1 stage-traced" in proc.stdout
    proc = subprocess.run(
        [sys.executable, run_py, "history", "show", "--store", store],
        env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert "1 run(s)" in proc.stdout
    assert "profile=smoke" in proc.stdout and "stage-traced=1" \
        in proc.stdout
    # append without a records path is a usage error, not a traceback
    proc = subprocess.run(
        [sys.executable, run_py, "history", "append", "--store", store],
        env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 2
    assert "needs a record-set" in proc.stderr
