"""Online decode service: delivery, backpressure/shedding, micro-batching,
bandit routing + strict fallback, cache, metrics, shutdown."""
import threading
import time

import numpy as np
import pytest

from repro.jpeg.paths import DECODE_PATHS, DecodePath, list_paths
from repro.service import (AdmissionController, BanditRouter, DecodeCache,
                           DecodeService, MicroBatcher, ServiceConfig,
                           ServiceOverloaded, ServiceShutdown, bucket_key,
                           content_key)

NUMPY_PATHS = [DECODE_PATHS[n] for n in ("numpy-fast", "numpy-int",
                                         "numpy-sparse")]


def mksvc(paths=NUMPY_PATHS, **kw):
    kw.setdefault("num_workers", 2)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_wait_ms", 2.0)
    kw.setdefault("seed", 3)
    return DecodeService(ServiceConfig(**kw), paths=paths)


def timed_path(name, delay_s, strict=False):
    """Synthetic decode path with a controlled service time."""
    def fn(data):
        time.sleep(delay_s)
        return np.zeros((8, 8, 3), np.uint8)
    return DecodePath(name=name, fn=fn, strict=strict, engine="numpy")


# ---------------------------------------------------------------- delivery
def test_concurrent_clients_delivered_exactly_once(corpus):
    refs = [DECODE_PATHS["numpy-ref"].decode(f) for f in corpus.files]
    results = {}
    errors = []
    with mksvc(cache_bytes=0) as svc:
        def client(cid):
            try:
                futs = [(i, svc.submit(corpus.files[i], client=cid))
                        for i in range(len(corpus.files))]
                results[cid] = [(i, f.result(timeout=60)) for i, f in futs]
            except Exception as e:          # pragma: no cover - diagnostics
                errors.append(e)
        threads = [threading.Thread(target=client, args=(f"c{k}",))
                   for k in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert not errors
    for cid, res in results.items():
        assert len(res) == len(corpus.files)       # exactly once per submit
        for i, img in res:
            err = np.abs(img.astype(int) - refs[i].astype(int)).max()
            assert err <= 4, (cid, i, err)
    snap = svc.metrics.snapshot()
    assert snap["completed"] == 3 * len(corpus.files)
    assert snap["failed"] == 0 and snap["shed"] == 0


def test_inline_mode_workers0(corpus):
    with mksvc(num_workers=0) as svc:
        img = svc.decode(corpus.files[0])
    assert img.dtype == np.uint8 and img.ndim == 3


def test_corrupt_input_fails_future_not_service(corpus):
    with mksvc() as svc:
        bad = svc.submit(b"\x00\x01not-a-jpeg")
        with pytest.raises(Exception):
            bad.result(timeout=30)
        ok = svc.submit(corpus.files[1])
        assert ok.result(timeout=30).ndim == 3


# ------------------------------------------------------------- backpressure
def test_saturation_sheds_instead_of_deadlocking(corpus):
    slow = timed_path("slow-arm", 0.05)
    with mksvc(paths=[slow], max_inflight=4, num_workers=1,
               cache_bytes=0) as svc:
        futs, shed = [], 0
        for i in range(40):
            try:
                futs.append(svc.submit(corpus.files[i % len(corpus.files)],
                                       client=f"c{i % 2}"))
            except ServiceOverloaded:
                shed += 1
        assert shed > 0                       # overload surfaced explicitly
        for f in futs:                        # accepted work still completes
            assert f.result(timeout=60) is not None
    assert svc.metrics.snapshot()["shed"] == shed


def test_admission_fairness_protects_polite_client():
    adm = AdmissionController(max_inflight=8, congestion=0.5)
    greedy_admitted = 0
    for _ in range(8):
        ok, _ = adm.try_admit("greedy")
        greedy_admitted += ok
    # greedy saturates its fair share, not the whole budget
    assert greedy_admitted < 8
    ok, _ = adm.try_admit("polite")
    assert ok
    for _ in range(greedy_admitted):
        adm.release("greedy")
    adm.release("polite")
    assert adm.inflight == 0


# ------------------------------------------------------------ micro-batcher
def test_bucket_key_groups_by_padded_mcu_grid(corpus):
    from repro.jpeg import parser as P
    keys = {}
    for f in corpus.files:
        spec = P.parse(f)
        keys.setdefault(bucket_key(f, granularity=4), []).append(
            (spec.height, spec.width, len(spec.components)))
    assert 1 < len(keys) < len(corpus.files)   # grouping, not degenerate
    for members in keys.values():
        assert len({ncomp for _, _, ncomp in members}) == 1


def test_batcher_fill_and_deadline_flush():
    b = MicroBatcher(max_batch=3, max_wait_s=0.5)
    assert b.add("k1", "a", now=0.0) is None
    assert b.add("k2", "x", now=0.1) is None
    full = b.add("k1", "b", now=0.2) or b.add("k1", "c", now=0.2)
    assert full is not None and full.items == ["a", "b", "c"]
    assert b.take_due(now=0.3) == []           # k2 not yet due
    due = b.take_due(now=0.7)
    assert [d.items for d in due] == [["x"]] and b.deadline_flushes == 1
    assert b.depth() == 0 and b.next_deadline(1.0) is None


def test_batcher_next_deadline_tracks_oldest():
    b = MicroBatcher(max_batch=8, max_wait_s=1.0)
    b.add("k", "a", now=10.0)
    b.add("k", "b", now=10.8)
    assert b.next_deadline(now=10.9) == pytest.approx(0.1)


def test_serve_batch_makes_one_decode_batch_call(corpus):
    """A full micro-batch reaches the decode path as ONE decode_batch
    call (the acceptance criterion: micro-batches decode as real
    batches, not a per-item loop around the batch)."""
    calls = []

    def batch_fn(datas):
        calls.append(len(datas))
        return [np.zeros((8, 8, 3), np.uint8) for _ in datas]

    path = DecodePath(name="counting", fn=lambda d: batch_fn([d])[0],
                      engine="numpy", batch_fn=batch_fn)
    same = [corpus.files[0]] * 4          # one bucket; cache is off
    with mksvc(paths=[path], num_workers=1, max_batch=4,
               max_wait_ms=500.0, cache_bytes=0) as svc:
        futs = [svc.submit(f) for f in same]
        for f in futs:
            f.result(timeout=30)
    assert calls == [4], calls


def test_service_batched_path_counts_one_transform_per_batch():
    """End-to-end through jnp-batch: 4 same-bucket images in a micro-batch
    cost exactly one fused transform invocation."""
    from repro.jpeg import encoder
    from repro.jpeg.corpus import natural_image
    from repro.obs import trace
    files = [encoder.encode_jpeg(
        natural_image(np.random.RandomState(20 + k), 64, 64),
        quality=85, subsampling="420") for k in range(4)]
    path = DECODE_PATHS["jnp-batch"]
    refs = [path.decode(f) for f in files]           # serial comparison
    with trace.use_tracer(trace.Tracer()) as tracer, \
            mksvc(paths=[path], num_workers=1, max_batch=4,
                  max_wait_ms=500.0, cache_bytes=0) as svc:
        futs = [svc.submit(f) for f in files]
        for fut, ref in zip(futs, refs):
            np.testing.assert_array_equal(fut.result(timeout=60), ref)
    launches = [e for e in tracer.events() if e["name"] == "jpeg.transform"]
    assert len(launches) == 1 and launches[0]["args"] == {"batch": 4}


def test_batch_level_failure_fails_futures_not_worker(corpus):
    """A decode_batch that blows up batch-wide must fail the batch's
    futures and leave the worker alive — never hang clients."""
    def exploding(datas):
        raise RuntimeError("transform exploded")

    path = DecodePath(name="exploding", fn=lambda d: np.zeros((2, 2, 3),
                                                              np.uint8),
                      engine="numpy", batch_fn=exploding)
    with mksvc(paths=[path], num_workers=1, max_batch=2,
               cache_bytes=0) as svc:
        futs = [svc.submit(corpus.files[0]), svc.submit(corpus.files[1])]
        for f in futs:
            with pytest.raises(RuntimeError, match="transform exploded"):
                f.result(timeout=30)
        assert svc._threads[1].is_alive()    # worker survived the batch
    assert svc.metrics.snapshot()["failed"] == 2


def test_transform_group_failure_contained_to_group(corpus, monkeypatch):
    """A transform-stage exception inside one structure group marks only
    that group's items as failed; decode_batch itself never raises."""
    from repro.jpeg import pipeline
    monkeypatch.setattr(pipeline, "transform_batch",
                        lambda *a, **kw: (_ for _ in ()).throw(
                            RuntimeError("group boom")))
    out = DECODE_PATHS["jnp-batch"].decode_batch(corpus.files[:3])
    assert len(out) == 3
    assert all(isinstance(r, RuntimeError) for r in out)


def test_serve_batch_mixed_outcomes_partial_batch(corpus):
    """Corrupt members fail their own future; good batch-mates deliver."""
    with mksvc(paths=[DECODE_PATHS["jnp-batch"]], num_workers=1,
               max_batch=2, cache_bytes=0) as svc:
        good = svc.submit(corpus.files[0])
        bad = svc.submit(b"\xff\xd8 broken")
        assert good.result(timeout=30).ndim == 3
        with pytest.raises(Exception):
            bad.result(timeout=30)


# ------------------------------------------------------------------ routing
def test_bandit_converges_to_fastest_path(corpus):
    fast = timed_path("fast-arm", 0.0005)
    slow = timed_path("slow-arm", 0.01)
    with mksvc(paths=[slow, fast], cache_bytes=0, num_workers=1,
               max_batch=2, max_wait_ms=1.0) as svc:
        for _ in range(30):
            futs = [svc.submit(f) for f in corpus.files[:4]]
            for f in futs:
                f.result(timeout=60)
    assert svc.router.best() == "fast-arm"
    snap = svc.router.snapshot()
    assert snap["fast-arm"]["pulls"] > snap["slow-arm"]["pulls"]


def test_router_epsilon_policy_converges():
    r = BanditRouter([timed_path("fast-arm", 0), timed_path("slow-arm", 0)],
                     policy="epsilon", epsilon=0.2, seed=0)
    for _ in range(50):
        p = r.pick()
        r.update(p.name, 4, 0.004 if p.name == "fast-arm" else 0.04)
    assert r.best() == "fast-arm"


def test_router_zero_skip_filter_prefers_safe_arm():
    r = BanditRouter([timed_path("strict-quick", 0, strict=True),
                      timed_path("safe-arm", 0)])
    r.update("strict-quick", 8, 0.004)        # fastest...
    r.record_skip("strict-quick")             # ...but it refused an input
    r.update("safe-arm", 8, 0.0042)           # within the practical floor
    assert r.best() == "safe-arm"             # ledger gates eligibility
    tier = r.tier()
    assert [t.decoder for t in tier] == ["safe-arm"]


def test_strict_path_falls_back_and_records_skip(corpus):
    strict = DECODE_PATHS["strict-fast"]
    safe = DECODE_PATHS["numpy-fast"]
    router = BanditRouter([strict, safe], seed=0)
    router.pick = lambda: strict              # force the strict arm
    rare = corpus.files[corpus.rare_index]
    svc = DecodeService(ServiceConfig(num_workers=1, max_batch=1,
                                      cache_bytes=0), router=router)
    with svc:
        img = svc.decode(rare)                # still served (via fallback)
    assert img.dtype == np.uint8 and img.ndim == 3
    assert router.snapshot()["strict-fast"]["skips"] == 1
    snap = svc.metrics.snapshot()
    assert snap["path_skips"] == {"strict-fast": 1}
    assert snap["path_hits"] == {"numpy-fast": 1}


def test_list_paths_query_helper():
    from repro.codecs import contrib
    assert {p.name for p in list_paths()} == set(DECODE_PATHS)
    for p in list_paths(strict=True):
        assert p.strict
    for p in list_paths(process_eligible=True):
        # fork-safe = numpy family + contrib C-extension backends
        assert p.process_eligible and p.engine in (
            "numpy", "pillow", "opencv")
    assert {p.name for p in list_paths(process_eligible=True, strict=False)} \
        == {"numpy-ref", "numpy-fast", "numpy-int", "numpy-sparse",
            "fft-idct"} | set(contrib.available())


# -------------------------------------------------------------------- cache
def test_cache_hit_serves_repeat_requests(corpus):
    with mksvc(cache_bytes=8 << 20) as svc:
        a = svc.decode(corpus.files[0])
        b = svc.decode(corpus.files[0])
    np.testing.assert_array_equal(a, b)
    assert svc.cache.stats()["hits"] == 1
    assert svc.metrics.snapshot()["cache_hits"] == 1
    assert b.flags.writeable                # hits behave like fresh decodes
    b[:] = 0                                # ...and cannot poison the cache
    from repro.service import content_key
    again = svc.cache.get(content_key(corpus.files[0]))
    assert again is not None and again.any()


def test_cache_lru_byte_budget():
    img = np.zeros((10, 10, 3), np.uint8)      # 300 bytes each
    c = DecodeCache(capacity_bytes=650)
    keys = [content_key(bytes([i])) for i in range(3)]
    for k in keys:
        c.put(k, img)
    assert len(c) == 2 and c.evictions == 1
    assert c.get(keys[0]) is None              # oldest evicted
    assert c.get(keys[2]) is not None
    c.put(content_key(b"big"), np.zeros((100, 100, 3), np.uint8))
    assert len(c) == 2                         # over-budget item not cached


# ----------------------------------------------------------------- shutdown
def test_graceful_shutdown_drains_accepted_work(corpus):
    svc = mksvc(paths=[timed_path("slow-arm", 0.02)], cache_bytes=0,
                num_workers=1)
    svc.start()
    futs = [svc.submit(f) for f in corpus.files[:8]]
    svc.stop(graceful=True)
    for f in futs:
        assert f.result(timeout=1) is not None   # already resolved
    with pytest.raises(ServiceShutdown):
        svc.submit(corpus.files[0])


def test_abort_shutdown_fails_pending_futures(corpus):
    svc = mksvc(paths=[timed_path("slow-arm", 0.05)], cache_bytes=0,
                num_workers=1, max_batch=1, max_wait_ms=0.0)
    svc.start()
    futs = [svc.submit(f) for f in corpus.files]
    svc.stop(graceful=False)
    outcomes = {"ok": 0, "shutdown": 0}
    for f in futs:
        try:
            f.result(timeout=1)
            outcomes["ok"] += 1
        except ServiceShutdown:
            outcomes["shutdown"] += 1
    assert outcomes["ok"] + outcomes["shutdown"] == len(corpus.files)
    assert outcomes["shutdown"] > 0


# ------------------------------------------------------------------ metrics
def test_rolling_rate_not_inflated_by_lone_event():
    from repro.service.metrics import RollingWindow
    w = RollingWindow()
    now = time.monotonic()
    w.add(1.0, t=now)
    assert w.rate() == 0.0                     # one event is not a rate
    w.add(1.0, t=now)                          # zero-span burst
    assert w.rate() == 0.0
    w2 = RollingWindow()
    for k in range(5):
        w2.add(1.0, t=now - 2.0 + k * 0.5)     # 5 events over 2s
    assert w2.rate() == pytest.approx(4 / 2.0)


def test_metrics_snapshot_shape(corpus):
    with mksvc() as svc:
        for f in corpus.files[:6]:
            svc.decode(f)
        snap = svc.stats()
    lat = snap["service"]["latency_s"]
    assert set(lat) == {"p50", "p95", "p99"}
    assert 0 < lat["p50"] <= lat["p95"] <= lat["p99"]
    assert snap["service"]["throughput_rps"] > 0
    assert sum(snap["service"]["path_hits"].values()) \
        + snap["service"]["cache_hits"] == 6
    import json
    json.loads(svc.metrics.to_json())          # JSON-exportable
