"""The online mapping service: hits, bucketing, coalescing, deadlines —
plus the concurrent-cache storm the service's hot path depends on."""
import math
import threading

import pytest

from repro.core.einsum import batched_matmul, matmul
from repro.core.mapper import tcm_map
from repro.core.presets import nvdla_like, tpu_v4i_like
from repro.netmap.cache import MappingCache
from repro.serve_map import MapRequest, MappingService, ShapeBucketer
from repro.serve_map.bucket import validate_bucketed
from repro.testing.faults import tear_last_line

ARCH = nvdla_like(tensors=("A", "B", "Z"))


def svc(tmp_path, **kw):
    kw.setdefault("background_warm", False)
    return MappingService(cache_root=tmp_path / "cache", **kw)


# -- bucketing ---------------------------------------------------------------


def test_bucketer_rounds_up_to_pow2():
    b = ShapeBucketer()
    assert [b.bucket_value(x) for x in (1, 2, 3, 5, 8, 100, 128)] == \
        [1, 2, 4, 8, 8, 128, 128]


def test_bucket_einsum_pow2_shapes_pass_through():
    ein = matmul("mm", 8, 16, 4)
    out, changed = ShapeBucketer().bucket_einsum(ein)
    assert out is ein and not changed


def test_bucket_einsum_dominates_and_validates(tmp_path):
    with svc(tmp_path) as s:
        exact = matmul("decode", 3, 16, 4)  # m=3 -> bucket m=4
        resp = s.map(MapRequest(einsum=exact, arch=ARCH))
        assert resp.bucketed
        assert resp.served_einsum.rank_shapes == {"m": 4, "k": 16, "n": 4}
        # the served mapping passes the full contract check
        validate_bucketed(exact, resp.served_einsum, ARCH,
                          resp.result.mapping)


def test_bucket_hit_reuses_neighbor_shape(tmp_path):
    with svc(tmp_path) as s:
        s.map(MapRequest(einsum=matmul("a", 3, 16, 4), arch=ARCH))
        resp = s.map(MapRequest(einsum=matmul("b", 4, 16, 4), arch=ARCH))
        # m=4 is the bucket the m=3 search produced: served from the index
        assert resp.source == "exact-hit"  # 4 is already on-boundary
        resp3 = s.map(MapRequest(einsum=matmul("c", 2, 16, 4), arch=ARCH))
        assert resp3.source == "search"  # different bucket (m=2)
        assert s.stats.searches == 2


# -- hits and parity ---------------------------------------------------------


def test_exact_hit_bit_parity_with_offline(tmp_path):
    ein = matmul("probe", 8, 16, 4)
    offline, _ = tcm_map(ein, ARCH, objective="edp")
    with svc(tmp_path) as s:
        first = s.map(MapRequest(einsum=ein, arch=ARCH))
        hit = s.map(MapRequest(einsum=ein, arch=ARCH))
    assert first.source == "search" and hit.source == "exact-hit"
    for r in (first, hit):
        assert r.result.mapping == offline.mapping
        assert (r.result.energy, r.result.latency, r.result.edp) == \
            (offline.energy, offline.latency, offline.edp)
    assert hit.gap_bound == 1.0


def test_hot_index_survives_cache_reopen(tmp_path):
    ein = matmul("probe", 8, 16, 4)
    with svc(tmp_path) as s:
        s.map(MapRequest(einsum=ein, arch=ARCH))
    with svc(tmp_path) as s2:  # fresh service, same cache dir
        resp = s2.map(MapRequest(einsum=ein, arch=ARCH))
        assert resp.source == "exact-hit"
        assert s2.stats.searches == 0


# -- coalescing --------------------------------------------------------------


def test_cold_stampede_runs_exactly_one_search(tmp_path):
    ein = matmul("herd", 16, 32, 8)
    with svc(tmp_path) as s:
        n = 8
        barrier = threading.Barrier(n)
        out, errs = [], []

        def worker():
            try:
                barrier.wait()
                out.append(s.map(MapRequest(einsum=ein, arch=ARCH)))
            except BaseException as e:
                errs.append(e)

        ts = [threading.Thread(target=worker) for _ in range(n)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errs
        assert s.stats.searches == 1  # the coalescing contract
        assert s.stats.coalesced == n - 1
        assert sorted(r.source for r in out) == \
            ["coalesced"] * (n - 1) + ["search"]
        assert len({r.result.edp for r in out}) == 1


# -- deadlines ---------------------------------------------------------------


def test_deadline_miss_returns_finite_certified_gap(tmp_path):
    big = batched_matmul("qk", 64, 256, 64, 256)
    arch = tpu_v4i_like()
    with MappingService(cache_root=tmp_path / "c",
                        background_warm=True) as s:
        resp = s.map(MapRequest(einsum=big, arch=arch, deadline_s=0.03))
        assert resp.result is not None
        assert resp.source == "search"
        assert math.isfinite(resp.gap_bound) and resp.gap_bound >= 1.0
        assert resp.stats.truncated
        assert s.stats.truncated_searches == 1
        # the background warm replaces it with the exact optimum
        assert s.drain_warm(timeout_s=120.0)
        assert s.stats.background_warms == 1
        warm = s.map(MapRequest(einsum=big, arch=arch, deadline_s=0.03))
        assert warm.source in ("exact-hit", "bucket-hit")
        assert warm.gap_bound == 1.0


def test_truncated_answers_are_never_cached(tmp_path):
    big = batched_matmul("qk", 64, 256, 64, 256)
    arch = tpu_v4i_like()
    with svc(tmp_path) as s:  # warm thread disabled
        resp = s.map(MapRequest(einsum=big, arch=arch, deadline_s=0.03))
        assert resp.stats.truncated
        assert len(s.cache) == 0  # only exact optima enter the store
        again = s.map(MapRequest(einsum=big, arch=arch, deadline_s=0.03))
        assert again.source == "search"  # re-searched, not served stale


# -- warm-hit tail latency ---------------------------------------------------


def test_warm_hit_tail_latency_under_concurrency(tmp_path):
    ein = matmul("hot", 8, 16, 4)
    with svc(tmp_path) as s:
        s.map(MapRequest(einsum=ein, arch=ARCH))  # warm
        n, per = 8, 25
        barrier = threading.Barrier(n)
        errs = []

        def worker():
            try:
                barrier.wait()
                for _ in range(per):
                    r = s.map(MapRequest(einsum=ein, arch=ARCH))
                    assert r.source == "exact-hit"
            except BaseException as e:
                errs.append(e)

        ts = [threading.Thread(target=worker) for _ in range(n)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errs
        p50, p99 = s.stats.latency_quantiles(hits_only=True)
        assert p50 < 0.005, f"hit p50 {p50 * 1e3:.3f} ms"
        assert p99 < 0.050, f"hit p99 {p99 * 1e3:.3f} ms"


# -- concurrent cache storm (satellite: netmap/cache thread safety) ----------


def _seed_result():
    ein = matmul("seed", 8, 16, 4)
    best, stats = tcm_map(ein, ARCH, objective="edp")
    return ein, best, stats


def test_cache_threaded_storm_loses_no_entries(tmp_path):
    _, best, stats = _seed_result()
    cache = MappingCache(root=tmp_path)
    n_threads, per = 8, 10
    barrier = threading.Barrier(n_threads)
    errs = []

    def worker(tid):
        try:
            barrier.wait()
            for i in range(per):
                ein = matmul(f"w{tid}", 8 * (tid + 1), 16, 2 * (i + 1))
                cache.put(ein, ARCH, "edp", best, stats)
                assert cache.get(ein, ARCH, "edp") is not None
        except BaseException as e:
            errs.append(e)

    ts = [threading.Thread(target=worker, args=(i,))
          for i in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs
    # every write survives in this instance AND on disk (fresh reload)
    assert len(cache) == n_threads * per
    fresh = MappingCache(root=tmp_path)
    assert len(fresh) == n_threads * per
    assert fresh.n_corrupt == 0
    for tid in range(n_threads):
        for i in range(per):
            ein = matmul(f"w{tid}", 8 * (tid + 1), 16, 2 * (i + 1))
            assert fresh.get(ein, ARCH, "edp") is not None


def test_cache_storm_with_crashing_external_writer(tmp_path):
    """Readers/writers race an external writer that crashes mid-append:
    no committed entry is lost and the torn line lands in quarantine."""
    ein0, best, stats = _seed_result()
    cache = MappingCache(root=tmp_path)
    cache.put(ein0, ARCH, "edp", best, stats)

    # external process' cache handle appends, then "crashes" (torn line)
    external = MappingCache(root=tmp_path)
    external.put(matmul("ext", 4, 16, 4), ARCH, "edp", best, stats)
    tear_last_line(cache.path)

    n_threads, per = 6, 6
    barrier = threading.Barrier(n_threads)
    errs = []

    def worker(tid):
        try:
            barrier.wait()
            for i in range(per):
                ein = matmul(f"s{tid}", 4 * (tid + 1), 8, 2 * (i + 1))
                cache.put(ein, ARCH, "edp", best, stats)
                assert cache.get(ein0, ARCH, "edp") is not None  # seed kept
        except BaseException as e:
            errs.append(e)

    ts = [threading.Thread(target=worker, args=(i,))
          for i in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs

    fresh = MappingCache(root=tmp_path)
    assert fresh.get(ein0, ARCH, "edp") is not None
    for tid in range(n_threads):
        for i in range(per):
            ein = matmul(f"s{tid}", 4 * (tid + 1), 8, 2 * (i + 1))
            assert fresh.get(ein, ARCH, "edp") is not None
    # the torn external append was quarantined, not resurrected
    assert fresh.get(matmul("ext", 4, 16, 4), ARCH, "edp") is None
    assert cache.quarantine_path.exists()


# -- load generator ----------------------------------------------------------


def test_loadgen_smoke(tmp_path):
    from repro.configs import get_config
    from repro.serve_map.loadgen import run_loadgen

    cfg = get_config("qwen1_5_0_5b", smoke=True)
    arch = tpu_v4i_like()
    with MappingService(cache_root=tmp_path / "c") as s:
        report = run_loadgen(s, cfg, arch, requests=16, clients=4,
                             seed=0, deadline_s=0.25, seq_range=(16, 256))
    assert report["requests"] == 16
    assert report["stampede_searches"] == 1
    assert report["stampede_coalesced"] == 3
    assert report["coalesce_ratio"] == pytest.approx(0.75)
    assert report["deadline_met_ratio"] == 1.0
    assert report["service"]["requests"] >= 16


def test_bench_measure_refuses_host_without_tpu(capsys):
    import jax

    from repro.serve_map.__main__ import main
    if jax.devices()[0].platform == "tpu":
        pytest.skip("this host has a TPU: --measure times it")
    assert main(["bench", "--fast", "--measure"]) == 2
    assert "TPU" in capsys.readouterr().err
