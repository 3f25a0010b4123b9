"""Autotune engine + cost model + dispatcher telemetry.

Contracts under test (README "Autotune & telemetry"):

* chunk resolution is a single resolved field: ``ResolvedLaunch.chunk``
  + ``chunk_source`` ('explicit' | 'heuristic' | 'cooperative' |
  'autotuned'), and the autotuner may only move knobs whose source is
  'heuristic'/'auto' — an explicit ``chunk=``/``backend=``/
  ``warp_exec=`` is never overridden (the regression the resolver
  refactor exists to prevent);
* tuned launches are bitwise-equal to heuristic launches, the winner is
  persisted (version-stamped, atomic), and a warm lookup — in-memory or
  from disk in a simulated fresh process — issues ZERO measurement
  launches;
* cache robustness: corrupt/truncated/stale-version files degrade to
  heuristics without crashing, concurrent writers never torch the file
  (atomic rename + read-merge), ``COX_AUTOTUNE_CACHE=off`` keeps disk
  untouched;
* the cost model returns positive op/mem estimates in both 'static'
  and 'xla' modes, and the footprint model scales with chunk;
* the dispatcher records per-stage-key telemetry rows and surfaces the
  autotune counters through ``health()``.
"""
import dataclasses
import json
import os
import threading

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from repro.core import cox  # noqa: E402
from repro.core import autotune as at  # noqa: E402
from repro.core import costmodel  # noqa: E402
from repro.core import runtime as rt  # noqa: E402
from repro.core.backends.plan import DEFAULT_CHUNK  # noqa: E402
from repro.core.types import CoxUnsupported  # noqa: E402


@cox.kernel
def _atSaxpy(c, out: cox.Array(cox.f32), x: cox.Array(cox.f32),
             y: cox.Array(cox.f32), n: cox.i32):
    i = c.block_idx() * c.block_dim() + c.thread_idx()
    if i < n:
        out[i] = 2.0 * x[i] + y[i]


@cox.kernel
def _atGridSum(c, out: cox.Array(cox.f32), x: cox.Array(cox.f32)):
    s = c.shared(32, cox.f32)
    i = c.block_idx() * c.block_dim() + c.thread_idx()
    s[c.thread_idx()] = x[i]
    c.syncthreads()
    if c.thread_idx() == 0:
        acc = 0.0
        for j in range(32):
            acc = acc + s[j]
        out[c.block_idx()] = acc


GRID, BLOCK = 16, 64
N = GRID * BLOCK


def _args():
    x = np.arange(N, dtype=np.float32) / N
    y = np.ones(N, np.float32)
    return (np.zeros(N, np.float32), x, y, N)


@pytest.fixture
def tuner(tmp_path, monkeypatch):
    """Isolated autotune state: fresh counters, a tmp cache file."""
    cache = tmp_path / "autotune.json"
    monkeypatch.setenv(at.ENV_CACHE, str(cache))
    monkeypatch.delenv(at.ENV_ENABLE, raising=False)
    at.reset()
    yield cache
    at.reset()


# ---------------------------------------------------------------------------
# chunk resolution: one resolved field, explicit never overridden
# ---------------------------------------------------------------------------

class TestChunkResolution:
    def test_heuristic_default(self):
        ck = _atSaxpy.compiled(block=BLOCK)
        val, src = rt.resolve_chunk(ck, GRID, None)
        assert (val, src) == (min(GRID, DEFAULT_CHUNK), "heuristic")
        val, src = rt.resolve_chunk(ck, GRID, "auto")
        assert (val, src) == (min(GRID, DEFAULT_CHUNK), "heuristic")

    def test_explicit(self):
        ck = _atSaxpy.compiled(block=BLOCK)
        assert rt.resolve_chunk(ck, GRID, 3) == (3, "explicit")
        # clamped to the grid but still explicit
        assert rt.resolve_chunk(ck, GRID, 999) == (GRID, "explicit")
        with pytest.raises(ValueError):
            rt.resolve_chunk(ck, GRID, 0)

    def test_resolved_launch_carries_source(self):
        req = _atSaxpy.make_request(grid=GRID, block=BLOCK, args=_args(),
                                    chunk=5)
        assert req.rl.chunk == 5
        assert req.rl.chunk_source == "explicit"
        assert req.chunk == 5  # the request mirrors the resolved value
        req = _atSaxpy.make_request(grid=GRID, block=BLOCK, args=_args())
        assert req.rl.chunk == min(GRID, DEFAULT_CHUNK)
        assert req.rl.chunk_source == "heuristic"

    def test_explicit_never_autotuned(self, tuner):
        """Regression: an explicit chunk= survives autotune=True."""
        req = _atSaxpy.make_request(grid=GRID, block=BLOCK, args=_args(),
                                    chunk=5, autotune=True)
        assert req.rl.chunk == 5
        assert req.rl.chunk_source == "explicit"

    def test_explicit_backend_never_autotuned(self, tuner):
        req = _atSaxpy.make_request(grid=GRID, block=BLOCK, args=_args(),
                                    backend="scan", warp_exec="serial",
                                    chunk=5, autotune=True)
        # nothing tunable: the tuner must not even measure
        assert req.rl.backend == "scan"
        assert req.rl.warp_exec == "serial"
        assert req.rl.chunk == 5
        assert at.stats()["measurements"] == 0

    def test_tuned_source_marked(self, tuner):
        req = _atSaxpy.make_request(grid=GRID, block=BLOCK, args=_args(),
                                    autotune=True)
        # whatever won, the knobs must be legal and the source recorded
        assert req.rl.backend in ("scan", "vmap")
        assert req.rl.chunk >= 1
        if req.rl.chunk_source == "autotuned":
            assert at.stats()["tuned"] >= 1


# ---------------------------------------------------------------------------
# tuning correctness + persistence
# ---------------------------------------------------------------------------

class TestTune:
    def test_cold_tune_writes_cache(self, tuner):
        out = _atSaxpy.launch(grid=GRID, block=BLOCK, args=_args(),
                              autotune=True)
        want = 2.0 * np.arange(N, dtype=np.float32) / N + 1.0
        np.testing.assert_allclose(np.asarray(out["out"]), want, rtol=1e-6)
        st = at.stats()
        assert st["misses"] == 1
        assert st["measurements"] > 0
        assert st["disk_writes"] == 1
        doc = json.loads(tuner.read_text())
        assert doc["version"] == at.AUTOTUNE_VERSION
        assert len(doc["entries"]) == 1
        rec = next(iter(doc["entries"].values()))
        assert rec["backend"] in ("scan", "vmap")
        assert rec["chunk"] >= 1
        assert rec["op_estimate"] > 0
        assert rec["mem_estimate"] > 0

    def test_warm_memory_hit(self, tuner):
        _atSaxpy.make_request(grid=GRID, block=BLOCK, args=_args(),
                              autotune=True)
        n = at.stats()["measurements"]
        req = _atSaxpy.make_request(grid=GRID, block=BLOCK, args=_args(),
                                    autotune=True)
        st = at.stats()
        assert st["hits"] == 1
        assert st["measurements"] == n  # zero new launches
        assert req.rl.chunk >= 1

    def test_warm_disk_hit_fresh_process(self, tuner):
        req1 = _atSaxpy.make_request(grid=GRID, block=BLOCK, args=_args(),
                                     autotune=True)
        cold = at.stats()["measurements"]
        at.reset(memory_only=True)  # simulated fresh process, disk intact
        req2 = _atSaxpy.make_request(grid=GRID, block=BLOCK, args=_args(),
                                     autotune=True)
        st = at.stats()
        assert st["disk_hits"] == 1
        assert st["measurements"] == cold  # zero NEW measurement launches
        assert (req2.rl.backend, req2.rl.warp_exec, req2.rl.chunk) == \
            (req1.rl.backend, req1.rl.warp_exec, req1.rl.chunk)

    def test_bitwise_equal_grid_sum(self, tuner):
        x = np.random.default_rng(0).random(8 * 32).astype(np.float32)
        args = (np.zeros(8, np.float32), x)
        base = _atGridSum.launch(grid=8, block=32, args=args)
        tuned = _atGridSum.launch(grid=8, block=32, args=args,
                                  autotune=True)
        np.testing.assert_array_equal(np.asarray(tuned["out"]),
                                      np.asarray(base["out"]))

    def test_heuristic_cell_always_candidate(self, tuner):
        _atSaxpy.make_request(grid=GRID, block=BLOCK, args=_args(),
                              autotune=True)
        rec = next(iter(at.entries().values()))
        rl = rt.resolve_launch(_atSaxpy.compiled(block=BLOCK), grid=GRID,
                               block=BLOCK)
        heur = "%s/%s/c%d" % (rl.backend, rl.warp_exec, rl.chunk)
        assert heur in rec["times_us"], \
            f"heuristic cell {heur} missing from {sorted(rec['times_us'])}"

    def test_env_enable_tunes_all_auto(self, tuner, monkeypatch):
        monkeypatch.setenv(at.ENV_ENABLE, "1")
        _atSaxpy.make_request(grid=GRID, block=BLOCK, args=_args())
        assert at.stats()["misses"] == 1


# ---------------------------------------------------------------------------
# cache robustness
# ---------------------------------------------------------------------------

class TestCacheRobustness:
    def test_corrupt_cache_falls_back(self, tuner):
        tuner.write_text("{not json at all")
        req = _atSaxpy.make_request(grid=GRID, block=BLOCK, args=_args(),
                                    autotune=True)
        assert req.rl.chunk >= 1  # no crash, tuning proceeded
        st = at.stats()
        assert st["load_errors"] >= 1
        # and the bad file was replaced with a valid one
        doc = json.loads(tuner.read_text())
        assert doc["version"] == at.AUTOTUNE_VERSION

    def test_truncated_cache_falls_back(self, tuner):
        # a valid doc chopped mid-way (torn write from a dead process)
        at.reset()
        _atSaxpy.make_request(grid=GRID, block=BLOCK, args=_args(),
                              autotune=True)
        whole = tuner.read_text()
        tuner.write_text(whole[: len(whole) // 2])
        at.reset()
        req = _atSaxpy.make_request(grid=GRID, block=BLOCK, args=_args(),
                                    autotune=True)
        st = at.stats()
        assert st["load_errors"] >= 1
        assert st["misses"] == 1  # re-measured, no crash
        assert req.rl.chunk >= 1

    def test_stale_version_invalidates(self, tuner):
        _atSaxpy.make_request(grid=GRID, block=BLOCK, args=_args(),
                              autotune=True)
        doc = json.loads(tuner.read_text())
        doc["version"] = at.AUTOTUNE_VERSION - 1
        tuner.write_text(json.dumps(doc))
        at.reset()
        _atSaxpy.make_request(grid=GRID, block=BLOCK, args=_args(),
                              autotune=True)
        st = at.stats()
        assert st["disk_hits"] == 0
        assert st["misses"] == 1  # stale stamp -> wholesale re-measure

    def test_wrong_shape_entries_tolerated(self, tuner):
        tuner.write_text(json.dumps(
            {"version": at.AUTOTUNE_VERSION, "entries": ["not", "a", "map"]}))
        _atSaxpy.make_request(grid=GRID, block=BLOCK, args=_args(),
                              autotune=True)
        assert at.stats()["load_errors"] >= 1

    def test_concurrent_writers_atomic(self, tuner):
        """N threads save disjoint records; the file must stay valid
        JSON and (read-merge) retain every record."""
        recs = {f"key-{i}": {"backend": "scan", "warp_exec": "serial",
                             "chunk": i + 1} for i in range(16)}
        errs = []

        def save(k):
            try:
                at._save_disk(str(tuner), {k: recs[k]})
            except Exception as e:  # pragma: no cover - the failure mode
                errs.append(e)

        threads = [threading.Thread(target=save, args=(k,)) for k in recs]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        doc = json.loads(tuner.read_text())  # never torn
        assert doc["version"] == at.AUTOTUNE_VERSION
        # atomic rename means a racer can lose an update but never
        # corrupt: whatever survives is a valid subset of what was
        # written, and at least the last replace's view is complete
        assert set(doc["entries"]) <= set(recs)
        assert doc["entries"]
        for k, v in doc["entries"].items():
            assert v == recs[k]

    def test_cache_off_env(self, tuner, monkeypatch):
        monkeypatch.setenv(at.ENV_CACHE, "off")
        _atSaxpy.make_request(grid=GRID, block=BLOCK, args=_args(),
                              autotune=True)
        st = at.stats()
        assert st["misses"] == 1
        assert st["disk_writes"] == 0
        assert at.cache_path() is None
        assert not tuner.exists()

    def test_no_leftover_temp_files(self, tuner):
        _atSaxpy.make_request(grid=GRID, block=BLOCK, args=_args(),
                              autotune=True)
        stray = [p for p in os.listdir(tuner.parent)
                 if p.startswith(".autotune-")]
        assert stray == []


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------

class TestCostModel:
    def test_static_estimate_positive(self):
        req = _atSaxpy.make_request(grid=GRID, block=BLOCK, args=_args())
        est = costmodel.estimate(req.ck, req.rl, req.shapes, mode="static")
        assert est.source == "static"
        assert est.op_estimate > 0
        assert est.mem_estimate > 0
        assert est.gflops(1.0) == pytest.approx(est.op_estimate / 1e9)
        assert est.gflops(0.0) == 0.0

    def test_xla_estimate_positive(self):
        req = _atSaxpy.make_request(grid=GRID, block=BLOCK, args=_args())
        est = costmodel.estimate(req.ck, req.rl, req.shapes, mode="xla")
        assert est.source == "xla"
        assert est.op_estimate > 0
        assert est.mem_estimate > 0

    def test_estimate_cached(self):
        req = _atSaxpy.make_request(grid=GRID, block=BLOCK, args=_args())
        a = costmodel.estimate(req.ck, req.rl, req.shapes, mode="static")
        b = costmodel.estimate(req.ck, req.rl, req.shapes, mode="static")
        assert a is b

    def test_footprint_scales_with_chunk(self):
        req = _atGridSum.make_request(grid=8, block=32,
                                      args=(np.zeros(8, np.float32),
                                            np.zeros(8 * 32, np.float32)))
        f4 = costmodel.chunk_footprint(req.ck, req.shapes, chunk=4,
                                       n_warps=1)
        f8 = costmodel.chunk_footprint(req.ck, req.shapes, chunk=8,
                                       n_warps=1)
        assert f8 == 2 * f4 > 0
        # the batched plane replicates shared memory per warp
        fb = costmodel.chunk_footprint(req.ck, req.shapes, chunk=4,
                                       n_warps=2, warp_exec="batched")
        assert fb > f4

    def test_kernel_features_shared(self):
        shared, peels, density = costmodel.kernel_features(
            _atGridSum.compiled(block=32))
        assert shared == 32 * 4  # 32 f32 slots
        assert peels >= 0
        assert 0.0 <= density <= 1.0

    def test_telemetry_mode_env(self, monkeypatch):
        monkeypatch.delenv(costmodel.ENV_MODE, raising=False)
        assert costmodel.telemetry_mode() == "static"
        monkeypatch.setenv(costmodel.ENV_MODE, "xla")
        assert costmodel.telemetry_mode() == "xla"
        monkeypatch.setenv(costmodel.ENV_MODE, "garbage")
        assert costmodel.telemetry_mode() == "static"


# ---------------------------------------------------------------------------
# dispatcher telemetry + health
# ---------------------------------------------------------------------------

class TestTelemetry:
    def test_rows_recorded(self):
        from repro.core.streams import Dispatcher
        d = Dispatcher()
        s = cox.Stream("telemetry-test", dispatcher=d)
        h = s.launch(_atSaxpy, grid=GRID, block=BLOCK, args=_args())
        h.result()
        rows = d.telemetry()
        assert len(rows) == 1
        row = rows[0]
        assert row["kernel"] == "_atSaxpy"
        assert row["launches"] == 1
        assert row["chunk"] >= 1
        assert row["chunk_source"] in ("heuristic", "explicit",
                                       "cooperative", "autotuned")
        assert row["op_estimate"] > 0
        assert row["mem_estimate"] > 0
        assert row["estimate_source"] in ("static", "xla")
        # dispatch timing is host-side and always present
        assert row["dispatch_s"] > 0
        assert row["s_per_launch"] == pytest.approx(row["dispatch_s"])
        assert row["bytes"] == row["mem_estimate"]
        assert row["flops"] == row["op_estimate"]

    def test_health_carries_autotune_and_telemetry(self):
        from repro.core.streams import Dispatcher
        d = Dispatcher()
        s = cox.Stream("health-test", dispatcher=d)
        s.launch(_atSaxpy, grid=GRID, block=BLOCK, args=_args()).result()
        h = d.health()
        assert h["telemetry_keys"] == 1
        assert h["dispatch_s"] > 0
        assert h["bytes"] > 0
        assert isinstance(h["autotune"], dict)
        assert set(h["autotune"]) >= {"hits", "misses", "measurements"}

    def test_roofline_from_telemetry(self):
        from benchmarks.roofline import from_telemetry
        from repro.core.streams import Dispatcher
        d = Dispatcher()
        s = cox.Stream("roofline-test", dispatcher=d)
        s.launch(_atSaxpy, grid=GRID, block=BLOCK, args=_args()).result()
        rows = from_telemetry(d.telemetry(), peak_flops=1e9, mem_bw=1e9)
        assert len(rows) == 1
        r = rows[0]
        assert r["dominant"] in ("compute", "memory")
        assert r["t_compute"] > 0 and r["t_memory"] > 0
        assert 0.0 <= r["roofline_fraction"] <= 1.0


# ---------------------------------------------------------------------------
# cooperative launches pin the chunk
# ---------------------------------------------------------------------------

@cox.kernel
def _atGridSync(c, out: cox.Array(cox.f32), x: cox.Array(cox.f32)):
    i = c.block_idx() * c.block_dim() + c.thread_idx()
    out[i] = x[i] * 2.0
    c.grid_sync()
    out[i] = out[i] + 1.0


class TestCooperative:
    def test_chunk_pinned_to_grid(self):
        n = 4 * 32
        req = _atGridSync.make_request(
            grid=4, block=32, args=(np.zeros(n, np.float32),
                                    np.ones(n, np.float32)))
        assert req.rl.chunk == 4
        assert req.rl.chunk_source == "cooperative"

    def test_explicit_small_chunk_rejected(self):
        n = 4 * 32
        with pytest.raises(CoxUnsupported):
            _atGridSync.make_request(
                grid=4, block=32, chunk=2,
                args=(np.zeros(n, np.float32), np.ones(n, np.float32)))

    def test_autotune_respects_cooperative(self, tuner):
        n = 4 * 32
        req = _atGridSync.make_request(
            grid=4, block=32, autotune=True,
            args=(np.zeros(n, np.float32), np.ones(n, np.float32)))
        assert req.rl.chunk == 4
        assert req.rl.chunk_source == "cooperative"
