"""Device-path resilience: the HBM residency governor (budget
accounting, LRU eviction, pins), the OOM recovery ladder
(evict-and-retry, host-fold degradation), plan-signature quarantine,
and the lock-free device_memory() consistency fix.

Every test runs on the 8-virtual-device CPU mesh (conftest), with
device OOM simulated through the mesh.stage / device.exec fault seams
(fault.SimulatedResourceExhausted carries the RESOURCE_EXHAUSTED
message marker the serve-layer classifier keys on — the same string
jaxlib puts in a real XlaRuntimeError).
"""

import threading
import time

import pytest

from pilosa_tpu import SLICE_WIDTH
from pilosa_tpu import fault
from pilosa_tpu.core import Holder
from pilosa_tpu.executor import Executor
from pilosa_tpu.ops.pool import CONTAINER_WORDS, ROW_SPAN
from pilosa_tpu.pql import parse_string

# Padded device bytes of ONE minimal staged view on the 8-device test
# mesh: 1 slice pads to 8, 1 row pads to ROW_SPAN containers, each slot
# is CONTAINER_WORDS words + 1 key. Budgets below are sized in units
# of this.
VIEW_BYTES = 8 * ROW_SPAN * (CONTAINER_WORDS * 4 + 4)


@pytest.fixture
def holder(tmp_path):
    h = Holder(str(tmp_path / "data"))
    h.open()
    yield h
    h.close()


@pytest.fixture(autouse=True)
def _clean_faults():
    fault.reset(seed=0)
    yield
    fault.reset(seed=0)


def seed(holder, index="i", frame="general", bits=()):
    idx = holder.create_index_if_not_exists(index)
    f = idx.create_frame_if_not_exists(frame)
    for row, col in bits:
        f.set_bit(row, col)
    return f


def q(executor, index, pql):
    return executor.execute(index, parse_string(pql))


def make_executor(holder, budget_bytes, **mesh_over):
    cfg = {"hbm_budget_bytes": budget_bytes, "hbm_headroom": 0.15,
           "quarantine_after": 2, "quarantine_ttl": 60.0}
    cfg.update(mesh_over)
    return Executor(holder, use_device=True, mesh_config=cfg)


class TestBudgetAccounting:
    def test_estimate_matches_staged_bytes(self, holder):
        seed(holder, bits=[(1, 0), (2, SLICE_WIDTH + 5)])
        e = make_executor(holder, budget_bytes=-1)  # unlimited
        assert q(e, "i", "Count(Bitmap(rowID=1))") == [1]
        mgr = e.mesh_manager()
        sv = mgr._views[("i", "general", "standard")]
        bitmaps, _ = mgr._snapshot_fragments("i", "general", "standard",
                                             sv.num_slices)
        assert mgr._estimate_staged_bytes(bitmaps) == mgr._view_bytes(sv)
        assert mgr.stats["staged_bytes"] == mgr._view_bytes(sv)

    def test_budget_resolution_order(self, holder, monkeypatch):
        e = make_executor(holder, budget_bytes=12345)
        mgr = e.mesh_manager()
        assert mgr._hbm_budget_bytes() == 12345
        # Env overrides only when config leaves the knob at 0 = auto.
        monkeypatch.setenv("PILOSA_TPU_HBM_BUDGET_BYTES", "777")
        mgr._config["hbm_budget_bytes"] = 0
        mgr._budget_resolved = None
        assert mgr._hbm_budget_bytes() == 777
        # Negative config = explicitly unlimited (<= 0 short-circuits).
        mgr._config["hbm_budget_bytes"] = -1
        mgr._budget_resolved = None
        assert mgr._hbm_budget_bytes() == -1
        # The resolved value is surfaced as a gauge.
        mgr._config["hbm_budget_bytes"] = 4096
        mgr._budget_resolved = None
        mgr._hbm_budget_bytes()
        assert mgr.stats["hbm_budget_bytes"] == 4096

    def test_lru_eviction_order(self, holder):
        idx = holder.create_index_if_not_exists("i")
        for fr in ("f1", "f2", "f3"):
            idx.create_frame_if_not_exists(fr).set_bit(1, 7)
        # Room for two views: staging the third evicts the LRU (f1).
        e = make_executor(holder, budget_bytes=2 * VIEW_BYTES)
        for fr in ("f1", "f2", "f3"):
            assert q(e, "i", f"Count(Bitmap(rowID=1, frame={fr}))") == [1]
        mgr = e.mesh_manager()
        frames = [k[1] for k in mgr._views]
        assert "f1" not in frames
        assert {"f2", "f3"} <= set(frames)
        assert mgr.stats["evicted_budget"] >= 1
        assert mgr.stats["staged_bytes"] <= 2 * VIEW_BYTES
        # Touch f2 (now LRU would be f2 without the touch), then stage
        # f1 again: f3 — the least recently USED — must go, not f2.
        # Fresh rowIDs defeat the executor's whole-query memo (same
        # plan shape, different cache key) so the queries actually
        # reach the mesh.
        assert q(e, "i", "Count(Bitmap(rowID=2, frame=f2))") == [0]
        assert q(e, "i", "Count(Bitmap(rowID=2, frame=f1))") == [0]
        frames = [k[1] for k in mgr._views]
        assert "f3" not in frames
        assert {"f1", "f2"} <= set(frames)

    def test_resident_view_not_evicted_by_its_own_restage(self, holder):
        f = seed(holder, bits=[(1, 0)])
        e = make_executor(holder, budget_bytes=VIEW_BYTES)
        assert q(e, "i", "Count(Bitmap(rowID=1))") == [1]
        # Growing the same view restages over its own slot — the
        # budget check must not see the old image as "other" bytes.
        f.set_bit(ROW_SPAN + 5, 3)  # new row block: forces restage
        assert q(e, "i", "Count(Bitmap(rowID=1))") == [1]
        mgr = e.mesh_manager()
        assert ("i", "general", "standard") in mgr._views


class TestPins:
    def test_pinned_views_survive_oom_eviction(self, holder):
        seed(holder, bits=[(1, 0)])
        e = make_executor(holder, budget_bytes=-1)
        assert q(e, "i", "Count(Bitmap(rowID=1))") == [1]
        mgr = e.mesh_manager()
        sv = mgr._views[("i", "general", "standard")]
        sv.pins = 1
        assert mgr._evict_for_oom() == 0
        assert ("i", "general", "standard") in mgr._views
        sv.pins = 0
        assert mgr._evict_for_oom() == 1
        assert not mgr._views
        assert mgr.stats["evicted_oom"] == 1
        assert mgr.stats["staged_bytes"] == 0

    def test_pins_released_after_query(self, holder):
        seed(holder, bits=[(1, 0), (2, 1)])
        e = make_executor(holder, budget_bytes=-1)
        assert q(e, "i",
                 "Count(Intersect(Bitmap(rowID=1), Bitmap(rowID=2)))") == [0]
        mgr = e.mesh_manager()
        assert all(sv.pins == 0 for sv in mgr._views.values())

    def test_budget_eviction_skips_pinned(self, holder):
        idx = holder.create_index_if_not_exists("i")
        for fr in ("f1", "f2", "f3"):
            idx.create_frame_if_not_exists(fr).set_bit(1, 7)
        e = make_executor(holder, budget_bytes=2 * VIEW_BYTES)
        for fr in ("f1", "f2"):
            assert q(e, "i", f"Count(Bitmap(rowID=1, frame={fr}))") == [1]
        mgr = e.mesh_manager()
        mgr._views[("i", "f1", "standard")].pins = 1  # simulate in-flight
        try:
            assert q(e, "i", "Count(Bitmap(rowID=1, frame=f3))") == [1]
            frames = [k[1] for k in mgr._views]
            # f1 is pinned: f2 must be the eviction victim even though
            # f1 is older in the LRU order.
            assert "f1" in frames and "f2" not in frames
        finally:
            mgr._views[("i", "f1", "standard")].pins = 0


class TestOomRecovery:
    def test_stage_oom_evicts_and_retries(self, holder):
        seed(holder, bits=[(1, 0), (1, SLICE_WIDTH + 2)])
        e = make_executor(holder, budget_bytes=-1)
        fault.arm("mesh.stage", error=fault.SimulatedResourceExhausted,
                  times=1)
        assert q(e, "i", "Count(Bitmap(rowID=1))") == [2]
        mgr = e.mesh_manager()
        assert mgr.stats["oom_retries"] >= 1
        assert mgr.stats["stage"] == 1  # the retry's stage succeeded

    def test_exec_oom_recovers_in_request(self, holder):
        seed(holder, bits=[(1, 0), (1, 1)])
        e = make_executor(holder, budget_bytes=-1)
        fired0 = fault.STATS.get("fault.device.exec", 0)
        fault.arm("device.exec", error=fault.SimulatedResourceExhausted,
                  times=1)
        assert q(e, "i", "Count(Bitmap(rowID=1))") == [2]
        mgr = e.mesh_manager()
        assert mgr.stats["oom_retries"] >= 1
        assert fault.STATS.get("fault.device.exec", 0) == fired0 + 1

    def test_persistent_exec_oom_host_folds_correctly(self, holder):
        seed(holder, bits=[(1, 0), (1, 1), (2, 1)])
        e = make_executor(holder, budget_bytes=-1,
                          quarantine_after=1000)  # isolate the ladder
        host = Executor(holder, use_device=False)
        fault.arm("device.exec", error=fault.SimulatedResourceExhausted)
        pql = "Count(Intersect(Bitmap(rowID=1), Bitmap(rowID=2)))"
        assert q(e, "i", pql) == q(host, "i", pql) == [1]
        mgr = e.mesh_manager()
        assert mgr.stats["fallback_oom"] >= 1
        assert mgr.stats["count"] == 0  # device path never answered

    def test_stage_oom_after_eviction_host_folds(self, holder):
        seed(holder, bits=[(1, 0)])
        e = make_executor(holder, budget_bytes=-1)
        host = Executor(holder, use_device=False)
        fault.arm("mesh.stage", error=fault.SimulatedResourceExhausted)
        pql = "Count(Bitmap(rowID=1))"
        assert q(e, "i", pql) == q(host, "i", pql) == [1]
        mgr = e.mesh_manager()
        assert mgr.stats["fallback_oom"] >= 1
        assert mgr.stats["stage"] == 0


# What the chip's compiler says when it refuses a program, and what the
# runtime says when an allocation fails. The first two were raised here
# by the installed compiler for a described v5e chip
# (tests/test_tpu_compile.py raises them again, live); the third came
# from a TPU v5 lite (chip run, PR 21). All three carry
# RESOURCE_EXHAUSTED; only the third is a device OOM.
VMEM_REFUSAL = (
    "RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem while "
    "allocating on stack for %run.1 = s32[28,960]{1,0:T(8,128)S(1)} "
    "custom-call(...), custom_call_target=\"tpu_custom_call\". Scoped "
    "allocation with size 17.63M and limit 16.00M exceeded scoped vmem "
    "limit by 1.63M.")
HBM_COMPILE_REFUSAL = (
    "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
    "memory in memory space hbm. Used 22.50G of 15.75G hbm. Exceeded hbm "
    "capacity by 6.75G.")
RUNTIME_OOM = (
    "RESOURCE_EXHAUSTED: Error allocating device buffer: Attempting to "
    "allocate 1.00G. That was not possible. There are 765.88M free.; "
    "(0x0x0_HBM0)")


class TestCompileRefusalIsNotOom:
    """jit compiles lazily, so a compiler refusal surfaces at the first
    launch inside _guarded_exec with the same status word a device OOM
    carries. It must not walk the OOM ladder: evicting every staged
    view cannot make a kernel's VMEM need smaller."""

    @pytest.mark.parametrize("msg", [VMEM_REFUSAL, HBM_COMPILE_REFUSAL])
    def test_classified_as_refusal_not_oom(self, msg):
        from pilosa_tpu.parallel.serve import (_is_compile_refusal,
                                               _is_resource_exhausted)

        err = RuntimeError(msg)
        assert _is_compile_refusal(err)
        assert not _is_resource_exhausted(err)

    def test_runtime_allocation_failure_is_still_oom(self):
        from pilosa_tpu.parallel.serve import (_is_compile_refusal,
                                               _is_resource_exhausted)

        err = ValueError(RUNTIME_OOM)  # the class jax raised on the chip
        assert _is_resource_exhausted(err)
        assert not _is_compile_refusal(err)

    @pytest.mark.parametrize("msg", [VMEM_REFUSAL, HBM_COMPILE_REFUSAL])
    def test_guarded_exec_neither_evicts_nor_retries(self, holder, msg):
        seed(holder, bits=[(1, 0), (1, 1)])
        e = make_executor(holder, budget_bytes=-1)
        assert q(e, "i", "Count(Bitmap(rowID=1))") == [2]  # stages a view
        mgr = e.mesh_manager()
        assert len(mgr._views) == 1
        staged0 = mgr.stats["stage"]
        launches = []

        def launch():
            launches.append(1)
            raise RuntimeError(msg)

        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            mgr._guarded_exec("[\"leaf\", 0]", launch)
        assert launches == [1]                       # not retried
        assert len(mgr._views) == 1                  # nothing evicted
        assert mgr.stats["evicted_oom"] == 0
        assert mgr.stats["oom_retries"] == 0
        assert mgr.stats["fallback_oom"] == 0
        assert mgr.stats["fallback_compile"] == 1    # counted as itself
        assert mgr.stats["stage"] == staged0

    def test_refusal_at_result_fetch_is_not_counted_as_oom(self, holder):
        """The async half: an error that surfaces when the batch's
        result is fetched goes through the same classifier."""
        seed(holder, bits=[(1, 0), (2, 0)])
        e = make_executor(holder, budget_bytes=-1, quarantine_after=1000)
        mgr = e.mesh_manager()

        class Limbs:  # what a launch hands to finish()
            def copy_to_host_async(self):
                pass

            def __array__(self, *a, **k):
                raise RuntimeError(VMEM_REFUSAL)

        real = mgr._guarded_exec
        mgr._guarded_exec = lambda sig, launch, **kw: Limbs()
        mgr.lone_fused = False  # take the batch path, whose fetch is async
        try:
            host = Executor(holder, use_device=False)
            pql = "Count(Intersect(Bitmap(rowID=1), Bitmap(rowID=2)))"
            assert q(e, "i", pql) == q(host, "i", pql) == [1]
        finally:
            mgr._guarded_exec = real
        assert mgr.stats["fallback_compile"] == 1
        assert mgr.stats["fallback_oom"] == 0
        assert mgr.stats["evicted_oom"] == 0
        assert mgr.stats["fallback_error"] == 1  # the executor said so


class TestInfeasible:
    def test_budget_below_one_view_host_folds(self, holder):
        seed(holder, bits=[(1, 0), (1, SLICE_WIDTH + 2)])
        e = make_executor(holder, budget_bytes=1000)  # < any view
        host = Executor(holder, use_device=False)
        for r in (1, 2, 3):  # fresh rows: no memo, no thrash, no errors
            pql = f"Count(Bitmap(rowID={r}))"
            assert q(e, "i", pql) == q(host, "i", pql)
        mgr = e.mesh_manager()
        assert mgr.stats["fallback_hbm_infeasible"] >= 1
        assert mgr.stats["stage"] == 0
        assert mgr.stats["staged_bytes"] == 0

    def test_routing_peek_skips_doomed_stage(self, holder):
        seed(holder, bits=[(1, 0)])
        e = make_executor(holder, budget_bytes=1000)
        assert q(e, "i", "Count(Bitmap(rowID=1))") == [1]  # builds mgr
        mgr = e.mesh_manager()
        routed0 = mgr.stats["routed_host"]
        # Fresh rowID so the whole-query memo can't answer first.
        assert q(e, "i", "Count(Bitmap(rowID=2))") == [0]
        # Second query routes at the executor (stage_infeasible peek):
        # it never enters the mesh count path at all.
        assert mgr.stats["routed_host"] == routed0 + 1

    def test_infeasible_cache_invalidated_by_writes(self, holder):
        f = seed(holder, bits=[(1, 0)])
        e = make_executor(holder, budget_bytes=1000)
        assert q(e, "i", "Count(Bitmap(rowID=1))") == [1]
        mgr = e.mesh_manager()
        leaves = [("general", "standard", 1, True)]
        assert mgr.stage_infeasible("i", leaves, 1) is True
        # Raise the budget: the verdict flips once the memoized epoch
        # is invalidated by any write.
        mgr._config["hbm_budget_bytes"] = 10 * VIEW_BYTES
        mgr._budget_resolved = None
        f.set_bit(3, 3)
        assert mgr.stage_infeasible(
            "i", leaves, holder.index("i").max_slice() + 1) is False


class TestQuarantine:
    def test_ttl_expiry(self):
        from pilosa_tpu.parallel.plan import CompiledPlanCache

        c = CompiledPlanCache()
        c.quarantine("sigA", ttl_s=60.0, now=1000.0)
        assert c.is_quarantined("sigA", now=1030.0)
        assert c.quarantined_sigs(now=1030.0) == ["sigA"]
        assert not c.is_quarantined("sigA", now=1061.0)
        assert c.quarantined_sigs(now=1061.0) == []
        assert c.stats["quarantined"] == 1

    def test_repeated_failures_quarantine_plan(self, holder):
        seed(holder, bits=[(1, 0), (1, 1)])
        e = make_executor(holder, budget_bytes=-1, quarantine_after=2)
        host = Executor(holder, use_device=False)
        fault.arm("device.exec", error=fault.SimulatedResourceExhausted)
        # Fresh rowIDs per query (same plan SHAPE, so same signature;
        # different cache key, so the whole-query memo never answers):
        # every query still answers correctly via the host fold.
        for r in (1, 2, 3, 4):
            pql = f"Count(Bitmap(rowID={r}))"
            assert q(e, "i", pql) == q(host, "i", pql)
        mgr = e.mesh_manager()
        assert mgr.stats["plan_quarantined"] == 1
        assert len(mgr.quarantined_plans()) == 1
        assert mgr.stats["fallback_quarantined"] >= 1
        # Quarantined queries skip the device path entirely: the seam
        # stops firing once the quarantine lands.
        fired = fault.STATS["fault.device.exec"]
        assert q(e, "i", "Count(Bitmap(rowID=9))") == [0]
        assert fault.STATS["fault.device.exec"] == fired

    def test_clear_quarantine_restores_device_path(self, holder):
        seed(holder, bits=[(1, 0)])
        e = make_executor(holder, budget_bytes=-1, quarantine_after=1)
        # Enough failures to exhaust the ladder on BOTH the lone-fused
        # attempt (strikes suppressed there) and the chained retry
        # (where the strike lands): one query -> one strike ->
        # quarantined at quarantine_after=1.
        fault.arm("device.exec", error=fault.SimulatedResourceExhausted,
                  times=4)
        assert q(e, "i", "Count(Bitmap(rowID=1))") == [1]
        mgr = e.mesh_manager()
        assert len(mgr.quarantined_plans()) == 1
        assert mgr.clear_quarantine() == 1
        assert mgr.quarantined_plans() == []
        fault.reset(seed=0)  # disarm any leftover budget of the rule
        # Fresh rowID (memo can't answer): must dispatch on device.
        assert q(e, "i", "Count(Bitmap(rowID=2))") == [0]
        assert mgr.stats["count"] >= 1  # device path serving again

    def test_explain_shows_quarantine(self, holder):
        seed(holder, bits=[(1, 0)])
        e = make_executor(holder, budget_bytes=-1, quarantine_after=1)
        # Fail every ladder attempt of the first query (lone-fused
        # pass plus the chained retry) -> one strike -> quarantined.
        fault.arm("device.exec", error=fault.SimulatedResourceExhausted,
                  times=4)
        assert q(e, "i", "Count(Bitmap(rowID=1))") == [1]
        assert len(e.mesh_manager().quarantined_plans()) == 1
        # Same plan shape, fresh rowID (explain's memo peek must miss
        # so the routing branch is the one exercised).
        info = e.explain("i", parse_string("Count(Bitmap(rowID=2))"))
        call = info["calls"][0]
        assert call["plan_cache"]["quarantined"] is True
        assert call["route"] == "host-fold"
        assert call["route_reason"] == "quarantined"


class TestFaultSeams:
    def test_prob_schedule_deterministic(self):
        def run():
            fault.reset(seed=1234)
            fault.arm("device.exec", error=ValueError, prob=0.5)
            pattern = []
            for i in range(32):
                try:
                    fault.point("device.exec", sig="s", kind="count")
                    pattern.append(0)
                except ValueError:
                    pattern.append(1)
            return pattern

        first = run()
        assert first == run()
        assert 0 < sum(first) < 32  # actually probabilistic

    def test_stage_seam_carries_context(self, holder):
        seed(holder, bits=[(1, 0)])
        e = make_executor(holder, budget_bytes=-1)
        # Context match: a rule scoped to another frame must not fire.
        # (fault.STATS is process-global and survives reset(): compare
        # deltas, not absolutes.)
        fired0 = fault.STATS.get("fault.mesh.stage", 0)
        fault.arm("mesh.stage", error=fault.SimulatedResourceExhausted,
                  frame="other")
        assert q(e, "i", "Count(Bitmap(rowID=1))") == [1]
        assert e.mesh_manager().stats["oom_retries"] == 0
        assert fault.STATS.get("fault.mesh.stage", 0) == fired0


class TestDeviceMemoryConsistency:
    def test_report_fields(self, holder):
        seed(holder, bits=[(1, 0), (2, SLICE_WIDTH + 1)])
        e = make_executor(holder, budget_bytes=-1)
        assert q(e, "i", "Count(Bitmap(rowID=1))") == [1]
        mgr = e.mesh_manager()
        dm = mgr.device_memory()
        assert dm["views"] == 1
        assert dm["padded_bytes"] == mgr.stats["staged_bytes"]
        assert 0 < dm["live_bytes"] <= dm["padded_bytes"]
        assert sum(dm["per_device"].values()) == dm["padded_bytes"]

    def test_consistent_under_concurrent_staging(self, holder):
        """Regression for the torn-read bug: device_memory() read
        sv.sharded twice per view (words, then keys), so an
        incremental swap between the reads mixed two image
        generations. The generation-checked snapshot must keep
        per-device totals equal to the padded total while a writer
        restages and scatters concurrently."""
        f = seed(holder, bits=[(1, 0)])
        e = make_executor(holder, budget_bytes=-1)
        assert q(e, "i", "Count(Bitmap(rowID=1))") == [1]
        mgr = e.mesh_manager()
        stop = threading.Event()
        errors: list = []

        def churn():
            col = 1
            try:
                while not stop.is_set():
                    f.set_bit(1, col % SLICE_WIDTH)
                    col += 97
                    mgr.refresh("i", "general", "standard", 1)
                    if col % 13 == 0:
                        mgr.invalidate("i")
            except Exception as ex:  # noqa: BLE001
                errors.append(ex)

        t = threading.Thread(target=churn, daemon=True)
        t.start()
        deadline = time.monotonic() + 1.0
        samples = 0
        try:
            while time.monotonic() < deadline:
                dm = mgr.device_memory()
                assert sum(dm["per_device"].values()) == dm["padded_bytes"]
                assert dm["live_bytes"] <= dm["padded_bytes"]
                samples += 1
        finally:
            stop.set()
            t.join(timeout=10)
        assert not errors
        assert samples > 50  # the scrape never stalled behind staging


class TestConcurrentHerdUnderBudget:
    def test_zero_failures_and_bounded_residency(self, holder):
        """Acceptance: budget below the working set; a concurrent herd
        over four frames completes with zero errors, evictions keep
        the pool bounded, and the final resident bytes respect the
        budget."""
        idx = holder.create_index_if_not_exists("i")
        frames = ["f1", "f2", "f3", "f4"]
        for fr in frames:
            fo = idx.create_frame_if_not_exists(fr)
            fo.set_bit(1, 3)
            fo.set_bit(1, 9)
        budget = 2 * VIEW_BYTES  # working set is 4 views
        e = make_executor(holder, budget_bytes=budget)
        host = Executor(holder, use_device=False)
        errors: list = []
        wrong: list = []

        def worker(wid):
            try:
                for i in range(12):
                    fr = frames[(wid + i) % len(frames)]
                    # Alternate seeded and fresh rows; fresh rowIDs
                    # dodge the whole-query memo so every iteration
                    # exercises staging/eviction for real.
                    if i % 2 == 0:
                        row, want = 1, [2]
                    else:
                        row, want = 100 + wid * 100 + i, [0]
                    out = q(e, "i",
                            f"Count(Bitmap(rowID={row}, frame={fr}))")
                    if out != want:
                        wrong.append((fr, row, out))
            except Exception as ex:  # noqa: BLE001
                errors.append(ex)

        threads = [threading.Thread(target=worker, args=(w,))
                   for w in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errors
        assert not wrong
        mgr = e.mesh_manager()
        assert mgr.stats["evicted_budget"] >= 1
        assert all(sv.pins == 0 for sv in mgr._views.values())
        assert mgr.stats["staged_bytes"] <= budget
        assert q(e, "i", "Count(Bitmap(rowID=1, frame=f1))") \
            == q(host, "i", "Count(Bitmap(rowID=1, frame=f1))") == [2]
