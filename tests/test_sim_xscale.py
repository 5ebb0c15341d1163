"""Scale-PR seams: the zero-progress stall guard, the memoized
per-component min_dt counters, the per-phase timing shares, the
`compare_engine_variants` harness, the engine's timed-queue/solver
parameter validation, and the optional jax.jit water-fill solver
(bitwise against the numpy round loop)."""
import numpy as np
import pytest

from repro.sim import (Fabric, SOLVERS, SimulationStalled, TIMED_QUEUES,
                       compare_engine_variants,
                       lovelock_cluster, phase_shares,
                       pipelined_shuffle_waves, shuffle)
from repro.sim.alloc import (ArrayCore, vector_water_fill,
                             vector_water_fill_jit)


def _topo(n=8):
    return lovelock_cluster(n, 1, accel_rate=1.0,
                            fabric=Fabric(rack_size=4))


# ---------------------------------------------------------------------------
# stall guard
# ---------------------------------------------------------------------------


def test_stalled_simulation_raises_with_diagnostics(monkeypatch):
    """A core whose min_dt is pinned at 0.0 while nothing completes and
    no timed event fires must raise `SimulationStalled` (with the stuck
    clock and running set) instead of spinning forever."""
    monkeypatch.setattr(ArrayCore, "min_dt", lambda self: 0.0)
    topo = _topo()
    eng = topo.engine(backend="array")
    with pytest.raises(SimulationStalled) as ei:
        eng.run(shuffle(topo, cpu_work_per_node=0.5, bytes_per_node=2.0))
    err = ei.value
    assert err.now == 0.0
    assert err.running                       # the stuck tasks are named
    assert "no progress" in str(err)
    assert any(tid in str(err) for tid in err.running)


def test_zero_width_progress_does_not_trip_the_guard():
    """Dense same-timestamp completions legitimately produce dt == 0.0
    steps *with* progress; a normal run must never trip the guard."""
    topo = _topo()
    res = topo.engine(backend="array").run(
        shuffle(topo, cpu_work_per_node=0.5, bytes_per_node=2.0))
    assert res.complete


# ---------------------------------------------------------------------------
# engine parameter validation
# ---------------------------------------------------------------------------


def test_engine_rejects_unknown_queue_and_solver():
    topo = _topo()
    with pytest.raises(ValueError):
        topo.engine(timed_queue="splay")
    with pytest.raises(ValueError):
        topo.engine(solver="fortran")
    with pytest.raises(ValueError):
        # the jit solver is an array-core feature
        topo.engine(backend="legacy", solver="jit")
    assert set(TIMED_QUEUES) == {"calendar", "heap"}
    assert set(SOLVERS) == {"numpy", "jit"}


# ---------------------------------------------------------------------------
# memoized min_dt + phase counters
# ---------------------------------------------------------------------------


def _waves(topo):
    return pipelined_shuffle_waves(topo, waves=3, tasks_per_node=2,
                                   jitter=0.35, seed=7)


def test_memoized_min_dt_skips_clean_components():
    """On the pipelined-waves workload most components are clean at any
    given step: the memo must actually skip them (skips >> 0) while the
    trace stays identical to the from-scratch legacy core (covered by
    test_sim_incremental); here we pin the counters exist and count."""
    topo = _topo(16)
    res = topo.engine(backend="array").run(_waves(topo))
    assert res.complete
    st = res.alloc_stats
    assert st["mindt_evals"] > 0
    assert st["mindt_skips"] > 0
    for key in ("t_solve_s", "t_min_dt_s", "t_advance_s", "t_events_s"):
        assert st[key] >= 0.0


def test_phase_shares_accounts_the_wall():
    topo = _topo()
    import time
    t0 = time.perf_counter()
    res = topo.engine(backend="array").run(
        shuffle(topo, cpu_work_per_node=0.5, bytes_per_node=2.0))
    wall = time.perf_counter() - t0
    shares = phase_shares(res.alloc_stats, wall)
    assert set(shares) == {"solve", "min_dt", "advance", "events",
                           "other"}
    total = sum(v["share"] for v in shares.values())
    assert total == pytest.approx(1.0, abs=0.02)
    assert all(v["seconds"] >= 0.0 for v in shares.values())


def test_legacy_core_reports_phase_counters_too():
    topo = _topo()
    res = topo.engine(backend="legacy").run(
        shuffle(topo, cpu_work_per_node=0.5, bytes_per_node=2.0))
    st = res.alloc_stats
    for key in ("t_solve_s", "t_min_dt_s", "t_advance_s", "t_events_s"):
        assert st[key] >= 0.0
    assert st["timed_queue"] == "calendar"


# ---------------------------------------------------------------------------
# compare_engine_variants harness
# ---------------------------------------------------------------------------


def test_compare_engine_variants_matrix():
    """The harness the engine_xscale bench cell runs: heap reference vs
    calendar (+ jit when available) with deferred submissions and a
    failure injected through ``prepare`` — all bit-identical, each with
    events/sec and phase shares."""
    def make_topo():
        return _topo(8)

    def build(topo):
        return list(_waves(topo))

    def prepare(eng, topo):
        eng.inject_failure("nic2", at=0.5, recover_at=1.0)
        eng.submit(shuffle(topo, cpu_work_per_node=0.2,
                           bytes_per_node=1.0, tag="late"), at=0.7)

    variants = {"heap": dict(backend="array", timed_queue="heap"),
                "calendar": dict(backend="array",
                                 timed_queue="calendar"),
                "jit": dict(backend="array", timed_queue="calendar",
                            solver="jit")}
    cmp = compare_engine_variants(make_topo, build, variants,
                                  repeats=2, prepare=prepare)
    for name in variants:
        if name != "heap":
            assert cmp["bit_identical"][name] is True
            assert cmp["speedup"][name] > 0.0
        assert cmp[name]["events_per_sec"] > 0.0
        assert cmp[name]["n_events"] == cmp["heap"]["n_events"]
        assert "solve" in cmp[name]["phases"]
    assert cmp["results"]["heap"].complete
    with pytest.raises(ValueError):
        compare_engine_variants(make_topo, build, {})


# ---------------------------------------------------------------------------
# jax.jit water-fill solver
# ---------------------------------------------------------------------------


def _random_instance(rng, nf, nres):
    """A random CSR flow->resource incidence + capacities, shaped like
    one solve of a connected component."""
    indptr = [0]
    indices = []
    for _ in range(nf):
        k = rng.integers(1, min(4, nres) + 1)
        cols = rng.choice(nres, size=k, replace=False)
        indices.extend(int(c) for c in cols)
        indptr.append(len(indices))
    cap = rng.uniform(0.1, 5.0, size=nres)
    return (np.asarray(indptr, dtype=np.int64),
            np.asarray(indices, dtype=np.int64),
            np.asarray(cap, dtype=np.float64))


@pytest.mark.parametrize("seed", range(5))
def test_jit_water_fill_bitwise_matches_numpy(seed):
    rng = np.random.default_rng(seed)
    for _ in range(4):
        nf = int(rng.integers(1, 96))
        nres = int(rng.integers(1, 24))
        indptr, indices, cap = _random_instance(rng, nf, nres)
        a = vector_water_fill(indptr, indices, cap.copy())
        b = vector_water_fill_jit(indptr, indices, cap.copy())
        # bitwise, not approx: the jit kernel replays the numpy float
        # op sequence exactly
        np.testing.assert_array_equal(a, b)


def test_jit_water_fill_empty_and_fallback():
    empty = vector_water_fill_jit(np.zeros(1, dtype=np.int64),
                                  np.zeros(0, dtype=np.int64),
                                  np.zeros(0, dtype=np.float64))
    assert empty.size == 0


def test_jit_solver_engine_trace_matches_numpy_solver():
    results = {}
    for solver in SOLVERS:
        topo = _topo(16)
        res = topo.engine(backend="array", solver=solver).run(
            _waves(topo))
        assert res.complete
        assert res.alloc_stats["solver"] == solver
        results[solver] = res
    assert results["jit"].events == results["numpy"].events
    assert results["jit"].finish_times == results["numpy"].finish_times
