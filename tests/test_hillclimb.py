"""hillclimb.py's offline measurement loop behind ``engine="auto"``.

``measure_bin_engines`` with an injected ``measure`` stub: full candidate
coverage of every non-empty bin, cache convergence identical to the
executor's incremental in-band rounds, argmin assignment, and the swept
cache serving ``engine="auto"`` as pure hits — all without timing a single
real kernel."""
import numpy as np
import pytest

from benchmarks.hillclimb import measure_bin_engines
from repro.core import executor
from repro.core.grouping import group_rows
from repro.sparse.formats import csr_from_dense


def int_sparse(rng, n, m, density=0.3):
    x = rng.integers(-4, 5, (n, m)).astype(np.float32)
    mask = rng.random((n, m)) < density
    return np.where(mask, x, 0.0).astype(np.float32)


@pytest.fixture()
def fixture():
    """Operands spanning three Table-I groups (single-nnz rows → group 0,
    0.25-density rows → group 1, full rows → group 2)."""
    rng = np.random.default_rng(2)
    xa = np.zeros((64, 48), np.float32)
    for i in range(24):
        xa[i, rng.integers(0, 48)] = float(rng.integers(1, 5))
    xa[24:48] = int_sparse(rng, 24, 48, 0.25)
    xa[48:] = rng.integers(1, 5, (16, 48)).astype(np.float32)
    a = csr_from_dense(xa)
    b = csr_from_dense(int_sparse(rng, 48, 52, 0.25))
    plan = group_rows(a, b)
    assert sum(s > 0 for s in plan.group_sizes) >= 3, plan.group_sizes
    return a, b, plan


def test_sweep_covers_every_populated_bin_and_engine(fixture):
    a, b, plan = fixture
    calls = []
    cache = executor.AutotuneCache()
    record = measure_bin_engines(
        a, b, plan=plan, cache=cache,
        measure=lambda g, e: calls.append((g, e)) or 100.0)
    populated = [g for g in range(4) if plan.group_sizes[g] > 0]
    expected = {(g, e) for g in populated
                for e in executor.available_engines()}
    assert set(calls) == expected and len(calls) == len(expected)
    assert record["group_sizes"] == list(plan.group_sizes)
    assert record["converged"]
    for g in populated:
        assert set(record["timings_us"][str(g)]) == \
            set(executor.available_engines())


def test_sweep_converges_cache_to_argmin(fixture):
    """Recording every candidate converges the entry exactly as the
    in-band rounds would, picking the per-bin argmin."""
    a, b, plan = fixture
    cache = executor.AutotuneCache()
    names = executor.available_engines()
    winner = {g: names[g % len(names)] for g in range(4)}
    record = measure_bin_engines(
        a, b, plan=plan, cache=cache,
        measure=lambda g, e: 10.0 if e == winner[g] else 100.0)
    key = executor.autotune_key(a, b, plan)
    assert cache.converged(key)
    seed = executor.static_bin_engines(plan.table_capacities)
    for g in range(4):
        expect = winner[g] if plan.group_sizes[g] > 0 else seed[g]
        assert record["assignment"][g] == expect


def test_swept_cache_serves_auto_as_pure_hits(fixture):
    """The sweep's whole point: engine="auto" against a swept cache never
    measures in-band — first call included."""
    from repro.core.spgemm import spgemm
    from repro.core.ref import spgemm_dense
    from repro.sparse.formats import csr_to_dense

    a, b, plan = fixture
    cache = executor.AutotuneCache()
    measured = []
    measure_bin_engines(a, b, plan=plan, cache=cache,
                        measure=lambda g, e: measured.append((g, e)) or 50.0)
    n_swept = len(measured)
    assert cache.stats()["hits"] == 0
    res = spgemm(a, b, engine="auto", plan=plan, autotune=cache)
    assert cache.stats()["hits"] == 1 and cache.stats()["misses"] == 0
    assert len(measured) == n_swept, "auto re-measured after a full sweep"
    np.testing.assert_array_equal(
        np.asarray(csr_to_dense(res.c)), np.asarray(spgemm_dense(a, b)))


def test_sweep_restricted_engine_list(fixture):
    a, b, plan = fixture
    calls = []
    record = measure_bin_engines(
        a, b, plan=plan, engines=("sort",),
        cache=executor.AutotuneCache(candidates=("sort",)),
        measure=lambda g, e: calls.append(e) or 75.0)
    assert set(calls) == {"sort"}
    assert record["converged"]
    assert all(e == "sort" for g, e in enumerate(record["assignment"])
               if plan.group_sizes[g] > 0)


def test_sweep_defaults_plan_and_module_cache():
    """plan=None derives group_rows(a, b); cache=None folds into the
    executor module cache (the one engine="auto" reads by default)."""
    rng = np.random.default_rng(5)
    a = csr_from_dense(int_sparse(rng, 20, 16, 0.3))
    executor.clear_program_cache()  # reset the module autotune cache
    record = measure_bin_engines(a, a, measure=lambda g, e: 60.0)
    plan = group_rows(a, a)
    assert record["group_sizes"] == list(plan.group_sizes)
    key = executor.autotune_key(a, a, plan)
    assert executor.default_autotune_cache().converged(key)
    executor.clear_program_cache()
