"""The program's spans and program names, read back from a profiler trace by
the benchmark's own reader (``benchmarks/chip/spans.py``): the SpGEMM call's
``spgemm.*`` spans and GCN training's ``gnn.*`` spans, their nesting and
counts, and the ``jit_spgemm_<phase>`` module names of the executor's
programs."""
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps.gnn import GNNConfig, train_gnn
from repro.apps.graphs import uniform_graph
from repro.core import executor
from repro.core.spgemm import spgemm, spgemm_batched
from repro.sparse.formats import CSR

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "benchmarks" / "chip"))
import spans  # noqa: E402
import xplane  # noqa: E402

N = 2048
GNN = GNNConfig(arch="gcn", n_layers=2, d_in=16, d_hidden=16, n_classes=4, topk=4)


def _traced(log_dir, body):
    """Run ``body`` inside ``bench.window`` under the profiler, as the
    benchmark harness does; returns (its result, the window's spans)."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
            out = body()
    finally:
        jax.profiler.stop_trace()
    return out, spans.spans_of(str(xplane.find_xplane(log_dir)))


def _fresh_values(a: CSR, i: int) -> CSR:
    data = jax.random.uniform(jax.random.PRNGKey(i), a.data.shape, jnp.float32, 0.1, 1.1)
    return CSR(a.indptr, a.indices, data, a.shape)


def _misses() -> int:
    return executor.cache_stats()["operand_misses"]


def _train(n_steps: int):
    g = uniform_graph(256, 4.0, seed=3)
    x = np.random.default_rng(0).standard_normal((256, GNN.d_in)).astype(np.float32)
    labels = np.arange(256) % GNN.n_classes
    return train_gnn(GNN, g, x, labels, n_steps=n_steps)


@pytest.fixture(scope="module")
def pattern():
    return uniform_graph(N, 6.2, seed=0)


@pytest.fixture(scope="module")
def window(pattern, tmp_path_factory):
    """Two products of one pattern with fresh values through a PlanCache,
    then two 2-step ``train_gnn`` calls, all in one traced window (the
    programs warmed before it); returns (spans, operand_misses delta)."""
    cache = executor.PlanCache()
    spgemm(_fresh_values(pattern, 0), _fresh_values(pattern, 0), plan=cache)
    _train(1)

    def body():
        before = _misses()
        for i in (1, 2):
            a = _fresh_values(pattern, i)
            jax.block_until_ready(spgemm(a, a, plan=cache).c.data)
        delta = _misses() - before
        _train(2)
        _train(2)
        return delta

    delta, found = _traced(tmp_path_factory.mktemp("window"), body)
    return found, delta


@pytest.fixture(scope="module")
def same_b_window(pattern, tmp_path_factory):
    """Two products of one B object it has seen before, then one on the
    planned lane (``fused_hash``, a small matrix seen before): the B-side
    operands come from the OperandCache."""
    cache = executor.PlanCache()
    a = _fresh_values(pattern, 7)
    small = uniform_graph(96, 3.0, seed=5)
    spgemm(a, a, plan=cache)
    spgemm(small, small, engine="fused_hash")

    def body():
        before = _misses()
        for _ in range(2):
            jax.block_until_ready(spgemm(a, a, plan=cache).c.data)
        jax.block_until_ready(spgemm(small, small, engine="fused_hash").c.data)
        return _misses() - before

    delta, found = _traced(tmp_path_factory.mktemp("same_b"), body)
    return found, delta


NESTING = {
    "spgemm": None,
    "spgemm.plan": "spgemm",
    "spgemm.execute": "spgemm",
    "spgemm.setup": "spgemm.execute",
    "spgemm.operands": "spgemm.execute",
    "spgemm.operands.build": "spgemm.operands",
    "spgemm.dispatch": "spgemm.execute",
    "spgemm.sync": "spgemm.execute",
    "spgemm.epilogue": "spgemm.execute",
    "spgemm.info": "spgemm",
    "gnn.train": None,
    "gnn.init": "gnn.train",
    "gnn.step": "gnn.train",
    "gnn.trace": "gnn.step",
    "gnn.loss_read": "gnn.train",
}


@pytest.mark.parametrize("name", sorted(NESTING))
def test_span_nests_in_its_parent(window, name):
    found, _ = window
    of = spans.named(found, name)
    assert of, f"no {name!r} span in the window"
    assert {s.parent for s in of} == {NESTING[name]}
    assert all(0 <= s.self_ns <= s.end_ns - s.start_ns for s in of)


def test_window_holds_only_program_spans_of_the_calls(window):
    found, _ = window
    assert {s.name for s in found} == set(NESTING)
    assert len(spans.named(found, "spgemm")) == 2
    assert len(spans.named(found, "gnn.step")) == 4
    assert len(spans.named(found, "gnn.loss_read")) == 4


@pytest.mark.parametrize("case, lookups, builds", [("fresh_values", 2, 2), ("same_b", 3, 0)])
def test_operand_builds_count_operand_cache_misses(window, same_b_window, case, lookups, builds):
    found, delta = window if case == "fresh_values" else same_b_window
    assert len(spans.named(found, "spgemm.operands")) == lookups
    assert len(spans.named(found, "spgemm.operands.build")) == delta == builds


def test_planned_lane_dispatches_without_a_sync(same_b_window):
    found, _ = same_b_window
    assert len(spans.named(found, "spgemm")) == 3
    assert len(spans.named(found, "spgemm.sync")) == 2  # the measured lane's calls
    for name in ("spgemm.dispatch", "spgemm.epilogue"):
        assert [s.parent for s in spans.named(found, name)] == ["spgemm.execute"] * 3


def test_one_step_trace_per_train_gnn_call(window):
    found, _ = window
    calls = spans.named(found, "gnn.train")
    assert len(calls) == 2
    for call in calls:
        inside = [s for s in found if call.start_ns <= s.start_ns and s.end_ns <= call.end_ns]
        (trace,) = spans.named(inside, "gnn.trace")
        first_step = spans.named(inside, "gnn.step")[0]
        assert first_step.start_ns <= trace.start_ns and trace.end_ns <= first_step.end_ns


# ---------------------------------------------------------------------------
# Program names
# ---------------------------------------------------------------------------

PHASES = ("enumerate", "allocate", "accumulate", "fused", "benumerate", "baccumulate", "bfused")
EPILOGUES = {
    "scatter": "reassemble_device",
    "bscatter": "reassemble_device_batched",
    "segment": "reassemble_segment",
    "bsegment": "reassemble_segment_batched",
    "merge": "merge_segments",
    "bmerge": "merge_segments_batched",
}


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


# The sharded epilogue runs only across devices: lowered from shapes.
SHARDED_EPILOGUE_ARGS = {
    "segment": (_i32(8), _f32(8), _i32(8), _i32(), _i32(2, 4), _f32(2, 4), _i32(2), _i32(2)),
    "bsegment": (_i32(8), _f32(3, 8), _i32(8), _i32(), _i32(2, 4), _f32(3, 2, 4), _i32(2), _i32(2)),
    "merge": (_i32(16), _f32(16), _i32(8), _f32(8), _i32(8)),
    "bmerge": (_i32(16), _f32(3, 16), _i32(8), _f32(3, 8), _i32(8)),
}


def _module_name(text: str) -> str:
    return re.match(r"module @(\S+)", text).group(1)


@pytest.fixture(scope="module")
def lowered_names():
    """{kind: module names} of the programs one device runs: the measured
    and planned lanes, single and batched."""
    a = uniform_graph(96, 3.0, seed=5)
    b = _fresh_values(a, 1)
    kinds = PHASES + ("scatter", "bscatter")
    with executor.record_lowerings(kinds) as texts:
        for engine in ("sort", "fused_hash"):
            spgemm(a, b, engine=engine)
            spgemm_batched([a, _fresh_values(a, 2)], b, engine=engine)
    names = {k: {_module_name(t) for t in v} for k, v in texts.items()}
    for kind, args in SHARDED_EPILOGUE_ARGS.items():
        prog = executor._BUILDERS[kind]()
        names[kind] = {_module_name(prog.lower(*args).as_text())}
    return names


def test_every_builder_is_checked():
    assert set(PHASES) | set(EPILOGUES) == set(executor._BUILDERS)


@pytest.mark.parametrize("kind", PHASES)
def test_phase_program_named_by_phase_and_table_capacity(lowered_names, kind):
    suffix = "" if kind.endswith("enumerate") else r"_t\d+"
    names = lowered_names[kind]
    assert names
    assert all(re.fullmatch(rf"jit_spgemm_{kind}{suffix}", n) for n in names), names


@pytest.mark.parametrize("kind", sorted(EPILOGUES))
def test_epilogue_program_keeps_its_function_name(lowered_names, kind):
    assert lowered_names[kind] == {f"jit_{EPILOGUES[kind]}"}
