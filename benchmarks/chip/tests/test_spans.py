"""The program spans of a traced window and the per-layer metrics that read
them, against a hand-built trace with known spans and device gaps, against
a trace without program spans (what a program older than the spans
writes), and against a small trace recorded on a TPU v5e.

    python -m pytest benchmarks/chip/tests
"""

import shutil
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import spans  # noqa: E402
import workload  # noqa: E402
import xplane  # noqa: E402
from test_xplane import _events, _metadata  # noqa: E402

READERS = (
    "accumulate_s.product",
    "operand_builds.product",
    "plan_host_s.product",
    "step_traces.train",
    "step_dispatch_s.train",
)

# Host (us): the window [1, 101); spgemm [10, 60) holding spgemm.plan
# [12, 20) and spgemm.execute [20, 55), which holds spgemm.operands [22, 30)
# around spgemm.operands.build [23, 29) and JAX's dispatch [31, 35) (not a
# program span); bench.block [60, 70); gnn.train [78, 96) around gnn.step
# [80, 90) around gnn.trace [81, 86); a spgemm [102, 107) past the window.
# Device: ops [0, 2) and [25, 40) in jit_spgemm_enumerate, [50, 75) in
# jit_spgemm_accumulate_t64, [85, 95) in jit_step; gaps in the window
# [2, 25), [40, 50), [75, 85), [95, 101).
SYNTHETIC = f"""
planes {{
  id: 1 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
    {_events((1, 1, 100), (2, 10, 50), (3, 12, 8), (4, 20, 35), (5, 22, 8), (6, 23, 6),
             (7, 31, 4), (8, 60, 10), (9, 78, 18), (10, 80, 10), (11, 81, 5), (2, 102, 5))} }}
  {_metadata(["bench.window", "spgemm", "spgemm.plan", "spgemm.execute", "spgemm.operands",
              "spgemm.operands.build", "PjitFunction(spgemm_enumerate)", "bench.block",
              "gnn.train", "gnn.step", "gnn.trace"])}
}}
planes {{
  id: 2 name: "/device:TPU:0"
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
    {_events((1, 0, 2), (2, 25, 15), (3, 50, 25), (4, 85, 10))} }}
  lines {{ id: 3 name: "XLA Modules" timestamp_ns: 0
    {_events((5, 0, 40), (6, 50, 25), (7, 85, 10))} }}
  {_metadata(["copy.0", "fusion.1 = s32[8]{0} fusion()", "fusion.2 = f32[8]{0} fusion()",
              "fusion.3 = f32[4]{0} fusion()", "jit_spgemm_enumerate(1)",
              "jit_spgemm_accumulate_t64(2)", "jit_step(3)"])}
}}
"""
US = 1e-6


@pytest.fixture(scope="module")
def synthetic():
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(SYNTHETIC)


def _by_name(found):
    return {s.name: s for s in found}


def test_spans_in_the_window_only_with_parents(synthetic):
    found = spans.program_spans(synthetic)
    assert [s.name for s in found] == [
        "spgemm",
        "spgemm.plan",
        "spgemm.execute",
        "spgemm.operands",
        "spgemm.operands.build",
        "gnn.train",
        "gnn.step",
        "gnn.trace",
    ]
    parents = {s.name: s.parent for s in found}
    assert parents == {
        "spgemm": None,
        "spgemm.plan": "spgemm",
        "spgemm.execute": "spgemm",
        "spgemm.operands": "spgemm.execute",
        "spgemm.operands.build": "spgemm.operands",
        "gnn.train": None,
        "gnn.step": "gnn.train",
        "gnn.trace": "gnn.step",
    }


def test_self_time_leaves_out_child_program_spans_only(synthetic):
    by = _by_name(spans.program_spans(synthetic))
    assert by["spgemm"].seconds == pytest.approx(50 * US)
    assert by["spgemm"].self_s == pytest.approx(7 * US)
    # JAX's dispatch event inside it is not a program span
    assert by["spgemm.execute"].self_s == pytest.approx(27 * US)
    assert by["spgemm.operands.build"].self_s == pytest.approx(6 * US)
    assert by["gnn.train"].self_s == pytest.approx(8 * US)


def test_device_idle_under_the_innermost_program_span(synthetic):
    idle = spans.idle_by_span(synthetic)
    assert idle == pytest.approx(
        {
            "spgemm": 2 * US,
            "spgemm.plan": 8 * US,
            "spgemm.execute": 12 * US,
            "spgemm.operands": 1 * US,
            "spgemm.operands.build": 2 * US,
            "gnn.train": 3 * US,
            "gnn.step": 1 * US,
            "gnn.trace": 4 * US,
            spans.OUTSIDE: 16 * US,
        }
    )
    summary = xplane.reduce_profile(synthetic)
    assert sum(idle.values()) == pytest.approx(summary.window_s - summary.busy_s)


def _read(name, items, summary):
    return workload.load("metrics", name).read({"items": items, "trace": summary})


def test_readers_on_the_synthetic_trace(synthetic, monkeypatch):
    monkeypatch.setattr(spans, "window_spans", lambda: spans.program_spans(synthetic))
    summary = xplane.reduce_profile(synthetic)
    got = {name: _read(name, 2, summary) for name in READERS}
    assert got == pytest.approx(
        {
            "accumulate_s.product": 25 * US / 2,
            "operand_builds.product": 0.5,
            "plan_host_s.product": 8 * US / 2,
            "step_traces.train": 1,
            "step_dispatch_s.train": 10 * US / 2,
        }
    )


def _traced_dir(tmp_path, recorded: Path, monkeypatch):
    """Lay one recorded trace out as the harness's trace directory."""
    profile_dir = tmp_path / "plugins" / "profile" / "run"
    profile_dir.mkdir(parents=True)
    shutil.copy(recorded, profile_dir / "host.xplane.pb")
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path)
    return xplane.reduce_file(recorded)


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_in_a_trace_without_program_spans(name, tmp_path, monkeypatch):
    summary = _traced_dir(tmp_path, HERE / "data" / "v5e_small.xplane.pb", monkeypatch)
    assert _read(name, 3, summary) is None


# Recorded on a TPU v5e by record_spans_trace.py: two products of a
# 4,096-row Economics-shaped pattern (fresh values, one PlanCache), then one
# 2-step train_gnn call.  Raw spans (ns on the trace's clock): spgemm.plan
# 604,650 and 557,600 (plan-cache hits); one spgemm.operands.build in each
# product (fresh values miss the OperandCache); gnn.step 231,764,274 (it
# holds the one gnn.trace, 37,552,607, and the step's compile) and 819,130.
# Device seconds of the Table-I programs: jit_spgemm_accumulate_t1024
# 0.093110445, _t64 0.027577103, jit_spgemm_allocate_t1024 0.000671752,
# _t64 0.0001492.
RECORDED = HERE / "data" / "v5e_spans.xplane.pb"
RECORDED_READINGS = {
    "accumulate_s.product": (0.093110445 + 0.027577103 + 0.000671752 + 0.0001492) / 2,
    "operand_builds.product": 1.0,
    "plan_host_s.product": (604_650 + 557_600) * 1e-9 / 2,
    "step_traces.train": 1,
    "step_dispatch_s.train": (231_764_274 + 819_130) * 1e-9 / 2,
}


def test_recorded_spans_nest_as_the_program_writes_them():
    found = spans.spans_of(str(RECORDED))
    assert [s.name for s in found if s.parent is None] == ["spgemm", "spgemm", "gnn.train"]
    assert {s.name: s.parent for s in found} == {
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


@pytest.mark.parametrize("name", READERS)
def test_reader_on_the_recorded_trace(name, tmp_path, monkeypatch):
    summary = _traced_dir(tmp_path, RECORDED, monkeypatch)
    assert _read(name, 2, summary) == pytest.approx(RECORDED_READINGS[name], rel=1e-6)


def test_recorded_executor_programs_carry_their_phase_names():
    programs = {op.split("/", 1)[0] for op in xplane.reduce_file(RECORDED).ops}
    assert {"jit_spgemm_enumerate", "jit_spgemm_accumulate_t1024", "jit_spgemm_allocate_t64"} <= programs
    assert not programs & {"jit_program", "jit__lambda"}
