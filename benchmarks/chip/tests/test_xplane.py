"""The trace reduction against a hand-built trace with known busy, idle and
per-op times, and against a small trace recorded on a TPU v5e.

    python -m pytest benchmarks/chip/tests
"""

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import xplane  # noqa: E402

US = 1_000_000  # picoseconds per microsecond
AIA_OP = (
    "%aia_gather_rows.1 = f32[64,128]{1,0:T(8,128)} custom-call(s32[64]{0} %i, f32[8,128]{1,0} %x),"
    ' custom_call_target=\\"tpu_custom_call\\"'
)
SLICE_OP = (
    "%slice.3 = f32[8,128]{1,0} slice(f32[64,128]{1,0} %aia_gather_rows.1),"
    " slice={[0:8], [0:128]}"
)
FUSION_OP = "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p)"


def _events(*events):
    out = []
    for meta, start_us, dur_us, *stat in events:
        s = f" stats {{ metadata_id: 9 str_value: {stat[0]!r} }}" if stat else ""
        out.append(
            f"events {{ metadata_id: {meta} offset_ps: {start_us * US}"
            f" duration_ps: {dur_us * US}{s} }}"
        )
    return "\n".join(out)


def _metadata(names):
    return "\n".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
        for i, n in enumerate(names, 1)
    )


# Host: the window [1, 101) us; bench.product [11, 45) with JAX's dispatch
# [30, 35) inside it; bench.block [40, 95).  Device: an op straddling the
# window's start [0, 2), the AIA kernel [6, 16), two overlapping fusions
# [50, 70) and [60, 80), and a slice of the kernel's output [55, 65) under
# them.  Busy: 1 + 10 + 30 = 41 us; gaps [2, 6) (host in no span), [16, 50)
# (middle 33: the dispatch) and [80, 101) (middle 90.5: bench.block).
SYNTHETIC = f"""
planes {{
  id: 1 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
    {_events((1, 1, 100), (2, 11, 34), (3, 30, 5), (4, 40, 55))} }}
  {_metadata(["bench.window", "bench.product", "PjitFunction(program)", "bench.block"])}
}}
planes {{
  id: 2 name: "/device:TPU:0"
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
    {_events((1, 0, 2), (2, 6, 10, "tpu_custom_call"), (3, 50, 20), (3, 60, 20), (5, 55, 10))} }}
  lines {{ id: 3 name: "XLA Modules" timestamp_ns: 0 {_events((4, 0, 100))} }}
  {_metadata(["copy.0", AIA_OP, FUSION_OP, "jit_program(42)", SLICE_OP])}
  stat_metadata {{ key: 9 value {{ id: 9 name: "long_name" }} }}
}}
"""


@pytest.fixture(scope="module")
def synthetic():
    from jax.profiler import ProfileData

    return xplane.reduce_profile(ProfileData.from_text_proto(SYNTHETIC))


def test_busy_and_window(synthetic):
    assert synthetic.window_s == pytest.approx(100e-6)
    assert synthetic.busy_s == pytest.approx(41e-6)
    assert synthetic.n_devices == 1


def test_per_op_times_clip_to_the_window(synthetic):
    assert synthetic.ops == pytest.approx(
        {
            "jit_program/copy.0": 1e-6,
            "jit_program/aia_gather_rows.1 f32[64,128]": 10e-6,
            "jit_program/fusion.2 f32[8]": 40e-6,
            "jit_program/slice.3 f32[8,128]": 10e-6,
        }
    )
    assert synthetic.top_ops(1) == [("jit_program/fusion.2 f32[8]", pytest.approx(40e-6))]


def test_kernel_found_by_its_instruction_name_not_its_consumers(synthetic):
    # the slice reads the kernel's output: its text names the kernel, its
    # instruction does not
    assert synthetic.kernel_s(r"aia_gather_rows") == pytest.approx(10e-6)
    assert synthetic.kernel_s(r"aia_gather_rows|slice") == pytest.approx(20e-6)
    assert synthetic.kernel_s(r"tpu_custom_call") == 0
    assert synthetic.kernel_s(r"hash_accumulate") == 0


def test_gaps_named_by_the_innermost_host_event(synthetic):
    assert synthetic.gaps == [
        ("PjitFunction(program)", pytest.approx(34e-6)),
        ("bench.block", pytest.approx(21e-6)),
        ("idle host", pytest.approx(4e-6)),
    ]
    assert sum(synthetic.idle_by_span.values()) == pytest.approx(59e-6)


def test_trace_without_window_is_refused():
    from jax.profiler import ProfileData

    text = SYNTHETIC.replace('"bench.window"', '"other"')
    with pytest.raises(ValueError, match="bench.window"):
        xplane.reduce_profile(ProfileData.from_text_proto(text))


# Recorded on a TPU v5e by record_trace.py.  Raw events (ns on the trace's
# clock): window [41652999, 117304098); device: the matmul's copy-start
# [61042078, 61042091), copy-done [61042092, 61064634), fusion
# [61064636, 61155976), then aia_gather_rows [112943794, 115527530).  The
# device clock runs about 1.06 ms ahead of the host's: the host dispatched
# the matmul at 62098819 and the gather at 114012368.
RECORDED = HERE / "data" / "v5e_small.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    return xplane.reduce_file(RECORDED)


def test_recorded_busy_and_window(recorded):
    assert recorded.window_s == pytest.approx(75_651_099e-9, abs=1e-12)
    assert recorded.busy_s == pytest.approx((13 + 22_542 + 91_340 + 2_583_736) * 1e-9, abs=1e-12)
    assert recorded.n_devices == 1


def test_recorded_ops_by_program_instruction_and_type(recorded):
    assert recorded.ops == pytest.approx(
        {
            "jit__lambda/copy-start f32[2048,2048]": 13e-9,
            "jit__lambda/copy-done f32[2048,2048]": 22_542e-9,
            "jit__lambda/fusion f32[2048,2048]": 91_340e-9,
            "jit__gather_rows/aia_gather_rows.1 f32[8192,128]": 2_583_736e-9,
        },
        abs=1e-12,
    )
    assert recorded.kernel_s(r"aia_gather_rows") == pytest.approx(2_583_736e-9, abs=1e-12)


def test_recorded_gaps_named_by_host_span(recorded):
    names = [name for name, _ in recorded.gaps[:3]]
    assert names == ["bench.sleep", "bench.lead", "idle host"]
    assert recorded.gaps[0][1] == pytest.approx(51_787_818e-9, abs=1e-12)
    assert recorded.gaps[1][1] == pytest.approx(19_389_079e-9, abs=1e-12)
