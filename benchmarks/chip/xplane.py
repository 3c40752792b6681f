"""Reduce a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device busy time, device time per operation, and the idle
gaps of the device, each named by what the host was doing in it.

Only the span ``bench.window`` that the harness opens around its measured
window counts.  A device is a plane named ``/device:TPU:<n>``; its
operations are the events of its ``XLA Ops`` line, each named by its program
(the ``XLA Modules`` event around it), its HLO instruction and its type.  The host line that
holds the window span is the harness's own thread, and a gap is named by
the innermost event of that line open at the gap's middle: a harness span
(``bench.*``) or one of JAX's own (tracing, dispatch, a compile-cache load).
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from pathlib import Path

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


@dataclasses.dataclass
class Summary:
    """One traced window, reduced.  Seconds throughout; ``busy_s`` and the
    per-op times are averaged over the devices traced."""

    window_s: float
    busy_s: float
    n_devices: int
    ops: dict  # op name -> device seconds
    op_instr: dict  # op name -> its HLO instruction's name
    op_text: dict  # op name -> the strings its events carry (name, stats)
    gaps: list  # [(host span, seconds)], longest first
    idle_by_span: dict  # host span -> idle device seconds in all its gaps

    def kernel_s(self, pattern: str) -> float:
        """Device seconds of the operations whose HLO instruction name
        starts with a match of ``pattern`` (a regular expression).  The
        operands are not read: an op that consumes a kernel's output is
        not the kernel."""
        rx = re.compile(pattern)
        return sum(s for name, s in self.ops.items() if rx.match(self.op_instr[name]))

    def top_ops(self, n: int = TOP):
        return sorted(self.ops.items(), key=lambda kv: -kv[1])[:n]


def find_xplane(log_dir) -> Path:
    """The one ``.xplane.pb`` file a trace wrote under ``log_dir``."""
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, found {found}")
    return found[0]


def _union(intervals):
    """Merged, sorted, disjoint intervals of ``[(start, end)]``."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _window_line(profile):
    """(host events of the harness's thread, window start, window end), ns."""
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = [(e.start_ns, e.end_ns, e.name) for e in line.events]
            for s, e, name in events:
                if name == WINDOW_SPAN:
                    return events, s, e
    raise ValueError(f"no host span {WINDOW_SPAN!r} in the trace")


def _namer(host_events):
    """A function naming a point in time by the innermost open host event."""
    host_events = sorted(host_events)
    starts = [s for s, _, _ in host_events]

    def name_at(t):
        i = bisect.bisect_right(starts, t) - 1
        for j in range(i, max(i - 5000, -1), -1):
            s, e, name = host_events[j]
            if e >= t and name != WINDOW_SPAN:
                return name
        return "idle host"

    return name_at


def _op_key(hlo: str, module: str) -> tuple[str, str]:
    """(``program/instruction type``, instruction) from an op event's HLO
    text, e.g. (``jit_program/aia_gather_rows.2 s32[86016,128]``,
    ``aia_gather_rows.2``)."""
    instr, _, rest = hlo.partition(" = ")
    instr = instr.lstrip("%")
    key = f"{module}/{instr}"
    shape = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rest)
    return (f"{key} {shape.group(1)}" if shape else key), instr


def _module_at(modules):
    """A function naming the program running at a time on one device."""
    modules = sorted(modules)
    starts = [s for s, _, _ in modules]

    def module_at(t):
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and modules[i][1] >= t:
            return re.sub(r"\(\d+\)$", "", modules[i][2])
        return "?"

    return module_at


def _event_text(event) -> str:
    parts = [event.name]
    for _, value in event.stats:
        if isinstance(value, str):
            parts.append(value)
    return " ".join(parts)


def reduce_profile(profile) -> Summary:
    """Reduce a ``jax.profiler.ProfileData`` to a ``Summary``."""
    host_events, w0, w1 = _window_line(profile)
    window_ns = w1 - w0
    name_at = _namer(host_events)
    busy_ns = 0.0
    ops, op_instr, op_text = {}, {}, {}
    gaps, idle_by_span = [], {}
    n_dev = 0
    for plane in profile.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = [ln for ln in plane.lines if ln.name == OPS_LINE]
        if not lines:
            continue
        n_dev += 1
        module_at = _module_at(
            (ev.start_ns, ev.end_ns, ev.name)
            for ln in plane.lines
            if ln.name == MODULES_LINE
            for ev in ln.events
        )
        spans = []
        for line in lines:
            for ev in line.events:
                s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
                if e <= s:
                    continue
                spans.append((s, e))
                key, instr = _op_key(ev.name, module_at(ev.start_ns))
                ops[key] = ops.get(key, 0.0) + (e - s) * 1e-9
                if key not in op_text:
                    op_instr[key] = instr
                    op_text[key] = _event_text(ev)
        merged = _union(spans)
        busy_ns += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                span = name_at((g0 + g1) / 2)
                gaps.append((span, (g1 - g0) * 1e-9))
                idle_by_span[span] = idle_by_span.get(span, 0.0) + (g1 - g0) * 1e-9
    if n_dev == 0:
        raise ValueError(f"no device plane with an {OPS_LINE!r} line in the trace")
    ops = {k: v / n_dev for k, v in ops.items()}
    idle_by_span = {k: v / n_dev for k, v in idle_by_span.items()}
    gaps.sort(key=lambda g: -g[1])
    return Summary(
        window_s=window_ns * 1e-9,
        busy_s=busy_ns * 1e-9 / n_dev,
        n_devices=n_dev,
        ops=ops,
        op_instr=op_instr,
        op_text=op_text,
        gaps=gaps[:TOP],
        idle_by_span=idle_by_span,
    )


def reduce_file(path) -> Summary:
    """Read and reduce one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(str(path)))


def describe(profile, limit: int = 12) -> list[str]:
    """Plane and line names with a few event names each: what a reader
    needs to see before trusting the reduction on a new chip or release."""
    out = []
    for plane in profile.planes:
        out.append(f"plane {plane.name}")
        for line in plane.lines:
            names = []
            for ev in line.events:
                if ev.name not in names:
                    names.append(ev.name)
                if len(names) >= limit:
                    break
            out.append(f"  line {line.name!r}: {names}")
    return out


def main(argv=None) -> int:
    """Print a trace's planes and lines, then its reduction: the top device
    operations with the strings they carry, the longest gaps, and the idle
    time by host span.  ``python3 benchmarks/chip/xplane.py <trace dir>``."""
    import sys

    from jax.profiler import ProfileData

    log_dir = (argv or sys.argv[1:])[0]
    profile = ProfileData.from_file(str(find_xplane(log_dir)))
    for line in describe(profile):
        print(line)
    s = reduce_profile(profile)
    print(f"window_s={s.window_s!r} busy_s={s.busy_s!r} devices={s.n_devices}")
    for name, seconds in s.top_ops(25):
        print(f"op {name!r} {seconds!r} | {s.op_text[name][:400]}")
    for span, seconds in s.gaps:
        print(f"gap {span!r} {seconds!r}")
    for span, seconds in sorted(s.idle_by_span.items(), key=lambda kv: -kv[1])[:15]:
        print(f"idle {span!r} {seconds!r}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
