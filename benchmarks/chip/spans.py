"""The program's own spans in a traced window: the ``spgemm`` and ``spgemm.*``
spans of the SpGEMM call and the ``gnn.*`` spans of GCN training, which the
program writes as ``jax.profiler.TraceAnnotation``s into the same
``.xplane.pb`` as the device planes.

Only spans inside ``bench.window`` on the harness's thread count (the line
``xplane._window_line`` finds).  Spans on one thread nest; a span's parent
is the innermost program span around it, and its self time is its duration
less its child program spans'.  A trace of a program without such spans
gives none, and the per-layer metrics that read them then report nothing.

    python3 benchmarks/chip/spans.py [<trace dir>]   # default .bench_trace

prints, for each program span name, its count, seconds and self seconds,
and the device's idle seconds in the window under each innermost program
span (the device clock runs about 1 ms ahead of the host's, so idle time
near a span's edge may belong to its neighbour); then the device seconds of
each program (``XLA Modules`` name) in the window.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import sys
from pathlib import Path

import xplane

HERE = Path(__file__).resolve().parent
TRACE_DIR = HERE.parents[1] / ".bench_trace"
OUTSIDE = "(no program span)"


@dataclasses.dataclass(frozen=True)
class Span:
    """One program span, nanoseconds on the trace's host clock."""

    name: str
    start_ns: int
    end_ns: int
    self_ns: int
    parent: str | None  # the innermost program span around it

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9

    @property
    def self_s(self) -> float:
        return self.self_ns * 1e-9


def _nest(events):
    """``[(start, end, name)]`` of one thread, sorted, as ``Span``s with
    their parents and self times, and each span's children's intervals."""
    spans, children, stack = [], [], []
    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and spans[stack[-1]][1] <= s:
            stack.pop()
        parent = stack[-1] if stack else None
        if parent is not None:
            children[parent].append((s, e))
        spans.append((s, e, name, parent))
        children.append([])
        stack.append(len(spans) - 1)
    out = []
    for (s, e, name, parent), kids in zip(spans, children):
        self_ns = (e - s) - sum(ke - ks for ks, ke in kids)
        out.append(Span(name, s, e, self_ns, None if parent is None else spans[parent][2]))
    return out, children


def _window_program_events(profile):
    events, w0, w1 = xplane._window_line(profile)
    found = [
        (s, e, n)
        for s, e, n in events
        if (n == "spgemm" or n.startswith(("spgemm.", "gnn."))) and w0 <= s and e <= w1
    ]
    return found, w0, w1


def program_spans(profile) -> tuple[Span, ...]:
    """The program spans of a ``jax.profiler.ProfileData`` inside its
    ``bench.window``, in order of start."""
    found, _, _ = _window_program_events(profile)
    return tuple(_nest(found)[0])


@functools.lru_cache(maxsize=None)
def spans_of(path: str) -> tuple[Span, ...]:
    """``program_spans`` of one ``.xplane.pb`` file, parsed once per process."""
    from jax.profiler import ProfileData

    return program_spans(ProfileData.from_file(path))


def window_spans() -> tuple[Span, ...]:
    """The program spans of the traced run's window (``TRACE_DIR``)."""
    return spans_of(str(xplane.find_xplane(TRACE_DIR)))


def named(found, name: str) -> list[Span]:
    return [s for s in found if s.name == name]


def _device_gaps(profile, w0: int, w1: int):
    """Per device plane, the sorted idle intervals of the window."""
    out = []
    for plane in profile.planes:
        if not xplane.DEVICE_PLANE.match(plane.name):
            continue
        ops = [
            (max(ev.start_ns, w0), min(ev.end_ns, w1))
            for ln in plane.lines
            if ln.name == xplane.OPS_LINE
            for ev in ln.events
            if min(ev.end_ns, w1) > max(ev.start_ns, w0)
        ]
        edges = [w0] + [x for iv in xplane._union(ops) for x in iv] + [w1]
        out.append([(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a])
    return out


def _overlap(gaps, starts, a: float, b: float) -> float:
    """Nanoseconds of the sorted disjoint ``gaps`` inside ``[a, b)``."""
    total = 0.0
    i = max(bisect.bisect_right(starts, a) - 1, 0)
    while i < len(gaps) and gaps[i][0] < b:
        total += max(0.0, min(b, gaps[i][1]) - max(a, gaps[i][0]))
        i += 1
    return total


def idle_by_span(profile) -> dict:
    """Device idle seconds of the window under each innermost program span
    (``OUTSIDE`` for the window outside every program span), averaged over
    the devices traced."""
    found, w0, w1 = _window_program_events(profile)
    nested, children = _nest(found)
    pieces = []  # (innermost span name, start, end)
    for span, kids in zip(nested, children):
        edges = [span.start_ns] + [x for iv in xplane._union(kids) for x in iv] + [span.end_ns]
        pieces += [(span.name, a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    tops = [(s.start_ns, s.end_ns) for s in nested if s.parent is None]
    edges = [w0] + [x for iv in xplane._union(tops) for x in iv] + [w1]
    pieces += [(OUTSIDE, a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    per_device = _device_gaps(profile, w0, w1)
    idle = {}
    for gaps in per_device:
        starts = [g[0] for g in gaps]
        for name, a, b in pieces:
            idle[name] = idle.get(name, 0.0) + _overlap(gaps, starts, a, b) * 1e-9
    return {k: v / max(len(per_device), 1) for k, v in idle.items()}


def main(argv=None) -> int:
    from jax.profiler import ProfileData

    args = sys.argv[1:] if argv is None else argv
    path = xplane.find_xplane(args[0] if args else TRACE_DIR)
    profile = ProfileData.from_file(str(path))
    found = program_spans(profile)
    idle = idle_by_span(profile)
    _, w0, w1 = xplane._window_line(profile)
    print(f"window_s={(w1 - w0) * 1e-9!r} program_spans={len(found)}")
    print("span count seconds self_seconds idle_seconds")
    for name in sorted({s.name for s in found}):
        of = named(found, name)
        seconds = sum(s.seconds for s in of)
        self_s = sum(s.self_s for s in of)
        print(f"{name} {len(of)} {seconds!r} {self_s!r} {idle.get(name, 0.0)!r}")
    print(f"{OUTSIDE} - - - {idle.get(OUTSIDE, 0.0)!r}")
    by_program = {}
    for op, seconds in xplane.reduce_profile(profile).ops.items():
        program = op.split("/", 1)[0]
        by_program[program] = by_program.get(program, 0.0) + seconds
    print("program device_seconds")
    for program, seconds in sorted(by_program.items(), key=lambda kv: -kv[1]):
        print(f"{program} {seconds!r}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
