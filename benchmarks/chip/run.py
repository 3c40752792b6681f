"""On-chip benchmark: run one cell of ``BENCHMARK.json`` and print its result.

    python3 benchmarks/chip/run.py --workload economics.reuse --seed 7 \
        --seconds 30 --trace 0

The cell names a configuration (``configs/<config>.json``, with its plain
reference ``configs/<config>.py`` beside it) and a traffic mix
(``traffic/<mix>.json``, whose ``drive`` names the code that drives it,
``drives/<drive>.py``).  Set-up makes the inputs from ``--seed`` and warms every
shape the window uses; the window then drives the program; afterwards the
outputs of the window are compared with the reference.  With ``--trace 1``
the window runs under the profiler and each per-layer metric is read by its
own reader, ``metrics/<metric>.py``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` a
``breakdown``, and last the ``checks``, each number compared beside its
limit; those also end standard error.  Without a TPU, or with fewer chips
than the cell asks for, the run exits non-zero and prints no result.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import workload  # noqa: E402

TRACE_DIR = ROOT / ".bench_trace"
CACHE_DIR = ROOT / ".jax_cache"
# libtpu would otherwise log under /tmp, outside the checkout.
os.environ.setdefault("TPU_LOG_DIR", "disabled")


class Unavailable(RuntimeError):
    """This checkout or machine cannot run the cell (no program, no TPU, too
    few chips)."""


def finite(x: float) -> float:
    """``x``, or the largest float where it is not finite: the result line
    stays strict JSON."""
    return x if math.isfinite(x) else 1.7976931348623157e308


def applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(manifest: dict, name: str):
    """(cell, configuration, traffic, reference)."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    config_file = ROOT / entry["file"]
    config = json.loads(config_file.read_text())
    traffic = json.loads((HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    reference = workload.load_path(config_file.with_suffix(".py"), f"reference_{cell['config']}")
    return cell, config, traffic, reference


def device_info(chips: int) -> dict:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Unavailable(f"no TPU: JAX reports {devices[0].platform!r} devices")
    if len(devices) < chips:
        raise Unavailable(f"the cell asks for {chips} chips and JAX sees {len(devices)}")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}


class CompileCounter:
    """Backend compilations and persistent-cache loads, as JAX reports them
    (a cache load also passes through the backend-compile event)."""

    def __init__(self):
        import jax

        self.requests = 0
        self.loads = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.loads += 1

    def snapshot(self):
        return self.requests - self.loads, self.loads


def per_layer(manifest, cell_name, result, drive, summary, peaks):
    """Every per-layer metric of the cell that its reader finds."""
    ctx = {
        "items": result["items"],
        "window_s": result["seconds"],
        "counts": drive.counts,
        "trace": summary,
        "peaks": peaks,
    }
    reported = {m["name"] for m in manifest["end_to_end"] if applies(m, cell_name)}
    out = {}
    for m in manifest["per_layer"]:
        if not applies(m, cell_name) or m["moves"] not in reported:
            continue
        value = workload.load("metrics", m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run(args) -> int:
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell, config, traffic, reference = load_cell(manifest, args.workload)
    try:
        import repro  # noqa: F401
    except ImportError as e:
        raise Unavailable(f"the program is not in this checkout: {e}") from e
    import jax

    import counts
    import xplane

    device = device_info(cell["chips"])
    # The compile cache lives in the checkout, at a fixed path, whatever the
    # environment says: only the first run of a cell in a checkout compiles,
    # and two checkouts share nothing.  Every program is kept, however small
    # or large.
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    compiles = CompileCounter()
    peaks = counts.load_peaks(device["kind"])

    drive = workload.load("drives", traffic["drive"]).Drive(
        config, traffic, args.seed, args.seconds, reference
    )
    drive.setup()
    setup_s = time.perf_counter() - T0
    setup_compiles, setup_loads = compiles.snapshot()
    print(f"[bench] setup_s={setup_s!r} compiles={setup_compiles} cache_loads={setup_loads}")

    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
            result = drive.window()
    finally:
        if args.trace:
            jax.profiler.stop_trace()
    c1, l1 = compiles.snapshot()
    print(
        f"[bench] window_s={result['seconds']!r} items={result['items']} "
        f"compiles_in_window={c1 - setup_compiles} cache_loads_in_window={l1 - setup_loads}"
    )
    if "item_s" in result:
        print(f"[bench] item_s={result['item_s']!r}")
    device["memory_peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices()
    )

    t_check = time.perf_counter()
    checks, failed = drive.check()
    print(f"[bench] check_s={time.perf_counter() - t_check!r}")
    limits = reference.LIMITS
    correct = failed == 0 and all(checks[k] <= limits[k] for k in limits)

    out = {"correct": correct, "attempted": result["items"], "failed": failed}
    if args.trace:
        summary = xplane.reduce_file(xplane.find_xplane(TRACE_DIR))
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["metrics"] = per_layer(manifest, cell["name"], result, drive, summary, peaks)
        out["breakdown"] = {
            "device_ops": [[k, v] for k, v in summary.top_ops()],
            "idle_gaps": [[k, v] for k, v in summary.gaps],
        }
        for span, s in sorted(summary.idle_by_span.items(), key=lambda kv: -kv[1])[:10]:
            print(f"[bench] idle_by_span {span!r} {s!r}")
    else:
        metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
        for m in manifest["end_to_end"]:
            if m["name"] != "setup_s" and applies(m, cell["name"]):
                metrics[m["name"]] = {"value": result[m["name"]], "unit": m["unit"]}
        out["metrics"] = metrics
    out["device"] = device
    out["checks"] = {k: {"value": finite(checks[k]), "limit": limits[k]} for k in limits}
    sys.stdout.flush()
    for k in limits:
        print(f"check {k}={checks[k]!r} limit={limits[k]!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        return run(args)
    except Unavailable as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3


if __name__ == "__main__":
    sys.exit(main())
