"""Record the small TPU trace that ``test_xplane.py`` reduces.

    python3 benchmarks/chip/tests/record_trace.py   # on a TPU

Inside ``bench.window``: 20 ms of host sleep in ``bench.lead`` (the device
clock of a trace runs about a millisecond ahead of the host's), a jitted
matmul, 50 ms of host sleep in ``bench.sleep`` (a gap the reduction must
name), and the AIA row gather on a small table.  Writes ``data/v5e_small.xplane.pb``.
"""

import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parents[2] / "src")]


def main() -> int:
    import jax
    import jax.numpy as jnp

    import xplane
    from repro.kernels.aia_gather import gather_rows

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 1
    x = jnp.ones((2048, 2048), jnp.float32)
    table = jnp.arange(4096 * 128, dtype=jnp.float32).reshape(4096, 128)
    idx = (jnp.arange(8192, dtype=jnp.int32) * 7) % 4096
    matmul = jax.jit(lambda a: a @ a)
    jax.block_until_ready((matmul(x), gather_rows(table, idx)))
    with tempfile.TemporaryDirectory() as tmp:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=options)
        with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
            with jax.profiler.TraceAnnotation("bench.lead"):
                time.sleep(0.02)
            jax.block_until_ready(matmul(x))
            with jax.profiler.TraceAnnotation("bench.sleep"):
                time.sleep(0.05)
            jax.block_until_ready(gather_rows(table, idx))
        jax.profiler.stop_trace()
        out = HERE / "data" / "v5e_small.xplane.pb"
        out.parent.mkdir(exist_ok=True)
        shutil.copy(xplane.find_xplane(tmp), out)
    s = xplane.reduce_file(out)
    print(f"window_s={s.window_s!r} busy_s={s.busy_s!r} ops={s.ops!r} gaps={s.gaps!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
