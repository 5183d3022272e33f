#!/usr/bin/env python3
"""Record the small TPU trace ``bench/tests/test_trace.py`` reduces.

    python3 bench/tools/record_test_trace.py --out bench_out/test_trace

On one TPU chip, inside a ``bench.window`` annotation: a jitted matmul,
a 20 ms sleep (an idle gap the host spends in plain Python), and a
jitted float64 reduction.  Writes ``<out>/small.xplane.pb``; copy it to
``bench/tests/data/tpu_small.xplane.pb``.
"""
import argparse
import glob
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=str(ROOT / "bench_out" / "test_trace"))
    out = Path(ap.parse_args(argv).out)
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("error: needs a TPU", file=sys.stderr)
        return 2
    jax.config.update("jax_enable_x64", True)
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    g = jax.jit(lambda v: jnp.cumsum(jnp.log1p(v)).max())
    x = jnp.ones((512, 512), jnp.float32)
    v = jnp.linspace(0.0, 1.0, 1 << 16, dtype=jnp.float64)
    f(x).block_until_ready()
    g(v).block_until_ready()
    shutil.rmtree(out, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(out / "raw"), profiler_options=options)
    with jax.profiler.TraceAnnotation("bench.window"):
        f(x).block_until_ready()
        time.sleep(0.02)
        g(v).block_until_ready()
    jax.profiler.stop_trace()
    found = sorted(glob.glob(str(out / "raw" / "**" / "*.xplane.pb"),
                             recursive=True))
    shutil.copy(found[-1], out / "small.xplane.pb")
    print(f"wrote {out / 'small.xplane.pb'}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
