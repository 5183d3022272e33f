"""Benchmark harness — one entry per paper table/figure plus the roofline
report.  Prints ``name,us_per_call,derived`` CSV (the repo contract).

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run fig3 fig4  # subset
"""
from __future__ import annotations

import sys
import traceback

BENCHES = {
    # paper artefacts
    "fig3": ("benchmarks.bench_bound_sweep", "Fig. 3 bound-vs-block-size sweep"),
    "fig4": ("benchmarks.bench_training", "Fig. 4 training curves + 3.8% claim"),
    "pipeline": ("benchmarks.bench_pipeline_vs_sequential",
                 "pipelined vs sequential (motivating claim)"),
    # framework layers
    "kernels": ("benchmarks.bench_kernels", "compute-layer micro-bench"),
    "streaming_llm": ("benchmarks.bench_streaming_llm",
                      "beyond-paper: schedule on LLM pretraining"),
    "extensions": ("benchmarks.bench_extensions",
                   "paper Sec.-6 extensions: Th1 MC, noisy channel, multi-device"),
    "federated": ("benchmarks.bench_federated",
                  "federated round planner: joint selection + (rate, n_c)"),
    # roofline (reads dry-run artifacts)
    "roofline": ("benchmarks.roofline_report", "roofline aggregation"),
}


def main(argv=None) -> int:
    """Run the selected benchmarks; return a non-zero exit code on ANY
    failure (unknown name or raising bench) so CI can gate on it."""
    wanted = list(argv if argv is not None else sys.argv[1:]) or list(BENCHES)
    unknown = [k for k in wanted if k not in BENCHES]
    if unknown:
        print(f"unknown benchmark(s): {unknown}; "
              f"available: {sorted(BENCHES)}", file=sys.stderr)
        return 2
    print("name,us_per_call,derived")
    failures = []
    for key in wanted:
        mod_name, _desc = BENCHES[key]
        try:
            mod = __import__(mod_name, fromlist=["run"])
            mod.run()
        except Exception:
            failures.append(key)
            traceback.print_exc()
    if failures:
        print(f"benchmark failures: {failures}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
