"""Benchmark harness: one module per paper table/figure + ours.

  python -m benchmarks.run [--only fig3,table1,fig4,kernels,roofline] [--quick]

Results are incrementally cached under artifacts/bench/ (FL experiments are
the expensive part on CPU); delete the cache to re-run from scratch.
"""
from __future__ import annotations

import argparse
import time

from benchmarks import ablation_prediction, engine_throughput, fig3_convergence
from benchmarks import fig4_class_ratio, kernel_bench, roofline_report
from benchmarks import table1_connection_rate
from repro.launch.compile_cache import use_compile_cache

SECTIONS = {
    "kernels": kernel_bench.main,
    "roofline": roofline_report.main,
    "engine": engine_throughput.main,
    "fig3": fig3_convergence.main,
    "table1": table1_connection_rate.main,
    "fig4": fig4_class_ratio.main,
    "ablation": ablation_prediction.main,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="comma-separated section names")
    args, _ = ap.parse_known_args()
    use_compile_cache()
    names = [n for n in args.only.split(",") if n] or list(SECTIONS)
    for name in names:
        print(f"\n===== {name} =====")
        t0 = time.time()
        SECTIONS[name]()
        print(f"===== {name} done in {time.time()-t0:.1f}s =====")


if __name__ == "__main__":
    main()
