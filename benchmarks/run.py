"""Benchmark orchestrator — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--skip-roofline]

Default is the quick profile (CPU-container friendly, minutes).  ``--full``
scales n to the paper's regimes (hours; intended for a real cluster).
"""
from __future__ import annotations

import argparse
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

from benchmarks import bench_constrained, bench_mr, bench_streaming
from benchmarks.common import table


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--skip-roofline", action="store_true")
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache(_ROOT)}")
    quick = not args.full
    t0 = time.time()

    print("=" * 72)
    print("Fig 1/2 — streaming approximation ratio vs (k, k')")
    print("=" * 72)
    rows = bench_streaming.run(quick=quick)
    print(table(rows, ["dataset", "k", "k'", "approx_ratio",
                       "throughput_pts_s"], "Streaming approximation"))

    print("\n" + "=" * 72)
    print("Fig 3 — streaming kernel throughput")
    print("=" * 72)
    rows = bench_streaming.run_throughput(quick=quick)
    print(table(rows, ["dataset", "k", "k'", "throughput_pts_s"],
                "Streaming throughput"))

    print("\n" + "=" * 72)
    print("Fig 4 / §7.2 — MapReduce approximation vs k' × parallelism")
    print("=" * 72)
    rows = bench_mr.run_mr_approx(quick=quick)
    print(table(rows, ["reducers", "k'", "partition", "approx_ratio"],
                "MR approximation"))

    print("\n" + "=" * 72)
    print("Table 4 — CPPU vs AFZ (remote-clique)")
    print("=" * 72)
    rows = bench_mr.run_afz(quick=quick)
    print(table(rows, ["k", "AFZ_approx", "CPPU_approx", "AFZ_time_s",
                       "CPPU_time_s", "speedup"], "CPPU vs AFZ"))

    print("\n" + "=" * 72)
    print("Fig 5 — scalability")
    print("=" * 72)
    rows = bench_mr.run_scalability(quick=quick)
    print(table(rows, ["n", "processors", "mode", "time_s"], "Scalability"))

    print("\n" + "=" * 72)
    print("Constrained diversity — fair pipeline quality vs m groups × k")
    print("=" * 72)
    rows = bench_constrained.run_quality(quick=quick)
    print(table(rows, ["m", "k", "k'", "approx_ratio", "throughput_pts_s"],
                "Constrained approximation"))

    print("\n" + "=" * 72)
    print("Constrained diversity — path throughput")
    print("=" * 72)
    rows = bench_constrained.run_throughput(quick=quick)
    print(table(rows, ["path", "m", "k", "k'", "throughput_pts_s"],
                "Constrained throughput"))

    print("\n" + "=" * 72)
    print("Constrained diversity — long-tail (Zipf) labels "
          "(BENCH_constrained.json)")
    print("=" * 72)
    rows = bench_constrained.run_longtail(quick=quick)
    bench_constrained.emit_json(rows, path="BENCH_constrained.json")
    print(table(rows, ["path", "m", "alpha", "head_share", "time_s",
                       "value_ratio_vs_single"], "Constrained long-tail"))

    print("\n" + "=" * 72)
    print("Selection engine — b=1 vs batched vs group-blocked (BENCH_gmm.json)")
    print("=" * 72)
    # bench_constrained.run_grouped_engine measures the same two grouped legs
    # at the ISSUE-2 acceptance shape; BENCH_gmm.json already carries that
    # speedup, so only the tracked artifact runs here.
    from benchmarks import bench_gmm
    rows = bench_gmm.run(quick=quick)
    bench_gmm.emit_json(rows, path="BENCH_gmm.json")
    print(table(rows, ["path", "n", "k", "b", "m", "time_s", "sweeps",
                       "effective_gbps"], "GMM engine"))

    print("\n" + "=" * 72)
    print("Adaptive engine — fixed b vs b=\"auto\" cluster sweep "
          "(BENCH_adaptive.json)")
    print("=" * 72)
    from benchmarks import bench_adaptive
    rows = bench_adaptive.run(quick=quick)
    bench_adaptive.emit_json(rows, path="BENCH_adaptive.json")
    print(table(rows, ["shape", "engine", "n", "clusters", "kprime",
                       "time_s", "radius_ratio_vs_b1", "speedup_vs_b1"],
                "Adaptive engine"))

    print("\n" + "=" * 72)
    print("Resilience — retry / degrade / checkpoint / resume "
          "(BENCH_resilience.json)")
    print("=" * 72)
    from benchmarks import bench_resilience
    rows = bench_resilience.run(quick=quick)
    bench_resilience.emit_json(rows, path="BENCH_resilience.json")
    print(table(rows, ["path", "n", "k'", "time_s", "degraded"],
                "Resilience"))

    print("\n" + "=" * 72)
    print("Serving — session-reuse rerank vs per-request re-solve "
          "(BENCH_serving.json)")
    print("=" * 72)
    from benchmarks import bench_serving
    rows = bench_serving.run(quick=quick)
    bench_serving.emit_json(rows, path="BENCH_serving.json")
    print(table(rows, ["path", "sessions", "n_per_req", "time_s", "p50_ms",
                       "p99_ms", "qps"], "Serving rerank"))

    print("\n" + "=" * 72)
    print("Dynamic index — incremental churn vs rebuild-from-scratch "
          "(BENCH_dynamic.json)")
    print("=" * 72)
    from benchmarks import bench_dynamic
    rows = bench_dynamic.run(quick=quick)
    bench_dynamic.emit_json(rows, path="BENCH_dynamic.json")
    print(table(rows, ["shape", "path", "n", "rounds", "time_s",
                       "radius_ratio_vs_rebuild"], "Dynamic index"))

    if not args.skip_roofline and os.path.isdir("results"):
        print("\n" + "=" * 72)
        print("§Roofline — dry-run derived terms (TPU v5e model)")
        print("=" * 72)
        from benchmarks import roofline
        print(roofline.render(roofline.load_rows("results")))

    print(f"\nTotal benchmark time: {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
