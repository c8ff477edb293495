# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark entry point: ``PYTHONPATH=src python -m benchmarks.run``.

Mapping to the paper (see DESIGN.md §6):
  fig1   generalization gap A1..A5 (Figure 1)
  table1 scaling over K and H (Table 1, time simulated per App. B.2)
  fig2b  local vs mini-batch at same effective batch (Figure 2b)
  table2 post-local vs mini-batch selected rows (Table 2)
  table4 sign / EF-sign compression (Table 4)
  table8 local x global momentum (Table 8)
  table14 isotropic-noise baseline (Table 14)
  table16/17 hierarchical local SGD (Tables 16/17, Fig. 19)
  fig4   flatness via Hessian power iteration (Figure 4)
  fig10  local-step warmup strategies (App. B.4.2, Fig. 10/11)
  fig6   convex logistic regression (Figure 6)
  sec5   K*Sigma noise-scale verification (Section 5, eq. 4)
  kernels Pallas kernel microbenches
  roofline dry-run derived roofline rows (deliverable g quick view)
  noise_adaptive composite controller smoke: wire bytes/round + loss
  elastic backend seam smoke: scripted resize + straggler demotion
  serving continuous batching vs static wave + hot-swap latency
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="comma-separated bench names")
    ap.add_argument("--fast", action="store_true",
                    help="skip the slower training benches")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke subset: kernel + bucket + resident-state "
                         "+ sharded + syncplan microbenches only")
    ap.add_argument("--json-out", default="",
                    help="write a BENCH_local_sgd.json artifact (structured "
                         "rows: step time, bytes/round, pack/unpack bytes, "
                         "collective counts) so the perf trajectory is "
                         "tracked across PRs")
    args = ap.parse_args()
    from repro.utils import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (bench_convex, bench_kernels, bench_roofline,
                            bench_serving, paper_tables)

    benches = {
        "kernels": bench_kernels.kernels_bench,
        "bucket": bench_kernels.bucket_bench,
        "resident": bench_kernels.resident_bench,
        "sharded": bench_kernels.sharded_bench,
        "syncplan": bench_kernels.syncplan_bench,
        "noise_adaptive": bench_kernels.noise_adaptive_bench,
        "elastic": bench_kernels.elastic_bench,
        "serving": bench_serving.serving_bench,
        "roofline": bench_roofline.roofline_rows,
        "sec5": paper_tables.sec5_noise_scale,
        "table17": paper_tables.table17_network_delay_tolerance,
        "fig6": bench_convex.fig6_convex,
        "fig6b": bench_convex.fig6b_speedup_over_K,
        "fig1": paper_tables.fig1_generalization_gap,
        "table2": paper_tables.table2_postlocal_vs_minibatch,
        "table1": paper_tables.table1_scaling,
        "fig2b": paper_tables.fig2b_same_effective_batch,
        "table4": paper_tables.table4_sign_compression,
        "table8": paper_tables.table8_momentum,
        "table14": paper_tables.table14_noise_injection,
        "table16": paper_tables.table16_hierarchical,
        "fig4": paper_tables.fig4_flatness,
        "fig10": paper_tables.fig10_warmup,
    }
    slow = {"table1", "fig1", "table2", "fig2b", "table4", "table8",
            "table14", "table16", "fig4", "fig6", "fig6b", "fig10"}
    smoke = ("kernels", "bucket", "resident", "sharded", "syncplan",
             "noise_adaptive", "elastic", "serving")
    selected = ([s for s in args.only.split(",") if s] if args.only
                else list(smoke) if args.smoke
                else [k for k in benches if not (args.fast and k in slow)])

    print("name,us_per_call,derived")
    failures = 0
    for name in selected:
        try:
            benches[name]()
        except Exception:
            failures += 1
            print(f"{name},0.0,ERROR", flush=True)
            traceback.print_exc()
    if args.json_out:
        import platform

        import jax

        from benchmarks.common import RECORDS, bench_metrics
        artifact = {
            "bench": "local_sgd",
            "selected": selected,
            "failures": failures,
            "backend": jax.default_backend(),
            "python": platform.python_version(),
            "jax": jax.__version__,
            "records": RECORDS,
            # Prometheus exposition of every timing-helper measurement
            # (repro_bench_seconds histogram, label name=<bench case>).
            "metrics_exposition": bench_metrics().exposition(),
        }
        with open(args.json_out, "w") as f:
            json.dump(artifact, f, indent=1)
        print(f"wrote {args.json_out} ({len(RECORDS)} records)", flush=True)
    if failures:
        sys.exit(1)


if __name__ == '__main__':
    main()
