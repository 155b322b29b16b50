"""Benchmark harness: one entry per paper table/figure + LM-framework
benches. Prints `name,value,derived` CSV rows.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--sections a,b,...]

Sections: tables (II,III,VIII), models (V,VI,VII,fig5), dse (IV,fig4,fig6),
kernels, lm, roofline, bridge, engine (batched-vs-naive surrogate
throughput + the dynamic-featurization overhead gate for the schema-v2
timing block, see benchmarks/engine_bench.py), dataset (batched-vs-loop
labeling throughput, see benchmarks/dataset_bench.py), train (vmapped
ensemble vs sequential loop fits, see benchmarks/train_bench.py),
pipeline (staged cold vs cached-resume + unified-vs-per-app surrogate
fits, with full-mode unified-SSIM-R² / PPA-R² quality gates, see
benchmarks/pipeline_bench.py), serve (cross-request batching
vs serial request handling in the evaluation daemon, see
benchmarks/serve_bench.py), fault (crash-safe search: checkpointed vs
plain DSE overhead + bit-identity gates, see
benchmarks/dse_bench.py::fault_main, writes BENCH_fault.json).
"""
from __future__ import annotations

import argparse
import sys
import time


def _run_gated_bench(name: str, bench_main, smoke: bool) -> None:
    """Run a standalone bench module's main() under this harness.

    The benches carry CI acceptance gates (SystemExit on a throughput
    floor); those are CI's job — a noise-sensitive threshold must not
    abort the rest of the benchmark report, so it becomes a gate row.
    """
    argv, sys.argv = sys.argv, [name] + (["--smoke"] if smoke else [])
    try:
        bench_main()
    except SystemExit as e:
        print(f"{name},gate,{e}")
    finally:
        sys.argv = argv


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller datasets/epochs")
    ap.add_argument("--sections", default="tables,models,dse,kernels,lm,"
                                          "roofline,bridge,engine,dataset,"
                                          "train,pipeline,serve,fault")
    args = ap.parse_args()

    from repro.core.artifacts import enable_compilation_cache
    enable_compilation_cache()

    from benchmarks import paper_tables as T
    from benchmarks import lm_bench as L

    if args.quick:
        T.SCALE.update(n_samples=300, epochs=12, hidden=48,
                       dse_budget=400, dse_pop=32)

    sections = set(args.sections.split(","))
    t0 = time.time()
    if "tables" in sections:
        T.table2_operator_summary()
        T.table3_library()
        T.table8_pruning()
    if "models" in sections:
        T.table5_rf_vs_gnn()
        T.table6_naive_vs_simplified()
        T.table7_gnn_variants()
        T.fig5_critical_path_ablation()
    if "dse" in sections:
        T.table4_fig4_pareto()
        T.fig6_sampling_methods()
    if "kernels" in sections:
        L.bench_kernels()
    if "lm" in sections:
        L.bench_train_decode_steps()
    if "roofline" in sections:
        L.bench_roofline_summary()
    if "bridge" in sections:
        L.bench_lm_bridge()
    if "engine" in sections:
        from benchmarks import engine_bench
        _run_gated_bench("engine_bench", engine_bench.main, args.quick)
    if "dataset" in sections:
        from benchmarks import dataset_bench
        _run_gated_bench("dataset_bench", dataset_bench.main, args.quick)
    if "train" in sections:
        from benchmarks import train_bench
        _run_gated_bench("train_bench", train_bench.main, args.quick)
    if "pipeline" in sections:
        from benchmarks import pipeline_bench
        _run_gated_bench("pipeline_bench", pipeline_bench.main, args.quick)
    if "serve" in sections:
        from benchmarks import serve_bench
        _run_gated_bench("serve_bench", serve_bench.main, args.quick)
    if "fault" in sections:
        from benchmarks import dse_bench
        _run_gated_bench("fault_bench", dse_bench.fault_main, args.quick)
    print(f"# total benchmark time: {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
