"""Reproduce the paper's quality tables and figures (not timings). Prints
`name,value,derived` CSV rows.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--sections a,b,...]

Sections: tables (II,III,VIII), models (V,VI,VII,fig5), dse (IV,fig4,fig6).
The speed benchmark is `bench/` (see `BENCHMARK.json` and `PERF.md`).
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller datasets/epochs")
    ap.add_argument("--sections", default="tables,models,dse")
    args = ap.parse_args()

    from repro.core.artifacts import enable_compilation_cache
    enable_compilation_cache()

    from benchmarks import paper_tables as T

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
    print(f"# total benchmark time: {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
