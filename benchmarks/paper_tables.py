"""One benchmark per paper table/figure (Tables II-VIII, Figs 4-6).

Each function prints `name,value,derived` CSV rows. Scales are CPU-reduced
(`SCALE`); `benchmarks/run.py --quick` reduces them further.
"""
from __future__ import annotations

import functools
import time
from typing import Dict

import numpy as np

from repro.accel import apps as apps_lib
from repro.accel import library as lib
from repro.core import dataset as ds_lib
from repro.core import dse, gnn, models, pipeline as pipe, pruning, training
from repro.core.rforest import RandomForest

SCALE = {"n_samples": 600, "epochs": 25, "hidden": 64, "n_layers": 3,
         "dse_budget": 1000, "dse_pop": 48}
_CACHE: Dict = {}


def _dataset(app: str, simplify=True):
    key = (app, SCALE["n_samples"], simplify)
    if key not in _CACHE:
        pruned, report = pruning.prune_library()
        entries = {k: pruned[k] for k in
                   {n.kind for n in apps_lib.APPS[app].unit_nodes}}
        _CACHE[key] = (ds_lib.build(app, n_samples=SCALE["n_samples"],
                                    lib_entries=entries,
                                    simplify_graph=simplify),
                       entries, report)
    return _CACHE[key]


def _train_gnn(ds, arch="gsae", use_crit=True, epochs=None):
    tr, te = ds.split(0.9)
    cfg = models.TwoStageConfig(
        gnn=gnn.GNNConfig(arch=arch, n_layers=SCALE["n_layers"],
                          hidden=SCALE["hidden"],
                          feature_dim=ds.x.shape[-1]),
        use_critical_path=use_crit)
    t0 = time.time()
    params = training.fit_two_stage(
        cfg, tr, training.TrainConfig(epochs=epochs or SCALE["epochs"]))
    dt = time.time() - t0
    return cfg, params, training.evaluate(cfg, params, ds, te), dt


def table2_operator_summary():
    print("# Table II: operator summary per accelerator")
    for name, app in apps_lib.APPS.items():
        counts: Dict[str, int] = {}
        for n in app.unit_nodes:
            counts[n.kind] = counts.get(n.kind, 0) + 1
        total = sum(counts.values())
        print(f"table2,{name},{counts},total={total}")


def table3_library():
    print("# Table III: approximate operator library sizes")
    t0 = time.time()
    full = lib.full_library()
    dt = (time.time() - t0) * 1e6 / max(sum(len(v) for v in full.values()), 1)
    for kind, entries in full.items():
        print(f"table3,{kind},{dt:.0f}us_per_unit_characterization,"
              f"n={len(entries)}")


def table8_pruning():
    print("# Table VIII: design space before/after pruning")
    _, report = pruning.prune_library()
    for name, app in apps_lib.APPS.items():
        sizes = pruning.space_sizes(app, report)
        print(f"table8,{name},initial={sizes['initial']:.3g},"
              f"invalid={sizes['after_invalid']:.3g},"
              f"redundant={sizes['after_redundant']:.3g}")


def table5_rf_vs_gnn(apps=("sobel", "gaussian", "kmeans")):
    print("# Table V: AutoAX (random forest) vs ApproxPilot (GNN) R2/MAPE")
    for app in apps:
        ds, entries, _ = _dataset(app)
        tr, te = ds.split(0.9)
        # RF baseline (flat features, black box)
        t0 = time.time()
        rf_metrics = {}
        Xtr, Xte = tr.flat_features(), te.flat_features()
        for i, tname in enumerate(models.TARGETS):
            rf = RandomForest(n_trees=16, seed=i).fit(Xtr, tr.y[:, i])
            pred = rf.predict(Xte) * ds.y_std[i] + ds.y_mean[i]
            rf_metrics[tname] = (training.r2_score(te.y_raw[:, i], pred),
                                 training.mape(te.y_raw[:, i], pred))
        rf_dt = time.time() - t0
        _, _, gnn_metrics, gnn_dt = _train_gnn(ds)
        for tname in models.TARGETS:
            r2g = gnn_metrics[tname]["r2"]
            mg = gnn_metrics[tname]["mape"]
            r2r, mr = rf_metrics[tname]
            print(f"table5,{app}/{tname},rf_r2={r2r:.3f},rf_mape={mr:.3f},"
                  f"gnn_r2={r2g:.3f},gnn_mape={mg:.3f}")
        print(f"table5,{app}/critpath,gnn_acc="
              f"{gnn_metrics['critical_path']['accuracy']:.3f},"
              f"train_s_rf={rf_dt:.1f},train_s_gnn={gnn_dt:.1f}")


def table6_naive_vs_simplified(app="kmeans"):
    print("# Table VI: naive vs simplified graph (kmeans)")
    for simplify in (False, True):
        ds, _, _ = _dataset(app, simplify=simplify)
        _, _, m, dt = _train_gnn(ds)
        tag = "simplified" if simplify else "naive"
        row = ",".join(f"{t}_r2={m[t]['r2']:.3f}" for t in models.TARGETS)
        print(f"table6,{tag},n_nodes={len(ds.graph.node_ids)},{row},"
              f"crit_acc={m['critical_path']['accuracy']:.3f}")


def table7_gnn_variants(app="gaussian"):
    print("# Table VII: GNN architecture comparison (gaussian)")
    ds, _, _ = _dataset(app)
    for arch in ("gcn", "mpnn", "gat", "gsae"):
        _, _, m, dt = _train_gnn(ds, arch=arch)
        row = ",".join(f"{t}_r2={m[t]['r2']:.3f}" for t in models.TARGETS)
        print(f"table7,{arch},{row},"
              f"crit_acc={m['critical_path']['accuracy']:.3f},"
              f"train_s={dt:.1f}")


def fig5_critical_path_ablation(app="gaussian"):
    print("# Fig 5: latency prediction - RF vs baseline GNN vs two-stage")
    ds, _, _ = _dataset(app)
    tr, te = ds.split(0.9)
    Xtr, Xte = tr.flat_features(), te.flat_features()
    rf = RandomForest(n_trees=16, seed=2).fit(Xtr, tr.y[:, 2])
    pred = rf.predict(Xte) * ds.y_std[2] + ds.y_mean[2]
    r2_rf = training.r2_score(te.y_raw[:, 2], pred)
    _, _, m_base, _ = _train_gnn(ds, use_crit=False)
    _, _, m_two, _ = _train_gnn(ds, use_crit=True)
    print(f"fig5,latency_r2,rf={r2_rf:.3f},"
          f"baseline_gnn={m_base['latency']['r2']:.3f},"
          f"two_stage={m_two['latency']['r2']:.3f}")
    return r2_rf, m_base["latency"]["r2"], m_two["latency"]["r2"]


def fig6_sampling_methods(app="sobel", budget=1000):
    print("# Fig 6: sampler comparison on sobel (batched surrogate engine)")
    ds, entries, _ = _dataset(app)
    cfg, params, _, _ = _train_gnn(ds)
    from repro.core.engine import SurrogateEngine
    app_def = apps_lib.APPS[app]
    engine = SurrogateEngine.from_gnn(cfg, params, ds, app_def, entries)

    sizes = [len(entries[n.kind]) for n in app_def.unit_nodes]
    # warm the jit cache for every bucket shape the samplers can hit, so no
    # sampler's time_s is dominated by XLA compilation
    rng = np.random.default_rng(0)
    b = 1
    while b <= engine.chunk_size:
        engine([tuple(int(rng.integers(0, s)) for s in sizes)
                for _ in range(b)])
        b <<= 1
    for name in ("random", "tpe", "nsga2", "nsga3", "islands"):
        engine.clear_cache()        # per-sampler timing fairness
        engine.reset_stats()
        t0 = time.time()
        res = dse.SAMPLERS[name](sizes, engine, budget, seed=0)
        dt = time.time() - t0
        # hypervolume proxy vs a fixed reference point
        F = res.pareto_objs
        ref = np.array([3000.0, 600.0, 120.0, 1.0])
        hv = float(np.mean(np.prod(np.maximum(ref - F, 0) / ref, axis=1)))
        s = res.stats or {}
        print(f"fig6,{name},pareto_n={len(F)},hv_proxy={hv:.4f},"
              f"time_s={dt:.2f},configs_s={s.get('configs_per_sec', 0):.0f},"
              f"cache_hit={s.get('cache_hit_rate', 0):.2f}")


def table4_fig4_pareto(apps=("sobel",), budget=None):
    print("# Table IV + Fig 4: Pareto points, ApproxPilot (gnn) vs "
          "AutoAX (rf)")
    budget = budget or SCALE["dse_budget"]
    for app in apps:
        for surrogate in ("gnn", "rf"):
            cfg = pipe.PipelineConfig(
                app=app, n_samples=SCALE["n_samples"],
                epochs=SCALE["epochs"], hidden=SCALE["hidden"],
                n_layers=SCALE["n_layers"], dse_budget=budget,
                dse_pop=SCALE["dse_pop"], surrogate=surrogate,
                sampler="nsga3" if surrogate == "gnn" else "tpe")
            t0 = time.time()
            res = pipe.run(cfg)
            dt = time.time() - t0
            objs = res.pareto_objs
            # per-pair pareto counts (area-ssim etc.), as in Table IV
            def pair_count(i):
                sub = objs[:, [i, 3]]
                pc, _ = dse.pareto_front(list(range(len(sub))), sub)
                return len(pc)
            eng = res.metrics.get("engine", {})
            print(f"table4,{app}/{surrogate},area_ssim={pair_count(0)},"
                  f"power_ssim={pair_count(1)},latency_ssim={pair_count(2)},"
                  f"total={len(objs)},time_s={dt:.1f},"
                  f"engine_cps={eng.get('configs_per_sec', 0):.0f},"
                  f"cache_hit={eng.get('cache_hit_rate', 0):.2f}")
            val = pipe.validate_pareto(res, 5)
            print(f"fig4,{app}/{surrogate},"
                  f"oracle_rel_err={val['mean_rel_err']:.3f}")
