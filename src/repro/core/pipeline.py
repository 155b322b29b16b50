"""ApproxPilot end-to-end pipeline (Fig. 1), as composable cached stages:

   prune -> dataset -> train -> engine -> search -> validate

Each stage is a pure function over typed artifacts, keyed into a
content-addressed `repro.core.artifacts.ArtifactStore` by a stable hash of
exactly the config slice that governs it — a second run, a DSE sweep over
``dse_budget``/``sampler``, or `validate_pareto` reuses the cached
dataset/params/engine instead of rebuilding them. `run()` is kept as a
thin wrapper that executes the stages in sequence (parity-tested against
the stage-by-stage path in tests/test_pipeline_stages.py).

`surrogate="rf"` swaps in the AutoAX random-forest baseline on the same
pruned space — both frameworks are first-class so every paper table has a
benchmark entry.

All three surrogates are served to the DSE loop through
`repro.core.engine.SurrogateEngine` (batched chunked inference, config
memoization, optional Pallas kernel dispatch); its throughput counters are
surfaced as ``PipelineResult.metrics["engine"]``. The search layer is
pluggable via ``sampler``: the serial samplers of `repro.core.dse` or the
island-model orchestrator (`sampler="islands"`,
`repro.core.islands.run_islands`) — per-generation convergence traces land
in ``PipelineResult.metrics["dse_history"]``.

On top of the staged layer, `unified_surrogate` trains ONE cross-app
two-stage GNN over the merged datasets of several accelerators
(`dataset.merge`: common pad width + app-identity feature block) and
serves per-app `SurrogateEngine` views off the shared params;
`training.evaluate_transfer` quantifies leave-one-app-out generalization.
See docs/pipeline_stages.md for the stage graph and cache-key semantics.
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.accel import apps as apps_lib
from repro.accel import library as lib
from repro.core import dataset as ds_lib
from repro.core import dse, gnn, models, pruning, training
from repro.core import graph as graph_lib
from repro.core.artifacts import ArtifactStore
from repro.core.engine import SurrogateEngine
from repro.core.rforest import RandomForest
from repro.data import images as images_lib

OBJ_NAMES = ("area", "power", "latency", "1-ssim")


@dataclass
class PipelineConfig:
    app: str = "sobel"
    n_samples: int = 1500
    theta: float = 0.15
    gnn_arch: str = "gsae"
    hidden: int = 96
    n_layers: int = 3
    epochs: int = 30
    dse_budget: int = 2000
    dse_pop: int = 64
    sampler: str = "nsga3"          # nsga3 | nsga2 | tpe | random |
                                    # islands
    dse_islands: int = 4            # island count for sampler="islands"
    dse_migrate_k: int = 4          # merged-front elites broadcast per epoch
    seed: int = 0
    use_critical_path: bool = True
    surrogate: str = "gnn"          # gnn | rf | oracle
    eval_chunk: int = 512           # engine chunk size for the DSE loop
    eval_devices: int = 1           # shard engine chunks over up to N
                                    # local devices (0 = all); results
                                    # are bit-identical at any width
    use_kernel: str = "auto"        # Pallas gnn_mp: auto | on | off
    ensemble_members: int = 0       # >0: vmapped GNN ensemble + uncertainty
    ensemble_archs: Optional[Tuple[str, ...]] = None  # per-member archs
    early_stop_patience: int = 0    # >0: early stopping on a val split
    artifact_dir: Optional[str] = None  # on-disk artifact cache root
    dse_checkpoint_every: int = 0   # >0: checkpoint the search every N
                                    # generations into the store; a rerun
                                    # of the same config resumes from the
                                    # last checkpoint (generational
                                    # samplers only; tpe/random run to
                                    # completion in one step and ignore it)

    @staticmethod
    def paper_faithful(app: str) -> "PipelineConfig":
        n = {"sobel": 55_000, "gaussian": 105_000, "kmeans": 105_000,
             "dct8": 105_000, "fir15": 105_000}[app]
        return PipelineConfig(app=app, n_samples=n, hidden=300, n_layers=5,
                              epochs=100, dse_budget=20_000)


# --------------------------------------------------------------------------
# typed stage artifacts
# --------------------------------------------------------------------------

@dataclass
class AppContext:
    """Shared app setup: pruned library entries for the app's unit kinds,
    the pruning report/space sizes, and the functional-model ground truth
    (image set + exact output) — everything `run`, `validate_pareto` and
    the oracle engine used to rebuild independently."""
    app_name: str
    app: apps_lib.AccelDef
    entries: Dict[str, Sequence]
    report: Dict[str, Dict]
    space: Dict[str, float]
    inp: jnp.ndarray
    exact_out: jnp.ndarray


@dataclass
class TrainArtifact:
    """Output of the train stage, one of three surrogate families."""
    two_cfg: models.TwoStageConfig
    metrics: Dict[str, Dict]
    params: Optional[models.TwoStageParams] = None
    ens: Optional[training.EnsembleParams] = None
    rf_models: Dict[int, RandomForest] = field(default_factory=dict)


@dataclass
class PipelineResult:
    cfg: PipelineConfig
    pruned_sizes: Dict[str, Dict]
    space: Dict[str, float]
    metrics: Dict[str, Dict]     # per-target quality + "engine" throughput
    pareto_configs: List[Tuple[int, ...]]
    pareto_objs: np.ndarray
    timings: Dict[str, float]
    dataset: object
    engine: SurrogateEngine      # the surrogate engine used for DSE

    @property
    def predictor(self) -> SurrogateEngine:
        """Deprecated alias for ``engine`` (pre-stage-refactor name)."""
        return self.engine


# --------------------------------------------------------------------------
# cache-key specs: exactly the config slice each stage depends on
# --------------------------------------------------------------------------

def _prune_spec(cfg: PipelineConfig) -> Dict:
    return {"app": cfg.app, "theta": cfg.theta}


def _dataset_spec(cfg: PipelineConfig) -> Dict:
    # feature_schema: bumping graph.ACTIVE_SCHEMA re-keys the dataset —
    # and, through the nested specs below, every downstream train /
    # engine / search artifact — so a store carrying old-layout tensors
    # can never serve them to a new-schema model
    return {**_prune_spec(cfg), "n_samples": cfg.n_samples,
            "seed": cfg.seed,
            "feature_schema": graph_lib.ACTIVE_SCHEMA.version}


def _train_spec(cfg: PipelineConfig) -> Dict:
    return {"dataset": _dataset_spec(cfg), "surrogate": cfg.surrogate,
            "gnn_arch": cfg.gnn_arch, "hidden": cfg.hidden,
            "n_layers": cfg.n_layers, "epochs": cfg.epochs,
            "seed": cfg.seed, "use_critical_path": cfg.use_critical_path,
            "ensemble_members": cfg.ensemble_members,
            "ensemble_archs": cfg.ensemble_archs,
            "early_stop_patience": cfg.early_stop_patience}


def _engine_spec(cfg: PipelineConfig) -> Dict:
    # eval_devices is deliberately EXCLUDED (like dse_checkpoint_every
    # from the search spec): sharded engines are bit-identical to the
    # single-device one, so all widths share one cache slot. Consequence:
    # a memory-cached engine is NOT reconfigured by changing only that
    # knob — evict the engine key (or use a fresh store) to rebuild at a
    # different width.
    return {"train": _train_spec(cfg), "eval_chunk": cfg.eval_chunk,
            "use_kernel": cfg.use_kernel}


def _search_spec(cfg: PipelineConfig) -> Dict:
    return {"engine": _engine_spec(cfg), "sampler": cfg.sampler,
            "dse_budget": cfg.dse_budget, "dse_pop": cfg.dse_pop,
            "dse_islands": cfg.dse_islands,
            "dse_migrate_k": cfg.dse_migrate_k, "seed": cfg.seed}


def default_store(cfg: PipelineConfig) -> ArtifactStore:
    """Store for one run: on-disk at ``cfg.artifact_dir`` when set,
    otherwise in-process memory only."""
    return ArtifactStore(cfg.artifact_dir)


# --------------------------------------------------------------------------
# shared app-context helper (used by the stages AND validate_pareto)
# --------------------------------------------------------------------------

def app_context(app_name: str, theta: float = 0.15,
                store: Optional[ArtifactStore] = None) -> AppContext:
    """Pruned library -> app entries -> image set -> exact output.

    The setup that was copy-pasted between `run` and `validate_pareto`;
    memory-cached per (app, theta) when a store is given (`AccelDef` and
    the jax arrays are cheap to rebuild but not picklable, so this
    artifact never hits the disk tier)."""
    def build() -> AppContext:
        app = apps_lib.APPS[app_name]
        pruned, report = pruning.prune_library(theta=theta)
        entries = {k: pruned[k] for k in {n.kind for n in app.unit_nodes}}
        space = pruning.space_sizes(app, report)
        imgs = images_lib.image_set(4, 64)
        if app_name == "kmeans":
            inp = jnp.asarray(imgs.astype(np.int32))
        else:
            inp = jnp.asarray(images_lib.gray(imgs))
        exact_out = app.run(
            apps_lib.make_impls(app, apps_lib.exact_choice(app)), inp)
        return AppContext(app_name, app, entries, report, space, inp,
                          exact_out)

    if store is None:
        return build()
    key = store.key("prune", {"app": app_name, "theta": theta})
    return store.get_or_build("prune", key, build, memory_only=True)


# --------------------------------------------------------------------------
# stages
# --------------------------------------------------------------------------

def stage_prune(cfg: PipelineConfig, store: ArtifactStore) -> AppContext:
    """Design-space pruning + app ground-truth context (Sec III-A)."""
    return app_context(cfg.app, cfg.theta, store)


def stage_dataset(cfg: PipelineConfig, store: ArtifactStore,
                  ctx: AppContext) -> ds_lib.AccelDataset:
    """Labeled dataset over the pruned space (Sec III-B1); disk-cached."""
    key = store.key("dataset", _dataset_spec(cfg))
    return store.get_or_build("dataset", key, lambda: ds_lib.build(
        cfg.app, n_samples=cfg.n_samples, seed=cfg.seed,
        lib_entries=ctx.entries))


def _np_params(params):
    """jax -> numpy leaves so trained params pickle device-independently."""
    import jax
    return None if params is None else jax.tree.map(np.asarray, params)


def _np_ens(ens: Optional[training.EnsembleParams]):
    if ens is None:
        return None
    return training.EnsembleParams(
        groups=[(c, _np_params(p)) for c, p in ens.groups],
        member_arch=list(ens.member_arch))


def stage_train(cfg: PipelineConfig, store: ArtifactStore,
                ds: ds_lib.AccelDataset,
                verbose: bool = False) -> TrainArtifact:
    """Surrogate fitting (two-stage GNN / ensemble / RF baseline);
    disk-cached. ``surrogate="oracle"`` is a no-op artifact."""
    two_cfg = models.TwoStageConfig(
        gnn=gnn.GNNConfig(arch=cfg.gnn_arch, n_layers=cfg.n_layers,
                          hidden=cfg.hidden,
                          feature_dim=ds.x.shape[-1]),
        use_critical_path=cfg.use_critical_path,
        schema_version=getattr(ds, "schema_version", 1))

    def build() -> TrainArtifact:
        tr, te = ds.split(0.9)
        if cfg.surrogate == "gnn":
            tc = training.TrainConfig(epochs=cfg.epochs, seed=cfg.seed,
                                      patience=cfg.early_stop_patience)
            if cfg.ensemble_members > 0:
                ens, _hist = training.fit_ensemble(
                    two_cfg, tr, tc, n_members=cfg.ensemble_members,
                    archs=cfg.ensemble_archs)
                metrics = training.evaluate_ensemble(ens, ds, te)
                return TrainArtifact(two_cfg, metrics, ens=_np_ens(ens))
            params = training.fit_two_stage(
                two_cfg, tr, tc, log_every=0 if not verbose else 10)
            metrics = training.evaluate(two_cfg, params, ds, te)
            return TrainArtifact(two_cfg, metrics,
                                 params=_np_params(params))
        if cfg.surrogate == "rf":
            Xf_tr, Xf_te = tr.flat_features(), te.flat_features()
            rf_models: Dict[int, RandomForest] = {}
            metrics = {}
            for i, tname in enumerate(models.TARGETS):
                rf = RandomForest(seed=cfg.seed + i).fit(Xf_tr, tr.y[:, i])
                rf_models[i] = rf
                pred = rf.predict(Xf_te) * ds.y_std[i] + ds.y_mean[i]
                metrics[tname] = {
                    "r2": training.r2_score(te.y_raw[:, i], pred),
                    "mape": training.mape(te.y_raw[:, i], pred)}
            return TrainArtifact(two_cfg, metrics, rf_models=rf_models)
        return TrainArtifact(two_cfg, {})      # oracle: nothing to fit

    key = store.key("train", _train_spec(cfg))
    return store.get_or_build("train", key, build)


def stage_engine(cfg: PipelineConfig, store: ArtifactStore,
                 ctx: AppContext, ds: ds_lib.AccelDataset,
                 art: TrainArtifact) -> SurrogateEngine:
    """Surrogate-evaluation engine for the DSE loop; memory-cached (the
    engine holds jitted closures, so it never hits the disk tier — its
    inputs, params and dataset, are the disk-cached artifacts)."""
    def build() -> SurrogateEngine:
        if cfg.surrogate == "oracle":
            return SurrogateEngine.from_oracle(ctx.app, ctx.entries,
                                               ctx.inp, ctx.exact_out)
        if cfg.surrogate == "rf":
            return SurrogateEngine.from_rforest(art.rf_models, ds, ctx.app,
                                                ctx.entries)
        if art.ens is not None:
            return SurrogateEngine.from_gnn_ensemble(
                art.ens, ds, ctx.app, ctx.entries,
                chunk_size=cfg.eval_chunk, devices=cfg.eval_devices)
        return SurrogateEngine.from_gnn(art.two_cfg, art.params, ds,
                                        ctx.app, ctx.entries,
                                        chunk_size=cfg.eval_chunk,
                                        use_kernel=cfg.use_kernel,
                                        devices=cfg.eval_devices)

    key = store.key("engine", _engine_spec(cfg))
    return store.get_or_build("engine", key, build, memory_only=True)


def stage_search(cfg: PipelineConfig, store: ArtifactStore,
                 ctx: AppContext, engine: SurrogateEngine) -> dse.DSEResult:
    """NSGA-III / island DSE over the engine (Sec III-C); disk-cached.

    With ``cfg.dse_checkpoint_every > 0`` and a generational sampler
    (nsga2/nsga3/islands), the running search persists a
    `dse.SearchCheckpoint` into the store every N generations under a
    ``search_ckpt`` key; a rerun of the identical config (after a crash
    or kill) resumes from the last checkpoint and produces the
    bit-identical front/history the uninterrupted run would have. The
    checkpoint is evicted once the finished result is cached. The knob
    is deliberately EXCLUDED from the search cache key: checkpointed and
    plain runs yield the same result, so they share one cache slot."""
    # checkpoint key: same spec as the result key, different stage prefix
    ck_key = store.key("search_ckpt", _search_spec(cfg))
    can_ckpt = (cfg.dse_checkpoint_every > 0
                and cfg.sampler in ("nsga2", "nsga3", "islands"))

    def ckpt_kwargs() -> Dict:
        if not can_ckpt:
            return {}
        kw: Dict = {"checkpoint_every": cfg.dse_checkpoint_every,
                    "checkpoint_sink": lambda ck: store.put(ck_key, ck)}
        try:
            kw["resume_from"] = store.get(ck_key)
        except KeyError:
            pass
        return kw

    def build() -> dse.DSEResult:
        sizes = [len(ctx.entries[n.kind]) for n in ctx.app.unit_nodes]
        sampler = dse.SAMPLERS[cfg.sampler]
        if cfg.sampler == "islands":
            # dse_pop is the *global* population; islands split it evenly
            res = sampler(sizes, engine, cfg.dse_budget, seed=cfg.seed,
                          n_islands=cfg.dse_islands,
                          migrate_k=cfg.dse_migrate_k,
                          pop=max(2, cfg.dse_pop // cfg.dse_islands),
                          **ckpt_kwargs())
        elif cfg.sampler.startswith("nsga"):
            res = sampler(sizes, engine, cfg.dse_budget, seed=cfg.seed,
                          pop=cfg.dse_pop, **ckpt_kwargs())
        else:
            res = sampler(sizes, engine, cfg.dse_budget, seed=cfg.seed)
        if can_ckpt:
            store.evict(ck_key)      # finished: the result key takes over
        return res

    key = store.key("search", _search_spec(cfg))
    return store.get_or_build("search", key, build)


# --------------------------------------------------------------------------
# orchestration: the staged path and the legacy wrapper
# --------------------------------------------------------------------------

def run_staged(cfg: PipelineConfig, store: Optional[ArtifactStore] = None,
               verbose: bool = False) -> PipelineResult:
    """Execute the stage graph against an artifact store.

    Pass a shared ``store`` to amortize datasets/params/engines across
    runs and sweeps; with ``store=None`` a fresh store is created per call
    (memory-only unless ``cfg.artifact_dir`` is set), which reproduces the
    legacy from-scratch `run()` semantics exactly."""
    store = store if store is not None else default_store(cfg)
    t: Dict[str, float] = {}
    # snapshot so metrics["store"] reports THIS run's hits/misses even on
    # a shared store carrying counters from earlier runs
    hits0 = dict(store.stats.hits)
    miss0 = dict(store.stats.misses)

    t0 = time.time()
    ctx = stage_prune(cfg, store)
    t["prune"] = time.time() - t0

    t0 = time.time()
    ds = stage_dataset(cfg, store, ctx)
    t["dataset"] = time.time() - t0

    t0 = time.time()
    art = stage_train(cfg, store, ds, verbose=verbose)
    t["train"] = time.time() - t0

    engine = stage_engine(cfg, store, ctx, ds, art)

    t0 = time.time()
    res = stage_search(cfg, store, ctx, engine)
    t["dse"] = time.time() - t0

    metrics = dict(art.metrics)
    metrics["engine"] = {"backend": engine.backend,
                         **engine.stats.as_dict()}
    metrics["dse_history"] = res.history
    metrics["store"] = {
        "hits": {k: v - hits0.get(k, 0)
                 for k, v in store.stats.hits.items()
                 if v - hits0.get(k, 0)},
        "misses": {k: v - miss0.get(k, 0)
                   for k, v in store.stats.misses.items()
                   if v - miss0.get(k, 0)}}
    if art.ens is not None and res.pareto_configs:
        # ensemble std on the selected points: the uncertainty column the
        # acquisition path sees, served from the engine's memo cache
        unc = engine.uncertainty(res.pareto_configs)
        metrics["pareto_uncertainty"] = {
            n: float(unc[:, i].mean()) for i, n in enumerate(OBJ_NAMES)}

    return PipelineResult(cfg, ctx.report, ctx.space, metrics,
                          res.pareto_configs, res.pareto_objs, t, ds,
                          engine)


def run(cfg: PipelineConfig, verbose: bool = False) -> PipelineResult:
    """Legacy single-call entry point: a thin wrapper over `run_staged`
    with a per-call store (see tests/test_pipeline_stages.py for the
    staged-vs-wrapper parity assertions)."""
    return run_staged(cfg, store=None, verbose=verbose)


def _oracle_eval(app, entries, inp, exact_out):
    """Ground-truth evaluator on the batched labeling path (vectorized
    synthesis oracle + config-batched LUT functional model)."""
    from repro.accel import batch_oracle

    def evaluate(configs: Sequence[Tuple[int, ...]]) -> np.ndarray:
        return batch_oracle.objective_rows(app, entries, configs, inp,
                                           exact_out)
    return evaluate


def validate_pareto(result: PipelineResult, k: int = 10,
                    store: Optional[ArtifactStore] = None
                    ) -> Dict[str, float]:
    """Oracle-check k Pareto points: surrogate error on selected designs.

    Uses the shared `app_context` helper; pass the run's store to reuse
    its cached pruning/ground-truth context."""
    cfg = result.cfg
    ctx = app_context(cfg.app, cfg.theta, store)
    oracle = _oracle_eval(ctx.app, ctx.entries, ctx.inp, ctx.exact_out)
    sel = result.pareto_configs[:k]
    if not sel:
        return {"mean_rel_err": float("nan")}
    true = oracle(sel)
    pred = result.pareto_objs[:len(sel)]
    rel = np.abs(pred - true) / np.maximum(np.abs(true), 1e-6)
    return {"mean_rel_err": float(rel.mean()),
            "per_obj": {n: float(rel[:, i].mean())
                        for i, n in enumerate(OBJ_NAMES)}}


# --------------------------------------------------------------------------
# cross-app unified surrogate (staged; ApproxGNN-style shared pretraining)
# --------------------------------------------------------------------------

@dataclass
class UnifiedResult:
    """One shared two-stage GNN over several apps + per-app engine views."""
    two_cfg: models.TwoStageConfig
    params: models.TwoStageParams
    merged: ds_lib.MergedDataset
    metrics: Dict[str, Dict]               # union test split + per_app
    engines: Dict[str, SurrogateEngine]    # per-app views, shared params
    timings: Dict[str, float]


def unified_surrogate(apps: Sequence[str], cfg: PipelineConfig,
                      store: Optional[ArtifactStore] = None,
                      split: float = 0.9) -> UnifiedResult:
    """Train (or reuse) ONE cross-app surrogate and its per-app engines.

    Runs the cached prune/dataset stages per app, merges them
    (`dataset.merge`: common pad width + app-identity block), fits one
    shared two-stage GNN over the union (disk-cached against the app set
    and the train config slice), and serves each app through
    `SurrogateEngine.from_gnn_shared`. Adding a new scenario later reuses
    every other app's cached dataset — only the merged fit reruns."""
    if len(apps) < 1:
        raise ValueError("unified_surrogate needs at least one app")
    if cfg.surrogate != "gnn" or cfg.ensemble_members > 0:
        raise ValueError(
            "unified_surrogate fits one shared two-stage GNN; "
            f"surrogate={cfg.surrogate!r} / ensemble_members="
            f"{cfg.ensemble_members} are not supported here")
    store = store if store is not None else default_store(cfg)
    t: Dict[str, float] = {}

    t0 = time.time()
    per_cfg = {a: dataclasses.replace(cfg, app=a) for a in apps}
    ctxs = {a: stage_prune(per_cfg[a], store) for a in apps}
    datasets = {a: stage_dataset(per_cfg[a], store, ctxs[a]) for a in apps}
    t["datasets"] = time.time() - t0

    two_cfg = models.TwoStageConfig(
        gnn=gnn.GNNConfig(arch=cfg.gnn_arch, n_layers=cfg.n_layers,
                          hidden=cfg.hidden,
                          feature_dim=graph_lib.MERGED_FEATURE_DIM),
        use_critical_path=cfg.use_critical_path,
        schema_version=getattr(
            datasets[next(iter(apps))], "schema_version", 1))
    tc = training.TrainConfig(epochs=cfg.epochs, seed=cfg.seed,
                              patience=cfg.early_stop_patience)
    n_pad = max(d.x.shape[1] for d in datasets.values())

    fresh: Dict[str, ds_lib.MergedDataset] = {}

    def build():
        params, merged0, metrics = training.fit_unified(
            datasets, two_cfg, tc, split=split, n_pad=n_pad)
        fresh["merged"] = merged0
        return {"params": _np_params(params), "metrics": metrics}

    # only the fields the unified fit actually consumes (NOT the full
    # train slice: surrogate/ensemble knobs are rejected above, and
    # hashing unread fields would miss the cache for identical fits)
    spec = {"apps": sorted(apps), "split": split,
            "datasets": {a: _dataset_spec(per_cfg[a]) for a in apps},
            "train": {"gnn_arch": cfg.gnn_arch, "hidden": cfg.hidden,
                      "n_layers": cfg.n_layers, "epochs": cfg.epochs,
                      "seed": cfg.seed,
                      "use_critical_path": cfg.use_critical_path,
                      "early_stop_patience": cfg.early_stop_patience}}
    t0 = time.time()
    fit = store.get_or_build("train_unified",
                             store.key("train_unified", spec), build)
    t["train"] = time.time() - t0
    # the merged dataset is deterministic given the per-app datasets: on
    # a cache miss reuse the one the fit just built, on a hit rebuild it
    # (cheaper than storing the union tensors twice)
    merged = fresh.get("merged") or ds_lib.merge(datasets, n_pad=n_pad)

    t0 = time.time()
    engines = {a: SurrogateEngine.from_gnn_shared(
        two_cfg, fit["params"], merged, a, ctxs[a].entries,
        chunk_size=cfg.eval_chunk, devices=cfg.eval_devices)
        for a in apps}
    t["engines"] = time.time() - t0
    return UnifiedResult(two_cfg, fit["params"], merged, fit["metrics"],
                         engines, t)
