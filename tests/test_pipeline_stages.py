"""Staged pipeline, artifact store, and cross-app unified surrogate.

Covers the ISSUE-5 acceptance criteria:
* staged-vs-legacy parity (metrics + identical Pareto configs, two apps);
* cache-resume: a second run with the same config hits the artifact
  cache for the dataset + train stages;
* `validate_pareto` (previously untested) — exactness on the oracle
  surrogate, structure on the GNN surrogate;
* `dataset.merge` layout, `evaluate_transfer` (fine-tune beats zero-shot),
  per-app engine views off shared params;
* the `pad_batch` empty-list guard and the `PipelineResult.engine`
  rename (with the deprecated `predictor` alias).
"""
import dataclasses

import numpy as np
import pytest

from repro.core import dataset as ds_lib
from repro.core import gnn, graph, models, training
from repro.core import pipeline as P
from repro.core.artifacts import ArtifactStore, stable_hash
from repro.core.engine import SurrogateEngine

TINY = dict(n_samples=120, epochs=4, dse_budget=100, hidden=32,
            n_layers=2, dse_pop=16)


def tiny_cfg(app="sobel", **kw):
    return P.PipelineConfig(app=app, **{**TINY, **kw})


@pytest.fixture(scope="module")
def sobel_run():
    return P.run(tiny_cfg())


# --------------------------------------------------------------------------
# artifact store
# --------------------------------------------------------------------------

def test_stable_hash_deterministic_and_order_insensitive():
    a = {"app": "sobel", "n": 5, "nested": {"x": 1.5, "y": (1, 2)}}
    b = {"nested": {"y": [1, 2], "x": 1.5}, "n": 5, "app": "sobel"}
    assert stable_hash(a) == stable_hash(b)
    assert stable_hash(a) != stable_hash({**a, "n": 6})


def test_stable_hash_rejects_address_bearing_values():
    class Opaque:
        pass
    with pytest.raises(TypeError, match="non-canonicalizable"):
        stable_hash({"evaluator": Opaque()})


def test_dataset_pickle_is_compact_and_round_trips(small_datasets):
    import pickle
    ds = small_datasets["sobel"]
    blob = pickle.dumps(ds)
    # constant-row adj/mask collapse: far smaller than the dense tensors
    dense = ds.adj.nbytes + ds.mask.nbytes + ds.unit_mask.nbytes
    assert len(blob) < dense
    back = pickle.loads(blob)
    for k in ("adj", "x", "mask", "unit_mask", "y", "y_raw", "crit"):
        np.testing.assert_array_equal(getattr(back, k), getattr(ds, k))
    assert back.configs == ds.configs


def test_store_disk_roundtrip_and_stats(tmp_path):
    store = ArtifactStore(str(tmp_path))
    key = store.key("dataset", {"app": "sobel", "n": 3})
    assert not store.has(key)
    built = store.get_or_build("dataset", key,
                               lambda: {"arr": np.arange(4)})
    assert store.stats.misses["dataset"] == 1
    # a FRESH store on the same root serves it from disk
    store2 = ArtifactStore(str(tmp_path))
    again = store2.get_or_build("dataset", key, lambda: 1 / 0)
    np.testing.assert_array_equal(again["arr"], built["arr"])
    assert store2.stats.hits["dataset"] == 1


def test_store_memory_only_never_hits_disk(tmp_path):
    store = ArtifactStore(str(tmp_path))
    key = store.key("engine", {"x": 1})
    store.get_or_build("engine", key, lambda: object(), memory_only=True)
    assert list(tmp_path.glob("*.pkl")) == []
    assert store.has(key)                     # memory tier still serves it


_CACHE_SCRIPT = """
import json, jax, jax.numpy as jnp
from repro.core import artifacts
path = artifacts.enable_compilation_cache()
if path != str(artifacts.DEFAULT_COMPILE_CACHE):
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(5.0)).block_until_ready()
print(json.dumps([path, jax.config.jax_compilation_cache_dir]))
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_compilation_cache_directory(tmp_path, from_env):
    """With JAX_COMPILATION_CACHE_DIR set, executables go there and the
    helper sets no directory of its own; without it, one fixed directory
    inside the checkout."""
    import json
    import os
    import subprocess
    import sys
    from pathlib import Path

    from repro.core import artifacts

    env = dict(os.environ, PYTHONPATH=str(Path(artifacts.__file__)
                                          .resolve().parents[2]))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jcc")
    r = subprocess.run([sys.executable, "-c", _CACHE_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    path, configured = json.loads(r.stdout.splitlines()[-1])
    want = (str(tmp_path / "jcc") if from_env
            else str(artifacts.DEFAULT_COMPILE_CACHE))
    assert path == configured == want
    if from_env:
        assert any((tmp_path / "jcc").iterdir())
    else:
        assert artifacts.DEFAULT_COMPILE_CACHE.is_dir()
        assert (artifacts.DEFAULT_COMPILE_CACHE.parent / "src").is_dir()


def test_store_leaves_the_compile_cache_alone(tmp_path):
    import jax

    before = jax.config.jax_compilation_cache_dir
    ArtifactStore(str(tmp_path))
    assert jax.config.jax_compilation_cache_dir == before
    assert not (tmp_path / "xla_cache").exists()


def test_store_key_spec_sensitivity():
    c1, c2 = tiny_cfg(), tiny_cfg(dse_budget=999)
    # dse_budget is a search-stage knob: dataset/train keys must not move
    assert ArtifactStore.key("dataset", P._dataset_spec(c1)) == \
        ArtifactStore.key("dataset", P._dataset_spec(c2))
    assert ArtifactStore.key("train", P._train_spec(c1)) == \
        ArtifactStore.key("train", P._train_spec(c2))
    assert ArtifactStore.key("search", P._search_spec(c1)) != \
        ArtifactStore.key("search", P._search_spec(c2))
    # n_samples invalidates everything downstream of the dataset
    c3 = tiny_cfg(n_samples=77)
    assert ArtifactStore.key("dataset", P._dataset_spec(c1)) != \
        ArtifactStore.key("dataset", P._dataset_spec(c3))
    assert ArtifactStore.key("train", P._train_spec(c1)) != \
        ArtifactStore.key("train", P._train_spec(c3))


# --------------------------------------------------------------------------
# staged pipeline: parity + cache resume
# --------------------------------------------------------------------------

@pytest.mark.parametrize("app", ["sobel", "dct8"])
def test_staged_matches_legacy_run(app, sobel_run):
    cfg = tiny_cfg(app)
    legacy = sobel_run if app == "sobel" else P.run(cfg)
    store = ArtifactStore(None)
    ctx = P.stage_prune(cfg, store)
    ds = P.stage_dataset(cfg, store, ctx)
    art = P.stage_train(cfg, store, ds)
    engine = P.stage_engine(cfg, store, ctx, ds, art)
    res = P.stage_search(cfg, store, ctx, engine)
    # identical Pareto front and equivalent metrics for the fixed seed
    assert res.pareto_configs == legacy.pareto_configs
    np.testing.assert_allclose(res.pareto_objs, legacy.pareto_objs,
                               rtol=1e-6)
    for t in models.TARGETS:
        assert art.metrics[t]["r2"] == pytest.approx(
            legacy.metrics[t]["r2"], abs=1e-6)
    assert art.metrics["critical_path"]["accuracy"] == pytest.approx(
        legacy.metrics["critical_path"]["accuracy"], abs=1e-9)


def test_second_run_hits_dataset_and_train_cache(tmp_path):
    cfg = tiny_cfg(artifact_dir=str(tmp_path))
    r1 = P.run(cfg)
    assert r1.metrics["store"]["hits"] == {}
    r2 = P.run(cfg)
    hits = r2.metrics["store"]["hits"]
    assert hits.get("dataset") == 1 and hits.get("train") == 1
    assert r2.pareto_configs == r1.pareto_configs
    np.testing.assert_array_equal(r2.pareto_objs, r1.pareto_objs)


def test_shared_store_sweep_reuses_dataset_and_train():
    """A DSE sweep (same surrogate, different budget) must only re-search."""
    store = ArtifactStore(None)
    P.run_staged(tiny_cfg(), store=store)
    r2 = P.run_staged(tiny_cfg(dse_budget=160), store=store)
    assert store.stats.hits.get("dataset") == 1
    assert store.stats.hits.get("train") == 1
    assert store.stats.misses.get("search") == 2
    # metrics["store"] is per-run (delta), not the shared cumulative view
    assert r2.metrics["store"] == {
        "hits": {"prune": 1, "dataset": 1, "train": 1, "engine": 1},
        "misses": {"search": 1}}


def test_cached_params_round_trip_through_disk(tmp_path):
    """Params reloaded from the disk tier drive an engine to the same
    objective rows as the fresh in-memory fit."""
    cfg = tiny_cfg(artifact_dir=str(tmp_path))
    r1 = P.run(cfg)
    # fresh process-equivalent: new store over the same root
    store = ArtifactStore(str(tmp_path))
    ctx = P.stage_prune(cfg, store)
    ds = P.stage_dataset(cfg, store, ctx)
    art = P.stage_train(cfg, store, ds)
    assert store.stats.hits.get("train") == 1
    engine = P.stage_engine(cfg, store, ctx, ds, art)
    probe = r1.pareto_configs[:4]
    np.testing.assert_allclose(engine(probe), r1.engine(probe), rtol=1e-6)


def test_run_staged_oracle_and_rf_surrogates():
    store = ArtifactStore(None)
    r_rf = P.run_staged(tiny_cfg(surrogate="rf", dse_budget=60),
                        store=store)
    assert r_rf.engine.backend == "rforest"
    r_or = P.run_staged(tiny_cfg(surrogate="oracle", dse_budget=60,
                                 n_samples=40, epochs=1), store=store)
    assert r_or.engine.backend == "oracle"
    assert len(r_or.pareto_configs) > 0


# --------------------------------------------------------------------------
# validate_pareto (previously untested)
# --------------------------------------------------------------------------

def test_validate_pareto_oracle_engine_is_exact():
    """With the oracle surrogate the 'prediction' IS the ground truth, so
    the oracle re-check must report ~zero relative error."""
    cfg = tiny_cfg(surrogate="oracle", n_samples=40, epochs=1,
                   dse_budget=60)
    res = P.run(cfg)
    val = P.validate_pareto(res, k=5)
    assert val["mean_rel_err"] < 1e-6
    assert set(val["per_obj"]) == set(P.OBJ_NAMES)


def test_validate_pareto_gnn_engine_reports_finite_error(sobel_run):
    val = P.validate_pareto(sobel_run, k=5)
    assert np.isfinite(val["mean_rel_err"]) and val["mean_rel_err"] >= 0
    assert all(np.isfinite(v) for v in val["per_obj"].values())


def test_validate_pareto_empty_front_is_nan():
    res = dataclasses.replace(
        P.run(tiny_cfg(surrogate="oracle", n_samples=40, epochs=1,
                       dse_budget=60)),
        pareto_configs=[], pareto_objs=np.zeros((0, 4)))
    assert np.isnan(P.validate_pareto(res)["mean_rel_err"])


def test_validate_pareto_reuses_store_context(sobel_run):
    store = ArtifactStore(None)
    P.app_context("sobel", sobel_run.cfg.theta, store)
    P.validate_pareto(sobel_run, k=3, store=store)
    assert store.stats.hits.get("prune") == 1


# --------------------------------------------------------------------------
# satellite fixes: pad_batch guard + engine rename
# --------------------------------------------------------------------------

def test_pad_batch_empty_list_returns_empty_tensors():
    A, X, M = graph.pad_batch([], [], n_pad=8)
    assert A.shape == (0, 8, 8)
    assert X.shape == (0, 8, graph.FEATURE_DIM)
    assert M.shape == (0, 8)
    A2, X2, _ = graph.pad_batch([], [], n_pad=8, feature_dim=5)
    assert X2.shape == (0, 8, 5)


def test_pad_batch_mismatched_lengths_raise():
    with pytest.raises(ValueError, match="pad_batch"):
        graph.pad_batch([np.eye(2, dtype=np.float32)], [], n_pad=4)


def test_result_engine_field_and_predictor_alias(sobel_run):
    assert isinstance(sobel_run.engine, SurrogateEngine)
    assert sobel_run.predictor is sobel_run.engine


# --------------------------------------------------------------------------
# cross-app unified surrogate
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_datasets():
    return {a: ds_lib.build(a, n_samples=100, seed=0)
            for a in ("sobel", "gaussian", "dct8")}


def test_merge_layout_and_bookkeeping(small_datasets):
    merged = ds_lib.merge(small_datasets)
    B = sum(len(d.y) for d in small_datasets.values())
    assert merged.x.shape == (B, merged.n_pad, graph.MERGED_FEATURE_DIM)
    # app order follows APP_VOCAB, rows shuffled but tracked by app_ids
    assert merged.app_names == ("sobel", "gaussian", "dct8")
    assert sorted(np.unique(merged.app_ids)) == [0, 1, 2]
    # one-hot block: on real nodes of app a, exactly its APP_VOCAB
    # column fires (vocab position, NOT position within the subset)
    for i, a in enumerate(merged.app_names):
        rows = merged.app_ids == i
        block = merged.x[rows][..., graph.FEATURE_DIM:]
        m = merged.mask[rows]
        np.testing.assert_array_equal(block[..., graph.APP_VOCAB.index(a)],
                                      m)
        assert block.sum() == m.sum()          # no other column fires
        # base features / labels survive the merge bit-exactly
        view = merged.view(a)
        np.testing.assert_allclose(
            np.sort(view.y_raw, 0),
            np.sort(small_datasets[a].y_raw, 0), rtol=1e-6)


def test_merge_single_app_keeps_layout(small_datasets):
    one = ds_lib.merge({"sobel": small_datasets["sobel"]}, n_pad=32)
    assert one.x.shape[-1] == graph.MERGED_FEATURE_DIM
    assert one.n_pad == 32


def test_merge_pads_square_feature_tensor_correctly():
    """A dataset built at n_pad == FEATURE_DIM has a square (B, 21, 21)
    feature tensor; padding must widen only the NODE axis (regression:
    shape-sniffed adjacency padding hit both axes)."""
    ds = ds_lib.build("sobel", n_samples=20, seed=0,
                      n_pad=graph.FEATURE_DIM)
    assert ds.x.shape[1] == ds.x.shape[2] == graph.FEATURE_DIM
    merged = ds_lib.merge({"sobel": ds}, n_pad=32)
    assert merged.x.shape[1:] == (32, graph.MERGED_FEATURE_DIM)
    assert merged.adj.shape[1:] == (32, 32)


def test_merge_split_mixes_apps(small_datasets):
    tr, te = ds_lib.merge(small_datasets).split(0.9)
    assert len(np.unique(tr.app_ids)) == 3
    assert len(np.unique(te.app_ids)) == 3


def test_merge_rejects_empty_and_unknown():
    with pytest.raises(ValueError):
        ds_lib.merge({})
    with pytest.raises(ValueError):
        graph.app_block("not-an-app", np.ones(4, np.float32))


def test_unified_fit_and_engine_views(small_datasets):
    cfg = models.TwoStageConfig(gnn=gnn.GNNConfig(
        arch="gsae", n_layers=2, hidden=32,
        feature_dim=graph.MERGED_FEATURE_DIM))
    tc = training.TrainConfig(epochs=6, seed=0)
    params, merged, metrics = training.fit_unified(small_datasets, cfg, tc)
    assert set(metrics["per_app"]) == set(merged.app_names)
    for t in models.TARGETS:
        assert np.isfinite(metrics[t]["r2"])
    # per-app engine views serve finite objective rows off shared params
    pruned, _ = __import__("repro.core.pruning",
                           fromlist=["prune_library"]).prune_library()
    from repro.accel import apps as apps_lib
    for a in merged.app_names:
        app = apps_lib.APPS[a]
        entries = {k: pruned[k] for k in {n.kind for n in app.unit_nodes}}
        eng = SurrogateEngine.from_gnn_shared(cfg, params, merged, a,
                                              entries)
        y = eng([tuple(0 for _ in app.unit_nodes),
                 tuple(1 for _ in app.unit_nodes)])
        assert y.shape == (2, 4) and np.isfinite(y).all()


def test_fit_unified_rejects_wrong_feature_dim(small_datasets):
    cfg = models.TwoStageConfig(gnn=gnn.GNNConfig(
        arch="gsae", n_layers=2, hidden=32,
        feature_dim=graph.FEATURE_DIM))
    with pytest.raises(ValueError, match="feature_dim"):
        training.fit_unified(small_datasets, cfg)


def test_evaluate_transfer_finetune_beats_zero_shot(small_datasets):
    """Leave-one-app-out: all four objectives reported for both legs, and
    the warm-started fine-tune improves on zero-shot (fixed seeds)."""
    cfg = models.TwoStageConfig(gnn=gnn.GNNConfig(
        arch="gsae", n_layers=2, hidden=32,
        feature_dim=graph.MERGED_FEATURE_DIM))
    tc = training.TrainConfig(epochs=8, seed=0)
    rep = training.evaluate_transfer(small_datasets, "gaussian", cfg, tc,
                                     finetune_epochs=8)
    assert rep["holdout"] == "gaussian"
    assert rep["shared_apps"] == ["dct8", "sobel"]
    for leg in ("zero_shot", "fine_tuned"):
        for t in models.TARGETS:
            assert np.isfinite(rep[leg][t]["r2"])
            assert np.isfinite(rep[leg][t]["mape"])
    zs = np.mean([rep["zero_shot"][t]["mape"] for t in models.TARGETS])
    ft = np.mean([rep["fine_tuned"][t]["mape"] for t in models.TARGETS])
    assert ft < zs


def test_evaluate_transfer_rejects_bad_holdout(small_datasets):
    cfg = models.TwoStageConfig(gnn=gnn.GNNConfig(
        feature_dim=graph.MERGED_FEATURE_DIM))
    with pytest.raises(ValueError):
        training.evaluate_transfer(small_datasets, "nope", cfg)
    with pytest.raises(ValueError):
        training.evaluate_transfer(
            {"sobel": small_datasets["sobel"]}, "sobel", cfg)


def test_unified_surrogate_rejects_non_gnn_surrogates():
    with pytest.raises(ValueError, match="shared two-stage GNN"):
        P.unified_surrogate(["sobel"], P.PipelineConfig(surrogate="rf"))
    with pytest.raises(ValueError, match="shared two-stage GNN"):
        P.unified_surrogate(["sobel"],
                            P.PipelineConfig(ensemble_members=4))


def test_unified_surrogate_staged_caching(tmp_path, small_datasets):
    cfg = P.PipelineConfig(n_samples=100, epochs=4, hidden=32, n_layers=2,
                           artifact_dir=str(tmp_path))
    u1 = P.unified_surrogate(["sobel", "dct8"], cfg)
    store = ArtifactStore(str(tmp_path))
    u2 = P.unified_surrogate(["sobel", "dct8"], cfg, store=store)
    assert store.stats.hits.get("dataset") == 2
    assert store.stats.hits.get("train_unified") == 1
    # the cached params serve the same predictions
    app = __import__("repro.accel.apps", fromlist=["APPS"]).APPS["sobel"]
    probe = [tuple(0 for _ in app.unit_nodes)]
    np.testing.assert_allclose(u1.engines["sobel"](probe),
                               u2.engines["sobel"](probe), rtol=1e-6)
    # onboarding a third app reuses the two cached datasets
    store3 = ArtifactStore(str(tmp_path))
    P.unified_surrogate(["sobel", "dct8", "gaussian"], cfg, store=store3)
    assert store3.stats.hits.get("dataset") == 2
    assert store3.stats.misses.get("dataset") == 1
    assert store3.stats.misses.get("train_unified") == 1
