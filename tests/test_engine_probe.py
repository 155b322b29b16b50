"""The GNN engines keep the functional probe's result on the device.

On the engine's path (`ConfigFeaturizer.normalized_on_device`) the prefetch
worker only dispatches the probe; each GNN backend's forward splices the
standardized distortion into the probe columns inside its own program
(`engine._probe_splice`), and ``collect`` checks the probe's LUT guards.
Proven here for sobel (analytic adders only) and kmeans (LUT units):

* the spliced features equal `ConfigFeaturizer.normalized` bit for bit in
  every column but the probe's, and within one float32 ulp there (the
  device's divide may round the standardization differently);
* the pipelined `from_gnn`, `from_gnn_shared` and `from_gnn_ensemble`
  engines serve the rows of their forward applied to `normalized`
  features, and count every pipelined chunk in ``probe_on_device``; a
  host-featurized engine counts none;
* a LUT-domain overflow inside the probe still raises `LutDomainError`
  from ``engine(configs)``.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.accel import apps as apps_lib
from repro.accel import library as lib
from repro.core import dataset as ds_lib
from repro.core import gnn, graph, models, pruning, training
from repro.core.engine import (SurrogateEngine, _make_jax_predict,
                               _probe_splice)

CHUNK, N_CONFIGS = 16, 48          # three chunks: the pipelined path


def _configs(app, entries, n, seed):
    rng = np.random.default_rng(seed)
    sizes = [len(entries[node.kind]) for node in app.unit_nodes]
    return [tuple(int(rng.integers(0, s)) for s in sizes) for _ in range(n)]


def _two_cfg(feature_dim):
    return models.TwoStageConfig(gnn=gnn.GNNConfig(
        arch="gsae", n_layers=2, hidden=16, feature_dim=feature_dim))


def _objectives(ds, y):
    y = ds.denorm_y(np.asarray(y))
    y[:, 3] = 1 - y[:, 3]
    return y


@functools.lru_cache(maxsize=None)
def _surrogate(app_name):
    pruned, _ = pruning.prune_library()
    app = apps_lib.APPS[app_name]
    entries = {k: pruned[k] for k in {n.kind for n in app.unit_nodes}}
    ds = ds_lib.build(app_name, n_samples=24, seed=0, lib_entries=entries)
    two_cfg = _two_cfg(ds.x.shape[-1])
    params = models.init(jax.random.PRNGKey(0), two_cfg)
    return app, entries, ds, two_cfg, params


@pytest.fixture(scope="module", params=["sobel", "kmeans"])
def surrogate(request):
    return _surrogate(request.param)


def test_spliced_features_match_normalized(surrogate):
    app, entries, ds, _, _ = surrogate
    feat = ds_lib.featurizer_for(ds, app, entries)
    cfgs = _configs(app, entries, 20, seed=4)
    X, probe = feat.normalized_on_device(cfgs)
    got = np.asarray(jax.jit(_probe_splice(feat))((X, probe.ssim)))
    want = feat.normalized(cfgs)
    cols = [c for c, _, _ in feat.probe_columns()]
    assert len(cols) == len(apps_lib.PROBE_SIZES) == len(probe.ssim)
    other = np.setdiff1d(np.arange(X.shape[-1]), cols)
    np.testing.assert_array_equal(got[..., other], want[..., other])
    np.testing.assert_array_equal(got[:, feat.n_nodes:],
                                  want[:, feat.n_nodes:])
    real = got[:, :feat.n_nodes][..., cols]
    np.testing.assert_array_max_ulp(real, want[:, :feat.n_nodes][..., cols],
                                    maxulp=1)
    # the splice wrote the probe: the host's placeholder is not the answer
    assert not np.array_equal(X[:, :feat.n_nodes][..., cols], real)


def test_pipelined_gnn_engine_serves_the_forward_on_normalized(surrogate):
    app, entries, ds, two_cfg, params = surrogate
    eng = SurrogateEngine.from_gnn(two_cfg, params, ds, app, entries,
                                   chunk_size=CHUNK, use_kernel="off")
    cfgs = _configs(app, entries, N_CONFIGS, seed=5)
    rows = eng(cfgs)
    feat = ds_lib.featurizer_for(ds, app, entries)
    forward = _make_jax_predict(two_cfg, params, feat.adj, feat.mask)
    want = _objectives(ds, forward(feat.normalized(cfgs)))
    np.testing.assert_allclose(rows, want, rtol=1e-5, atol=1e-5)
    assert eng.stats.chunks == N_CONFIGS // CHUNK
    assert eng.stats.probe_on_device == eng.stats.chunks
    assert eng.stats.as_dict()["probe_on_device"] == eng.stats.chunks


def test_pipelined_shared_engine_serves_the_forward_on_normalized(surrogate):
    app, entries, ds, _, _ = surrogate
    merged = ds_lib.merge({app.name: ds})
    two_cfg = _two_cfg(graph.MERGED_FEATURE_DIM)
    params = models.init(jax.random.PRNGKey(1), two_cfg)
    eng = SurrogateEngine.from_gnn_shared(two_cfg, params, merged, app.name,
                                          entries, chunk_size=CHUNK)
    cfgs = _configs(app, entries, N_CONFIGS, seed=6)
    rows = eng(cfgs)
    view = merged.per_app[app.name]
    feat = ds_lib.ConfigFeaturizer(view.graph, app, entries, merged.n_pad,
                                   schema=view.schema)
    feat.set_norm(view.x_mean, view.x_std)
    X = feat.normalized(cfgs)
    block = graph.app_block(app.name, feat.mask)
    Xa = np.concatenate([X, np.broadcast_to(block, (len(X),) + block.shape)],
                        -1)
    forward = _make_jax_predict(two_cfg, params, feat.adj, feat.mask)
    np.testing.assert_allclose(rows, _objectives(view, forward(Xa)),
                               rtol=1e-5, atol=1e-5)
    assert eng.stats.probe_on_device == eng.stats.chunks == 3


def test_pipelined_ensemble_engine_serves_the_forward_on_normalized(
        surrogate):
    app, entries, ds, two_cfg, _ = surrogate
    members = [models.init(jax.random.PRNGKey(k), two_cfg) for k in (2, 3)]
    stacked = jax.tree.map(lambda *a: jnp.stack(a), *members)
    ens = training.EnsembleParams([(two_cfg, stacked)], ["gsae", "gsae"])
    eng = SurrogateEngine.from_gnn_ensemble(ens, ds, app, entries,
                                            chunk_size=CHUNK)
    cfgs = _configs(app, entries, N_CONFIGS, seed=7)
    mean, std = eng.predict_with_uncertainty(cfgs)
    A, X, M = ds_lib.features_for_configs(ds, app, entries, cfgs)
    want_mean, want_std, _ = training.ensemble_predict(ens, A, X, M)
    np.testing.assert_allclose(mean, _objectives(ds, want_mean),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(std, np.asarray(want_std) * ds.y_std,
                               rtol=1e-5, atol=1e-5)
    assert eng.stats.probe_on_device == eng.stats.chunks == 3


def test_host_featurized_engine_keeps_no_probe_on_device(surrogate):
    from repro.core.rforest import RandomForest

    app, entries, ds, _, _ = surrogate
    Xf = ds.flat_features()
    rf = {i: RandomForest(n_trees=2, seed=i).fit(Xf, ds.y[:, i])
          for i in range(4)}
    eng = SurrogateEngine.from_rforest(rf, ds, app, entries, chunk_size=8)
    eng(_configs(app, entries, 24, seed=8))
    assert eng.stats.chunks == 3
    assert eng.stats.probe_on_device == 0


def test_probe_lut_overflow_raises_from_pipelined_engine(monkeypatch):
    app, entries, ds, two_cfg, params = _surrogate("kmeans")
    # a copy without the dataset's featurizer cache: the engine's
    # featurizer resolves the probe's labeler on its first chunk, after
    # the domain below is narrowed
    ds = dataclasses.replace(ds)
    eng = SurrogateEngine.from_gnn(two_cfg, params, ds, app, entries,
                                   chunk_size=CHUNK, use_kernel="off")
    # kmeans' mul8 operands reach |sub10| <= 383; a 2^4 table cannot hold
    monkeypatch.setitem(lib.APP_LUT_DOMAINS, ("kmeans", "mul8"), (4, 4))
    apps_lib._batch_label_fn.cache_clear()
    try:
        with pytest.raises(apps_lib.LutDomainError, match="mul8"):
            eng(_configs(app, entries, N_CONFIGS, seed=9))
    finally:
        apps_lib._batch_label_fn.cache_clear()
    # the overflow came through the device probe, found at collect
    assert eng.stats.probe_on_device == N_CONFIGS // CHUNK
