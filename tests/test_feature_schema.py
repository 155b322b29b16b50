"""Tests for the versioned FeatureSchema and the dynamic timing block.

Covers the tentpole invariants of the schema refactor:

* schema bookkeeping — block layout, derived indices, v1/v2 dims, the
  back-compat constant aliases;
* batched == scalar timing: `batch_oracle.timing_batch` per-node
  slack / criticality / crit bits are EXACTLY the scalar
  `synth.static_timing` values on hypothesis-driven random configs
  (max/min/sub/div over identical operands are IEEE-exact); the
  DAG-propagated error features agree to float tolerance (summation
  order differs);
* build-path / hot-path bit identity: `ConfigFeaturizer.normalized`
  with dynamic features returns rows bit-identical to the tensors
  `dataset.build` produced for the same configs;
* `dataset.merge` rejects mixed schema versions;
* `sample_configs` warns (instead of silently shorting) when the dedup
  retry cap trips on a saturated space;
* `ArtifactStore.gc_checkpoints` sweeps only stale `search_ckpt` keys,
  and `EvalService.health` reports the sweep.

Runs under the real `hypothesis` package when installed, else under the
deterministic fallback shim in tests/conftest.py.
"""
import warnings

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.accel import apps as apps_lib
from repro.accel import batch_oracle
from repro.accel import library as lib
from repro.accel import synth
from repro.core import dataset as ds_lib
from repro.core import graph as graph_lib


def _entries(app):
    return {k: lib.build_library(k) for k in {n.kind for n in app.unit_nodes}}


# --------------------------------------------------------------------------
# schema bookkeeping
# --------------------------------------------------------------------------

def test_schema_v1_layout():
    s = graph_lib.SCHEMA_V1
    assert s.version == 1
    assert s.dim == 21
    assert s.crit_index == 8
    assert s.start("kind_onehot") == 9
    assert s.dynamic_fields == ()
    assert s.dynamic_slice == slice(9, 9)
    assert s.merged_dim == 21 + len(graph_lib.APP_VOCAB)


def test_schema_v2_layout_and_aliases():
    s = graph_lib.SCHEMA_V2
    assert s.version == 2
    assert s.dim == 27
    assert s.crit_index == 8
    assert s.start("kind_onehot") == 15
    assert s.dynamic_fields == ("slack", "criticality", "err_mae",
                                "err_wce", "probe_err8", "probe_err16")
    assert s.dynamic_slice == slice(9, 15)
    assert s.col("timing", "slack") == 9
    assert s.col("timing", "probe_err8") == 13
    # the legacy constants must stay derived from the active schema
    a = graph_lib.ACTIVE_SCHEMA
    assert graph_lib.FEATURE_DIM == a.dim
    assert graph_lib.CRIT_IDX == a.crit_index
    assert graph_lib.N_BASE == a.start("kind_onehot")
    assert graph_lib.MERGED_FEATURE_DIM == a.merged_dim


def test_schema_normalize_mask():
    s = graph_lib.SCHEMA_V2
    keep = s.normalize_mask()
    assert keep.shape == (s.dim,)
    assert keep[s.sl("unit_stats")].all()
    assert not keep[s.crit_index]
    assert keep[s.dynamic_slice].all()
    assert not keep[s.sl("kind_onehot")].any()


def test_schema_for_unknown_version():
    assert graph_lib.schema_for(None) is graph_lib.ACTIVE_SCHEMA
    assert graph_lib.schema_for(1) is graph_lib.SCHEMA_V1
    with pytest.raises(KeyError):
        graph_lib.schema_for(99)


# --------------------------------------------------------------------------
# batched timing oracle == scalar reference (hypothesis-driven)
# --------------------------------------------------------------------------

@settings(max_examples=12, deadline=None)
@given(st.sampled_from(("sobel", "gaussian", "dct8")),
       st.integers(0, 2 ** 16))
def test_timing_batch_matches_scalar(app_name, seed):
    app = apps_lib.APPS[app_name]
    entries = _entries(app)
    cfgs = ds_lib.sample_configs(app, 4, seed=seed, lib_entries=entries)
    rep = batch_oracle.timing_batch(app, entries,
                                    np.asarray(cfgs, np.int64))
    for bi, cfg in enumerate(cfgs):
        choice = {n.id: entries[n.kind][i]
                  for n, i in zip(app.unit_nodes, cfg)}
        ref = synth.static_timing(app, choice)
        assert np.isclose(rep["tmax"][bi], ref["tmax"], rtol=1e-12)
        for a, nid in enumerate(rep["node_ids"]):
            nd = ref["nodes"][nid]
            # exact: both paths max/min/subtract/divide identical floats
            assert rep["slack"][bi, a] == nd["slack"], (app_name, nid)
            assert rep["criticality"][bi, a] == nd["criticality"], \
                (app_name, nid)
            assert float(rep["crit"][bi, a]) == nd["on_critical_path"], \
                (app_name, nid)
            # tolerance: the batched sweep sums the error mass in a
            # different edge order
            assert np.isclose(rep["err_mae"][bi, a], nd["err_mae"],
                              rtol=1e-9, atol=1e-12), (app_name, nid)
            assert np.isclose(rep["err_wce"][bi, a], nd["err_wce"],
                              rtol=1e-9, atol=1e-12), (app_name, nid)


def test_timing_bounds_and_crit_consistency():
    """slack >= 0 with 0 on the critical path; criticality in (0, 1]."""
    app = apps_lib.APPS["sobel"]
    entries = _entries(app)
    cfgs = ds_lib.sample_configs(app, 16, seed=7, lib_entries=entries)
    rep = batch_oracle.timing_batch(app, entries,
                                    np.asarray(cfgs, np.int64))
    assert (rep["slack"] > -1e-9).all()
    assert (rep["criticality"] > 0).all()
    assert (rep["criticality"] <= 1 + 1e-12).all()
    # every config has at least one zero-slack node and it is critical
    on_crit = rep["crit"].astype(bool)
    assert on_crit.any(axis=1).all()
    assert (np.abs(rep["slack"][on_crit]) < 1e-9).all()


# --------------------------------------------------------------------------
# build path vs engine hot path: bit identity
# --------------------------------------------------------------------------

@pytest.mark.parametrize("app_name", ["sobel", "gaussian"])
def test_build_vs_hot_path_bit_identical(app_name):
    ds = ds_lib.build(app_name, n_samples=24, seed=3)
    assert ds.schema_version == graph_lib.ACTIVE_SCHEMA.version
    app = apps_lib.APPS[app_name]
    entries = _entries(app)
    _, Xn, _ = ds_lib.features_for_configs(ds, app, entries,
                                           ds.configs[:12])
    ref = np.array(ds.x[:12])
    ref[..., ds.schema.crit_index] = 0.0   # build zeroes crit; so does
    assert (Xn == ref).all()               # the hot path (stage-1 fills)


def test_build_batched_vs_loop_features_identical():
    """`build_loop` featurizes via scalar `static_timing`; `build` via
    `timing_batch` — the normalized tensors must stay bit-identical (same
    discipline as the PPA/crit label parity in
    tests/test_batch_oracle.py, now including the dynamic block). The
    probe columns are the one exception: the scalar functional model and
    the vmapped LUT path reduce the SSIM moments in different orders, so
    they carry a float32-noise tolerance instead."""
    b = ds_lib.build("sobel", n_samples=16, seed=5)
    l = ds_lib.build_loop("sobel", n_samples=16, seed=5)
    assert b.configs == l.configs
    s = b.schema
    probe_cols = [s.col("timing", f) for f in apps_lib.PROBE_FIELDS]
    exact = np.ones(s.dim, bool)
    exact[probe_cols] = False
    assert (b.x[..., exact] == l.x[..., exact]).all()
    np.testing.assert_allclose(b.x[..., probe_cols], l.x[..., probe_cols],
                               atol=1e-4)
    assert (b.crit == l.crit).all()


def test_probe_batch_matches_scalar():
    """`batch_oracle.probe_batch` (vmapped LUT functional model) agrees
    with the scalar `apps.probe_scalar` reference per config, and the
    distortion is 0 for the all-exact design."""
    app = apps_lib.APPS["gaussian"]
    entries = _entries(app)
    cfgs = ds_lib.sample_configs(app, 4, seed=11, lib_entries=entries)
    exact_cfg = tuple(0 for _ in app.unit_nodes)
    C = np.asarray(list(cfgs) + [exact_cfg], np.int64)
    rep = batch_oracle.probe_batch(app, entries, C)
    for bi, cfg in enumerate(C):
        choice = {n.id: entries[n.kind][i]
                  for n, i in zip(app.unit_nodes, cfg)}
        ref = apps_lib.probe_scalar(app, choice)
        for f in apps_lib.PROBE_FIELDS:
            assert np.isclose(rep[f][bi], ref[f], atol=1e-5), (f, bi)
    # exact design: SSIM == 1 -> distortion 0 (float32 noise only)
    for f in apps_lib.PROBE_FIELDS:
        assert abs(rep[f][-1]) < 1e-6


def test_merge_rejects_mixed_schema_versions():
    ds_a = ds_lib.build("sobel", n_samples=8, seed=0)
    ds_b = ds_lib.build("gaussian", n_samples=8, seed=0)
    ds_b.schema_version = 1
    with pytest.raises(ValueError, match="schema"):
        ds_lib.merge({"sobel": ds_a, "gaussian": ds_b})


# --------------------------------------------------------------------------
# satellite: sample_configs shortfall warning
# --------------------------------------------------------------------------

def test_sample_configs_warns_on_saturated_space():
    app = apps_lib.APPS["sobel"]
    entries = _entries(app)
    # restrict every kind to 1 entry -> exactly one canonical config
    tiny = {k: v[:1] for k, v in entries.items()}
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        out = ds_lib.sample_configs(app, 10, seed=0, lib_entries=tiny)
    assert len(out) == 1
    assert any("dedup retry cap" in str(x.message) for x in w)


def test_sample_configs_no_warning_when_satisfied():
    app = apps_lib.APPS["sobel"]
    entries = _entries(app)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        out = ds_lib.sample_configs(app, 8, seed=0, lib_entries=entries)
    assert len(out) == 8
    assert not w


# --------------------------------------------------------------------------
# satellite: checkpoint GC
# --------------------------------------------------------------------------

def test_gc_checkpoints_sweeps_only_stale_ckpts(tmp_path):
    from repro.core.artifacts import ArtifactStore

    store = ArtifactStore(str(tmp_path))
    old = store.key("search_ckpt", {"run": "dead"})
    fresh = store.key("search_ckpt", {"run": "live"})
    other = store.key("dataset", {"app": "sobel"})
    store.put(old, {"gen": 3})
    store.put(fresh, {"gen": 5})
    store.put(other, {"x": 1})
    store._mtimes[old] -= 1000.0            # age the dead run's ckpt
    evicted = store.gc_checkpoints(max_age_s=600.0)
    assert evicted == (old,)
    assert not store.has(old)
    assert store.has(fresh) and store.has(other)
    # idempotent
    assert store.gc_checkpoints(max_age_s=600.0) == ()


def test_gc_checkpoints_disk_mtime_fallback(tmp_path):
    """Disk entries from a previous process (no in-memory put timestamp)
    age by file mtime."""
    import os

    from repro.core.artifacts import ArtifactStore

    store = ArtifactStore(str(tmp_path))
    key = store.key("search_ckpt", {"run": "orphan"})
    store.put(key, {"gen": 1})
    p = store._path(key)
    os.utime(p, (p.stat().st_atime, p.stat().st_mtime - 1000.0))
    fresh_store = ArtifactStore(str(tmp_path))   # simulates a restart
    assert fresh_store.gc_checkpoints(max_age_s=600.0) == (key,)
    assert not p.exists()


def test_health_reports_checkpoint_gc(tmp_path):
    from repro.core.artifacts import ArtifactStore
    from repro.launch.serve import EvalService

    store = ArtifactStore(str(tmp_path))
    stale = store.key("search_ckpt", {"run": "dead"})
    store.put(stale, {"gen": 2})
    store._mtimes[stale] -= 1000.0
    with EvalService(store, checkpoint_gc_age_s=600.0) as svc:
        h = svc.health()
        assert h["checkpoint_gc"] == {"evicted_now": 1,
                                      "evicted_total": 1, "remaining": 0}
        assert not store.has(stale)
        # disabled sweep still reports the remaining count
        svc.checkpoint_gc_age_s = None
        store.put(stale, {"gen": 2})
        h2 = svc.health()
        assert h2["checkpoint_gc"]["evicted_now"] == 0
        assert h2["checkpoint_gc"]["remaining"] == 1
