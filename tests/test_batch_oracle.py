"""Batched ground-truth labeling: randomized parity vs the scalar path.

Three layers of equivalence, per the acceptance criteria of the batched
labeling engine:
  * `batch_oracle.synthesize_batch` vs `synth.synthesize` — PPA within
    float tolerance, *identical* critical-node bit vectors;
  * the config-batched LUT functional model (`apps.accuracy_ssim_batch`)
    vs the closure-based `apps.accuracy_ssim`, across all five apps;
  * `dataset.build` vs its scalar reference `dataset.build_loop` —
    unchanged labels end to end.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.accel import apps, batch_oracle, library as lib, synth
from repro.core import dataset as ds_lib
from repro.data import images

ALL_APPS = ["sobel", "gaussian", "kmeans", "dct8", "fir15"]


@pytest.fixture(scope="module")
def imgset():
    imgs = images.image_set(2, 32)
    return (jnp.asarray(images.gray(imgs)),
            jnp.asarray(imgs.astype(np.int32)))


def _entries(app):
    return {n.kind: lib.build_library(n.kind) for n in app.unit_nodes}


def _rand_configs(app, entries, n, seed):
    rng = np.random.default_rng(seed)
    sizes = [len(entries[node.kind]) for node in app.unit_nodes]
    return np.stack([rng.integers(0, s, n) for s in sizes], axis=1)


@pytest.mark.parametrize("name", ALL_APPS)
def test_synthesize_batch_parity(name):
    app = apps.APPS[name]
    entries = _entries(app)
    C = _rand_configs(app, entries, 16, seed=11)
    rep = batch_oracle.synthesize_batch(app, entries, C)
    csets = batch_oracle.crit_sets(rep)
    delay_pos = {nid: i for i, nid in enumerate(rep["node_ids"])}
    for i, row in enumerate(C):
        choice = {node.id: entries[node.kind][c]
                  for node, c in zip(app.unit_nodes, row)}
        r = synth.synthesize(app, choice)
        for k in ("area", "power", "latency"):
            assert rep[k][i] == pytest.approx(r[k], rel=1e-9), (name, k)
        assert csets[i] == r["critical_nodes"], (name, i)
        for nid, d in r["node_delay"].items():
            assert rep["node_delay"][i, delay_pos[nid]] == pytest.approx(
                d, rel=1e-12)


def test_synthesize_batch_exact_config_and_determinism():
    app = apps.APPS["gaussian"]
    entries = _entries(app)
    C = np.zeros((3, len(app.unit_nodes)), np.int64)     # exact everywhere
    r1 = batch_oracle.synthesize_batch(app, entries, C)
    r2 = batch_oracle.synthesize_batch(app, entries, C)
    np.testing.assert_array_equal(r1["latency"], r2["latency"])
    np.testing.assert_array_equal(r1["crit"], r2["crit"])
    # identical configs -> identical rows (jitter is config-hashed)
    assert r1["area"][0] == r1["area"][1] == r1["area"][2]


# (table entries, of them squares) the functional probe reads per
# configuration over its 8x8 and 16x16 images: kmeans's 6 mul8 squarers
# and 2 sqrt18; gaussian's 9 and fir15's 8 mul8x4 with a constant operand,
# dct8's 4 once in each pass; sobel has no LUT unit
PROBE_READS = {"sobel": (0, 0), "gaussian": (9 * 320, 0),
               "kmeans": (8 * 320, 6 * 320), "dct8": (8 * 320, 0),
               "fir15": (8 * 320, 0)}


@pytest.mark.parametrize("name", ALL_APPS)
def test_accuracy_ssim_batch_parity(name, imgset):
    """Batched labels match the scalar path; only kmeans's squarers read
    a diagonal table, as the probe's counts show."""
    g, rgb = imgset
    app = apps.APPS[name]
    entries = _entries(app)
    inp = rgb if name == "kmeans" else g
    C = _rand_configs(app, entries, 8, seed=7)
    got = apps.accuracy_ssim_batch(app, entries, C, inp, chunk=8)
    for i, row in enumerate(C):
        choice = {node.id: entries[node.kind][c]
                  for node, c in zip(app.unit_nodes, row)}
        want = apps.accuracy_ssim(app, choice, inp)
        assert got[i] == pytest.approx(want, abs=2e-5), (name, i)
    prober = batch_oracle.DeviceProber(app, entries)
    assert (prober.lut_reads, prober.lut_square_reads) == PROBE_READS[name]


def test_accuracy_ssim_batch_ragged_chunk(imgset):
    """A batch that is not a chunk multiple pads + slices correctly."""
    g, _ = imgset
    app = apps.APPS["sobel"]
    entries = _entries(app)
    C = _rand_configs(app, entries, 11, seed=9)
    whole = apps.accuracy_ssim_batch(app, entries, C, g, chunk=4)
    per = apps.accuracy_ssim_batch(app, entries, C, g, chunk=16)
    np.testing.assert_allclose(whole, per, atol=1e-6)


def test_accuracy_ssim_batch_lut_app_matches_scalar(imgset):
    """An app whose multipliers go through the LUT gather (gaussian:
    mul8x4 with constant coefficient columns) labels each config exactly
    as the closure-based scalar model does."""
    g, _ = imgset
    app = apps.APPS["gaussian"]
    entries = _entries(app)
    assert any(n.kind in lib.LUT_DOMAINS for n in app.unit_nodes)
    C = _rand_configs(app, entries, 4, seed=5)
    got = apps.accuracy_ssim_batch(app, entries, C, g, chunk=4)
    for i, row in enumerate(C):
        choice = {node.id: entries[node.kind][c]
                  for node, c in zip(app.unit_nodes, row)}
        assert got[i] == pytest.approx(apps.accuracy_ssim(app, choice, g),
                                       abs=2e-5), i


@pytest.mark.parametrize("kind,wa,wb,idx,M", [
    ("mul8", 8, 8, 5, 4096), ("mul8x4", 8, 4, 3, 4096),
    ("add8", 8, 8, 7, 4096),
    # lengths that are not a multiple of any block or vector width
    ("mul8x4", 8, 4, 2, 4096 + 700), ("add8", 8, 8, 4, 1023),
])
def test_lut_gather_sweep(kind, wa, wb, idx, M):
    """The labeler's gather over a stacked truth table reads entry
    ``idx``'s value for every (a, b): equal to evaluating the unit."""
    import jax

    entries = tuple(lib.build_library(kind))
    table = jnp.asarray(lib.stacked_lut(entries, wa, wb))
    rng = np.random.default_rng(7)
    a = jnp.asarray(rng.integers(0, 1 << wa, M), jnp.int32)
    b = jnp.asarray(rng.integers(0, 1 << wb, M), jnp.int32)
    gather = jax.jit(apps.lut_gather, static_argnums=3)
    got = gather(table, (idx << wa) | a, b, wb)
    direct = entries[idx].inst.fn()(a, b)
    assert got.shape == (M,)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(direct))


def test_lut_domain_guard_raises(imgset):
    """Shrinking a LUT domain below the app's real operand range must
    raise instead of silently mislabeling."""
    g, _ = imgset
    app = apps.APPS["gaussian"]
    entries = _entries(app)
    C = _rand_configs(app, entries, 4, seed=3)
    key = ("gaussian", "mul8x4")
    old = lib.lut_domain(*key)
    lib.APP_LUT_DOMAINS[key] = (4, 4)            # pixels reach 255 >= 2^4
    apps._batch_label_fn.cache_clear()
    try:
        with pytest.raises(apps.LutDomainError):
            apps.accuracy_ssim_batch(app, entries, C, g, chunk=4)
    finally:
        lib.APP_LUT_DOMAINS[key] = old
        apps._batch_label_fn.cache_clear()


SQUARE_FORMS = {"select": apps.lut_square_select, "take": apps.lut_square_take}


@pytest.mark.parametrize("form", sorted(SQUARE_FORMS))
def test_lut_square_reads_the_diagonal(form):
    """For every mul8 entry (kmeans's pruned ones among them) and every
    operand in the (9, 9) domain, the squarer's diagonal read equals the
    full table's value at (a, a), in both of `apps.lut_square`'s forms."""
    import jax

    entries = tuple(lib.build_library("mul8"))
    ea, eb = lib.LUT_DOMAINS["mul8"]
    rows = jnp.asarray(lib.lut_diagonal(entries, ea, eb))
    assert rows.shape == (len(entries), 1 << ea)
    table = jnp.asarray(lib.stacked_lut(entries, ea, eb))
    idx = jnp.arange(len(entries), dtype=jnp.int32)
    a = jnp.broadcast_to(jnp.arange(1 << ea, dtype=jnp.int32),
                         (len(entries), 1 << ea))
    square = SQUARE_FORMS[form]
    got = jax.jit(jax.vmap(lambda e, x: square(rows, e, x)))(idx, a)
    want = apps.lut_gather(table, (idx[:, None] << ea) | a, a, eb)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _full_table_twin(app):
    """``app`` under another name, its squarers fed a copy of their
    operand, so they read the full table as any two-operand unit does."""
    import dataclasses

    def run(impls, images):
        twin = {k: (lambda f: lambda a, b=None: f(a, a + 0 if b is a else b))
                (f) for k, f in impls.items()}
        return app.run(twin, images)
    return dataclasses.replace(app, name=app.name + "_full_table", run=run)


@pytest.mark.parametrize("form", ["platform", "select"])
def test_kmeans_squarers_match_the_full_table(imgset, monkeypatch, form):
    """A 512-config kmeans block labels and probes bit-identically through
    the squarers' diagonal and through full-table gathers: in the form the
    platform picks (a gather here) and in the TPU's compare-select-sum."""
    _, rgb = imgset
    app = apps.APPS["kmeans"]
    twin = _full_table_twin(app)
    monkeypatch.setitem(apps.APPS, twin.name, twin)
    if form == "select":
        # labelers built apart from the cache, squaring in the TPU's form
        monkeypatch.setattr(apps, "lut_square", apps.lut_square_select)
        monkeypatch.setattr(apps, "_batch_label_fn",
                            apps._batch_label_fn.__wrapped__)
    entries = _entries(app)
    C = _rand_configs(app, entries, 512, seed=16)
    diag, full = (apps.batch_labeler(a, entries) for a in (app, twin))
    assert diag.lut_square_reads(rgb) == 6 * rgb.size // 3
    assert full.lut_square_reads(rgb) == 0
    assert diag.lut_reads(rgb) == full.lut_reads(rgb)
    probe_sets = [apps.probe_inputs("kmeans", s) for s in apps.PROBE_SIZES]
    exact = app.run(apps.make_impls(app, apps.exact_choice(app)), rgb)
    for image_sets in (probe_sets, [(rgb, exact)]):
        outs = []
        for a, labeler in ((app, diag), (twin, full)):
            scores, check = apps.ssim_batch_on_device(a, labeler, C,
                                                      image_sets, chunk=512)
            check()
            outs.append([np.asarray(s) for s in scores])
        for got, want in zip(*outs):
            np.testing.assert_array_equal(got, want)


def test_squarer_operand_outside_the_domain_raises(monkeypatch):
    """A squarer fed 512, one past the (9, 9) domain, raises with the
    guard's message, as a full-table read did."""
    from repro.accel.apps import AccelDef, Node

    app = AccelDef("square_probe", (Node("m", "mul8"),), (),
                   lambda impls, x: impls["m"](x, x))
    entries = {"mul8": lib.build_library("mul8")[:3]}
    x = jnp.full((1, 8, 8), 511, jnp.int32)
    monkeypatch.setitem(apps.APPS, app.name, app)
    try:
        C = np.arange(3)[:, None]
        apps.accuracy_ssim_batch(app, entries, C, x, chunk=4)   # in domain
        with pytest.raises(apps.LutDomainError,
                           match=r"unit m#0 \(mul8\) left its LUT domain "
                                 r"\(2\^9, 2\^9\) by up to a:1 b:1"):
            apps.accuracy_ssim_batch(app, entries, C, x + 1, chunk=4)
    finally:
        apps._batch_label_fn.cache_clear()


@pytest.mark.parametrize("n", [8, 12, 16])
def test_seg_swar_matches_scalar_add_seg(n):
    """The SWAR carry-kill formulation of the segmented adder is bit-exact
    vs the per-segment scalar loop, for every cut and signed operands."""
    from repro.accel import units
    rng = np.random.default_rng(n)
    a = jnp.asarray(rng.integers(-(1 << (n + 2)), 1 << (n + 2), 512),
                    jnp.int32)
    b = jnp.asarray(rng.integers(-(1 << (n + 2)), 1 << (n + 2), 512),
                    jnp.int32)
    for k in range(2, n):
        want = units.add_seg(a, b, n, k)
        mask = jnp.int32(units.seg_kill_mask(n, k))
        got = units.addsub_batched("add", n, jnp.int32(units.FAM_IDS["seg"]),
                                   jnp.int32(k), mask, a, b)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=f"n={n} k={k}")


def test_stacked_lut_layout():
    ent = lib.build_library("mul8x4")[:3]
    ea, eb = 9, 4
    table = lib.stacked_lut(tuple(ent), ea, eb)
    assert table.shape == (3 << (ea + eb),)
    a = np.asarray([7, 300, 511], np.int32)
    b = np.asarray([3, 15, 1], np.int32)
    for i, e in enumerate(ent):
        fn = e.inst.fn()
        want = np.asarray(fn(jnp.asarray(a), jnp.asarray(b)))
        got = table[(i << (ea + eb)) | (a << eb) | b]
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("name", ["sobel", "kmeans"])
def test_dataset_build_labels_unchanged(name):
    """`build()` on the batched path reproduces the scalar-loop dataset:
    bit-identical critical labels, float-tolerance PPA/SSIM."""
    kw = dict(n_samples=25, seed=4, n_images=2, img_size=32)
    d_b = ds_lib.build(name, **kw)
    d_l = ds_lib.build_loop(name, **kw)
    assert d_b.configs == d_l.configs
    np.testing.assert_array_equal(d_b.crit, d_l.crit)
    np.testing.assert_allclose(d_b.y_raw[:, :3], d_l.y_raw[:, :3],
                               rtol=1e-6)
    np.testing.assert_allclose(d_b.y_raw[:, 3], d_l.y_raw[:, 3], atol=2e-5)
    np.testing.assert_allclose(d_b.x, d_l.x, atol=1e-6)
    np.testing.assert_array_equal(d_b.adj, d_l.adj)
    np.testing.assert_array_equal(d_b.mask, d_l.mask)
    np.testing.assert_array_equal(d_b.unit_mask, d_l.unit_mask)


def test_oracle_engine_serves_batched_labels(imgset):
    """`SurrogateEngine.from_oracle` rides the batched labeling path and
    reproduces the scalar oracle's objective rows."""
    from repro.core.engine import SurrogateEngine

    g, _ = imgset
    app = apps.APPS["sobel"]
    entries = _entries(app)
    exact_out = app.run(apps.make_impls(app, apps.exact_choice(app)), g)
    eng = SurrogateEngine.from_oracle(app, entries, g, exact_out,
                                      chunk_size=8)
    cfgs = [tuple(int(v) for v in row)
            for row in _rand_configs(app, entries, 10, seed=21)]
    rows = eng(cfgs)
    for i, c in enumerate(cfgs):
        choice = {node.id: entries[node.kind][j]
                  for node, j in zip(app.unit_nodes, c)}
        r = synth.synthesize(app, choice)
        acc = apps.accuracy_ssim(app, choice, g, exact_out)
        np.testing.assert_allclose(
            rows[i], [r["area"], r["power"], r["latency"], 1 - acc],
            rtol=1e-6, atol=2e-5)
    assert eng.stats.chunks == 2                 # 8 + pad(2 -> 2)


def test_featurizer_cached_on_dataset():
    """The DSE hot path reuses one featurizer per library signature
    instead of rebuilding the constant feature columns."""
    from repro.accel import apps as apps_lib
    from repro.core import pruning

    pruned, _ = pruning.prune_library()
    app = apps_lib.APPS["sobel"]
    entries = {k: pruned[k] for k in {n.kind for n in app.unit_nodes}}
    ds = ds_lib.build("sobel", n_samples=12, n_images=2, img_size=32,
                      lib_entries=entries)
    cfgs = _rand_configs(app, entries, 6, seed=2)
    A1, X1, M1 = ds_lib.features_for_configs(ds, app, entries, cfgs)
    feat = ds._featurizers[ds_lib._entries_sig(entries)]
    A2, X2, M2 = ds_lib.features_for_configs(ds, app, entries, cfgs)
    assert ds._featurizers[ds_lib._entries_sig(entries)] is feat
    np.testing.assert_array_equal(X1, X2)
    # the engine featurizer shares the same cache entry
    from repro.core.engine import _ConfigFeaturizer
    ef = _ConfigFeaturizer(ds, app, entries)
    assert ef._feat is feat
    np.testing.assert_array_equal(ef(cfgs), X1)
