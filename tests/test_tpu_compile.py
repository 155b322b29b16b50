"""Compile the main path's device programs for a described TPU v5e.

Nothing here runs on a chip: `topologies.get_topology_desc` describes a
v5e:2x2 host and the TPU compiler, which is installed with jaxlib, compiles
for it. A refusal here (an unsupported gather, a misaligned block, too
much VMEM) is what the chip's compiler would raise. The topology is
described inside a module-scoped fixture, never while a module is
imported: only one process at a time may load the TPU library, and the
test workers all import this file.

The persistent compilation cache is turned off around these compiles: an
executable compiled for a described chip is written to it but cannot be
read back without one.

Code that asks the default backend sees the CPU here, so the tests steer
the Pallas dispatch (`ops.on_tpu`) to its TPU branch themselves.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

F = 27          # SCHEMA_V2 feature width
CHUNK = 512     # the engine's default chunk


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.asarray(topo.devices), ("shard",))


@pytest.fixture(autouse=True)
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", old)
        compilation_cache.reset_cache()


@pytest.fixture
def kernels_on_tpu(monkeypatch):
    from repro.kernels import ops
    monkeypatch.setattr(ops, "on_tpu", lambda: True)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_count(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


@pytest.mark.parametrize("B,N,Fi,Fo", [
    (CHUNK, 20, F, 96), (CHUNK, 32, F, 300), (CHUNK, 32, 300, 300),
])
def test_gnn_mp_compiles(one_chip, B, N, Fi, Fo):
    from repro.kernels import gnn_mp

    args = [_sds((B, N, N), jnp.float32, one_chip),
            _sds((B, N, Fi), jnp.float32, one_chip),
            _sds((Fi, Fo), jnp.float32, one_chip),
            _sds((Fi, Fo), jnp.float32, one_chip),
            _sds((Fo,), jnp.float32, one_chip)]
    compiled = gnn_mp.gnn_mp.lower(*args, interpret=False).compile()
    assert _kernel_count(compiled) >= 1


def _kernel_predict(hidden, n_layers, N):
    from repro.core import gnn, models
    from repro.core.engine import _make_kernel_predict

    two_cfg = models.TwoStageConfig(gnn=gnn.GNNConfig(
        arch="gsae", n_layers=n_layers, hidden=hidden, feature_dim=F))
    params = jax.tree.map(np.asarray,
                          models.init(jax.random.PRNGKey(0), two_cfg))
    rng = np.random.default_rng(0)
    adj = (rng.random((N, N)) < 0.2).astype(np.float32)
    mask = np.ones(N, np.float32)
    return _make_kernel_predict(two_cfg, params, adj, mask), n_layers


@pytest.mark.parametrize("hidden,n_layers,N", [
    (96, 3, 20), (96, 3, 32), (300, 5, 20), (300, 5, 32),
])
def test_engine_kernel_predict_compiles(one_chip, kernels_on_tpu, hidden,
                                        n_layers, N):
    f, layers = _kernel_predict(hidden, n_layers, N)
    compiled = f.lower(_sds((CHUNK, N, F), jnp.float32, one_chip)).compile()
    # one fused message-passing kernel per layer, in each of two stages
    assert _kernel_count(compiled) >= 2 * layers


@pytest.mark.parametrize("app_name", ["kmeans", "gaussian"])
def test_labeling_model_compiles(one_chip, app_name):
    from repro.accel import apps
    from repro.accel import library as lib

    app = apps.APPS[app_name]
    entries = {n.kind: lib.build_library(n.kind) for n in app.unit_nodes}
    run_chunk = apps._batch_label_fn(app_name,
                                     apps._entries_items(app, entries)).fn
    # the pipeline's labeling set: 4 RGB (kmeans) or gray 64x64 images
    images = (4, 64, 64, 3) if app_name == "kmeans" else (4, 64, 64)
    exact = jax.eval_shape(
        lambda x: app.run(apps.make_impls(app, apps.exact_choice(app)), x),
        jax.ShapeDtypeStruct(images, jnp.int32))
    compiled = jax.jit(run_chunk).lower(
        _sds((256, len(app.unit_nodes)), jnp.int32, one_chip),
        _sds(images, jnp.int32, one_chip),
        _sds(exact.shape, exact.dtype, one_chip)).compile()
    assert "gather" in compiled.as_text()


@pytest.mark.parametrize("n_islands,members", [(4, 32), (4, 128)])
def test_island_ranks_compile(one_chip, four_chips, n_islands, members):
    """One chip, and the island axis over four: each island's tensors stay
    on its device; the only collective is the while loop's scalar
    "any front left" condition."""
    from repro.core import islands

    kern = islands._ranks_jit()
    shape = (n_islands, members, 4)
    kern.lower(_sds(shape, jnp.int32, one_chip)).compile()
    island = NamedSharding(four_chips, PartitionSpec("shard"))
    text = kern.lower(_sds(shape, jnp.int32, island)).compile().as_text()
    for op in ("all-gather", "all-to-all", "collective-permute"):
        assert op not in text, op
    reduces = re.findall(r"= (\w+\[[^\]]*\])\S* all-reduce\(", text)
    assert len(reduces) == 1 and reduces[0].endswith("[]"), reduces   # scalar
