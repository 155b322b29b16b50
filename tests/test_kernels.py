"""Per-kernel shape/dtype sweeps, allclose vs pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops

RNG = np.random.default_rng(7)


@pytest.mark.parametrize("B,N,F,Fo,gb", [
    (2, 8, 16, 8, 2), (4, 32, 21, 48, 4), (3, 16, 24, 24, 1),
    (8, 32, 8, 304, 8),
])
def test_gnn_mp_sweep(B, N, F, Fo, gb):
    adj = jnp.asarray(RNG.random((B, N, N)), jnp.float32)
    h = jnp.asarray(RNG.standard_normal((B, N, F)), jnp.float32)
    ws = jnp.asarray(RNG.standard_normal((F, Fo)) * 0.1, jnp.float32)
    wn = jnp.asarray(RNG.standard_normal((F, Fo)) * 0.1, jnp.float32)
    b = jnp.asarray(RNG.standard_normal(Fo) * 0.1, jnp.float32)
    got = ops.gnn_mp(adj, h, ws, wn, b, graph_block=gb)
    want = ops.gnn_mp(adj, h, ws, wn, b, backend="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("B,H,KV,S,D,bq,bk", [
    (1, 2, 1, 32, 8, 16, 16), (2, 4, 2, 64, 16, 32, 16),
    (1, 8, 2, 128, 32, 64, 64), (2, 2, 2, 64, 64, 64, 32),
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, H, KV, S, D, bq, bk, causal):
    q = jnp.asarray(RNG.standard_normal((B, H, S, D)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, KV, S, D)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, KV, S, D)), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=causal, bq=bq, bk=bk)
    want = ops.flash_attention(q, k, v, causal=causal, backend="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_flash_attention_bf16():
    B, H, KV, S, D = 1, 2, 2, 64, 16
    q = jnp.asarray(RNG.standard_normal((B, H, S, D)), jnp.bfloat16)
    k = jnp.asarray(RNG.standard_normal((B, KV, S, D)), jnp.bfloat16)
    v = jnp.asarray(RNG.standard_normal((B, KV, S, D)), jnp.bfloat16)
    got = ops.flash_attention(q, k, v, bq=32, bk=32)
    want = ops.flash_attention(q, k, v, backend="ref")
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=0.05, atol=0.05)


@pytest.mark.parametrize("T,D,block", [
    (64, 8, 16), (256, 32, 128), (128, 128, 32), (100, 16, 100),
])
def test_ssm_scan_sweep(T, D, block):
    a = jnp.asarray(RNG.random((T, D)) * 0.95, jnp.float32)
    b = jnp.asarray(RNG.standard_normal((T, D)), jnp.float32)
    y0 = jnp.asarray(RNG.standard_normal(D), jnp.float32)
    ys1, yf1 = ops.ssm_scan(a, b, y0, block=block)
    ys2, yf2 = ops.ssm_scan(a, b, y0, backend="ref")
    np.testing.assert_allclose(np.asarray(ys1), np.asarray(ys2),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(yf1), np.asarray(yf2),
                               rtol=1e-5, atol=1e-5)


def test_gnn_mp_inside_surrogate():
    """The Pallas kernel computes the same layer as gnn.apply's GCN."""
    from repro.core import gnn
    cfg = gnn.GNNConfig(arch="gcn", n_layers=1, hidden=16, feature_dim=8,
                        dropout=0.0)
    params = gnn.init_params(jax.random.PRNGKey(0), cfg)
    B, N = 3, 12
    adj = jnp.asarray(RNG.random((B, N, N)), jnp.float32)
    x = jnp.asarray(RNG.standard_normal((B, N, 8)), jnp.float32)
    mask = jnp.ones((B, N))
    lp = params["layers"][0]
    got = ops.gnn_mp(adj, x, lp["w_self"], lp["w_nbr"], lp["b"],
                     graph_block=1)
    want = gnn._layer(cfg, lp, adj, x, mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_importing_the_package_touches_no_device():
    """The TPU is picked at dispatch (`ops.on_tpu`), never at import: a
    process that only imports the main path's modules has initialized no
    backend, so it holds no chip."""
    import os
    import subprocess
    import sys

    code = ("import repro.kernels.ops, repro.core.engine, "
            "repro.core.pipeline, repro.core.islands, repro.launch.serve\n"
            "from jax._src import xla_bridge\n"
            "print(len(xla_bridge._backends))")
    env = dict(os.environ, PYTHONPATH=os.path.join(
        os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split()[-1] == "0"
    assert not ops.on_tpu()          # this test process runs on the CPU
