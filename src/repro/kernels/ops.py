"""jit'd public wrappers for the Pallas kernels.

Each op dispatches to the Pallas kernel, compiled natively on TPU and run
with ``interpret=True`` on every other backend, or to the pure-jnp
reference via ``backend="ref"``. The platform is read at dispatch
(`on_tpu`), never at import, so importing this module touches no device.
Tests sweep shapes/dtypes and assert allclose between the two.
"""
from __future__ import annotations

import jax

from repro.kernels import flash_attention as _fa
from repro.kernels import gnn_mp as _mp
from repro.kernels import ref
from repro.kernels import ssm_scan as _scan


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU (kernels compile natively)."""
    return jax.default_backend() == "tpu"


def gnn_mp(adj, h, w_self, w_nbr, b, backend: str = "pallas", **kw):
    if backend == "ref":
        return ref.gnn_mp_ref(adj, h, w_self, w_nbr, b)
    return _mp.gnn_mp(adj, h, w_self, w_nbr, b,
                      interpret=not on_tpu(), **kw)


def flash_attention(q, k, v, causal=True, backend: str = "pallas", **kw):
    if backend == "ref":
        return ref.flash_attention_ref(q, k, v, causal=causal)
    return _fa.flash_attention(q, k, v, causal=causal,
                               interpret=not on_tpu(), **kw)


def ssm_scan(a, b, y0, backend: str = "pallas", **kw):
    if backend == "ref":
        return ref.ssm_scan_ref(a, b, y0)
    return _scan.ssm_scan(a, b, y0, interpret=not on_tpu(), **kw)
