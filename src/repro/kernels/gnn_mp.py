"""Pallas kernel: fused batched-dense GNN message passing.

The ApproxPilot DSE loop evaluates millions of candidate configurations
through the surrogate — the hot spot is `relu(A @ (H @ Wn) + H @ Ws + b)`
per GNN layer over a large batch of small graphs. On TPU we fuse the two
matmuls, the aggregation and the ReLU into one kernel: per grid step, one
graph block (Gb graphs) stays resident in VMEM, both weights are VMEM-wide,
and the MXU sees two back-to-back (Gb*N, F)x(F, Fo) contractions without an
HBM round-trip for the (Gb,N,Fo) intermediate.

Block sizing: Gb chosen so Gb*(N*N + N*F + 2*N*Fo) * 4B plus the two weight
panels fits comfortably in ~16MB VMEM; N (padded graph size) and F are
multiples of 8/128 for lane alignment where possible.

Precision: every contraction is pinned to ``Precision.HIGHEST`` (float32
on the MXU). The surrogate engine's pure-JAX forward is pinned the same
way, so the two paths differ only in summation order and the engine's
parity check can hold them to a float32 tolerance.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(adj_ref, h_ref, ws_ref, wn_ref, b_ref, out_ref):
    adj = adj_ref[...]                   # (Gb, N, N)
    h = h_ref[...]                       # (Gb, N, F)
    ws = ws_ref[...]                     # (F, Fo)
    wn = wn_ref[...]                     # (F, Fo)
    bias = b_ref[...]                    # (1, Fo)
    Gb, N, F = h.shape
    Fo = ws.shape[1]
    h2 = h.reshape(Gb * N, F)
    hi = jax.lax.Precision.HIGHEST
    msg = jnp.dot(h2, wn, precision=hi, preferred_element_type=jnp.float32)
    own = jnp.dot(h2, ws, precision=hi, preferred_element_type=jnp.float32)
    msg = msg.reshape(Gb, N, Fo)
    own = own.reshape(Gb, N, Fo)
    agg = jax.lax.dot_general(adj, msg, (((2,), (1,)), ((0,), (0,))),
                              precision=hi,
                              preferred_element_type=jnp.float32)
    out_ref[...] = jax.nn.relu(agg + own + bias[None]).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("graph_block", "interpret"))
def gnn_mp(adj: jax.Array, h: jax.Array, w_self: jax.Array,
           w_nbr: jax.Array, b: jax.Array, *, graph_block: int = 8,
           interpret: bool = True) -> jax.Array:
    """adj: (B,N,N) f32; h: (B,N,F); w: (F,Fo); b: (Fo,) -> (B,N,Fo)."""
    B, N, F = h.shape
    Fo = w_self.shape[1]
    gb = min(graph_block, B)
    if B % gb:
        gb = 1
    grid = (B // gb,)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((gb, N, N), lambda i: (i, 0, 0)),
            pl.BlockSpec((gb, N, F), lambda i: (i, 0, 0)),
            pl.BlockSpec((F, Fo), lambda i: (0, 0)),
            pl.BlockSpec((F, Fo), lambda i: (0, 0)),
            pl.BlockSpec((1, Fo), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((gb, N, Fo), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, N, Fo), h.dtype),
        interpret=interpret,
    )(adj, h, w_self, w_nbr, b.reshape(1, -1))
