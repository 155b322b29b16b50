"""Pure-jnp oracles for every Pallas kernel (allclose targets in tests)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def gnn_mp_ref(adj, h, w_self, w_nbr, b):
    """Fused GNN message passing: relu(A @ (H @ Wn) + H @ Ws + b).
    adj: (B,N,N); h: (B,N,F); w_*: (F,Fo); b: (Fo,)."""
    return jax.nn.relu(adj @ (h @ w_nbr) + h @ w_self + b)


def flash_attention_ref(q, k, v, *, causal=True):
    """q: (B,H,S,D); k,v: (B,KV,S,D); GQA grouping H = KV*G."""
    B, H, S, D = q.shape
    KV = k.shape[1]
    G = H // KV
    q5 = q.reshape(B, KV, G, S, D)
    s = jnp.einsum("bkgqd,bksd->bkgqs", q5, k,
                   preferred_element_type=jnp.float32) * D ** -0.5
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bksd->bkgqd", p.astype(v.dtype), v)
    return o.reshape(B, H, S, D)


def ssm_scan_ref(a, b, y0):
    """Diagonal linear recurrence y_t = a_t * y_{t-1} + b_t.
    a,b: (T,D) f32; y0: (D,). Returns ys (T,D) and y_final (D,)."""
    def step(carry, inp):
        at, bt = inp
        y = at * carry + bt
        return y, y
    yT, ys = jax.lax.scan(step, y0, (a, b))
    return ys, yT
