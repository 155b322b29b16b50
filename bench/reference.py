"""Plain reference for the benchmark's ApproxPilot configurations.

It imports nothing of the program under test and takes nothing the program
made: the accelerator (nodes, edges) comes from the configuration file, the
unit library is generated and characterized here, and the weights and
feature scales are the benchmark's own, drawn from the seed. What it
computes, one configuration at a time and in plain Python, NumPy and
`jax.numpy`:

* the approximate-unit library (the instance grid of the paper's Table III,
  exhaustive or LCG-sampled error metrics, the analytic PPA model with its
  per-instance jitter) and the design-space pruning (invalid designs, then
  K-means redundancy at ``theta``);
* the accelerators' functional models and the mean 8x8-window SSIM;
* the timing features of synthesis (slack, criticality, accumulated error
  mass along the dataflow);
* node features of feature schema v2 on the simplified graph;
* the two-stage GraphSAGE-mean surrogate forward at a stated matmul
  precision (``highest`` is float32, ``high`` three bfloat16 passes).

Its callers run it on the host's CPU (`host`).

The formulas follow the ApproxPilot paper (arXiv:2407.11324, Sec. III) as the
repository models it; this file is the benchmark's fixed yardstick and is
never edited by a change that claims a gain.
"""
from __future__ import annotations

import functools
import hashlib
import math
from dataclasses import dataclass
from typing import Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import networkx as nx
import numpy as np


def host():
    """Context in which the reference runs: the host's CPU, whose float32
    arithmetic is IEEE, so its answers do not depend on how an
    accelerator's compiler fuses or approximates an operation (a unit's
    floor(log2(x)) or x / 2**z sits on a step)."""
    return jax.default_device(jax.devices("cpu")[0])


# --------------------------------------------------------------------------
# approximate arithmetic units (elementwise int32)
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class Kind:
    op: str
    width_a: int
    width_b: int

    @property
    def name(self) -> str:
        if self.op == "mul" and self.width_a != self.width_b:
            return f"mul{self.width_a}x{self.width_b}"
        if self.op == "sqrt":
            return f"sqrt{self.width_a}"
        return f"{self.op}{self.width_a}"


KINDS = {k.name: k for k in (Kind("add", 8, 8), Kind("add", 12, 12),
                             Kind("add", 16, 16), Kind("sub", 10, 10),
                             Kind("mul", 8, 8), Kind("mul", 8, 4),
                             Kind("sqrt", 18, 0))}


def _mask(k):
    return (1 << k) - 1


def _ilog2(x):
    return jnp.floor(jnp.log2(jnp.maximum(x, 1).astype(jnp.float32))
                     ).astype(jnp.int32)


def _isqrt(x):
    r = jnp.floor(jnp.sqrt(x.astype(jnp.float32))).astype(jnp.int32)
    r = jnp.where((r + 1) * (r + 1) <= x, r + 1, r)
    r = jnp.where(r * r > x, r - 1, r)
    return jnp.maximum(r, 0)


def _add_seg(a, b, n, k):
    out = jnp.zeros_like(a)
    for lo in range(0, n, k):
        out = out | (((((a >> lo) & _mask(k)) + ((b >> lo) & _mask(k)))
                      & _mask(k)) << lo)
    top = n - (n % k or k)
    return (out & _mask(top)) | (((a >> top) + (b >> top)) << top)


def _mul_mitchell(a, b, c):
    za, zb = _ilog2(a), _ilog2(b)
    fa = a.astype(jnp.float32) / jnp.exp2(za.astype(jnp.float32)) - 1.0
    fb = b.astype(jnp.float32) / jnp.exp2(zb.astype(jnp.float32)) - 1.0
    if c > 0:
        q = float(1 << c)
        fa, fb = jnp.floor(fa * q) / q, jnp.floor(fb * q) / q
    s = fa + fb
    e = (za + zb).astype(jnp.float32)
    out = jnp.where(s < 1.0, jnp.exp2(e) * (1.0 + s), jnp.exp2(e + 1.0) * s)
    return jnp.where((a == 0) | (b == 0), 0.0, out).astype(jnp.int32)


def _mul_drum(a, b, m):
    def trim(x):
        sh = jnp.maximum(_ilog2(x) - (m - 1), 0)
        return (((x >> sh) | 1) << sh) * (x > 0)
    return trim(a) * trim(b)


def _sqrt_pwl(x, seg):
    z = _ilog2(x)
    f = x.astype(jnp.float32) / jnp.exp2(z.astype(jnp.float32)) - 1.0
    if seg > 0:
        q = float(1 << seg)
        f = jnp.floor(f * q) / q
    r = jnp.exp2(z.astype(jnp.float32) / 2.0) * (1.0 + f / 2.0)
    return jnp.where(x == 0, 0, r.astype(jnp.int32))


def _sqrt_newton(x, seg):
    r0 = jnp.maximum(_sqrt_pwl(x, seg).astype(jnp.float32), 1.0)
    r = 0.5 * (r0 + x.astype(jnp.float32) / r0)
    return jnp.where(x == 0, 0, r.astype(jnp.int32))


@dataclass(frozen=True)
class Unit:
    kind: Kind
    family: str
    level: int
    param: Tuple[int, ...] = ()

    @property
    def name(self) -> str:
        p = "_".join(str(x) for x in self.param)
        return f"{self.kind.name}_{self.family}" + (f"_{p}" if p else "")

    def fn(self) -> Callable:
        """(a, b) -> int32 result; sqrt ignores b."""
        n, f, p = self.kind.width_a, self.family, self.param
        k = p[0] if p else 0
        op = self.kind.op
        if op == "add":
            return {
                "exact": lambda a, b: a + b,
                "trunc": lambda a, b: ((a >> k) + (b >> k)) << k,
                "loa": lambda a, b: ((((a >> k) + (b >> k)) << k)
                                     | ((a | b) & _mask(k))),
                "lox": lambda a, b: ((((a >> k) + (b >> k)) << k)
                                     | ((a ^ b) & _mask(k))),
                "aca": lambda a, b: (((((a >> k) + (b >> k))
                                       + ((a >> (k - 1)) & (b >> (k - 1)) & 1))
                                      << k) | ((a + b) & _mask(k))),
                "seg": lambda a, b: _add_seg(a, b, n, k)}[f]
        if op == "sub":
            return {
                "exact": lambda a, b: a - b,
                "trunc": lambda a, b: ((a >> k) - (b >> k)) << k,
                "loa": lambda a, b: ((((a >> k) - (b >> k)) << k)
                                     | ((a ^ b) & _mask(k)))}[f]
        if op == "mul":
            return {
                "exact": lambda a, b: a * b,
                "rtrunc": lambda a, b: ((a * b) >> k) << k,
                "otrunc": lambda a, b: ((a >> p[0]) * (b >> p[1])
                                        ) << (p[0] + p[1]),
                "broken": lambda a, b: a * ((b >> k) << k),
                "mitchell": lambda a, b: _mul_mitchell(a, b, k),
                "drum": lambda a, b: _mul_drum(a, b, k)}[f]
        return {"exact": lambda a, b=None: _isqrt(a),
                "itrunc": lambda a, b=None: _isqrt(a >> (2 * k)) << k,
                "pwl": lambda a, b=None: _sqrt_pwl(a, k),
                "newton": lambda a, b=None: _sqrt_newton(a, k)}[f]


# --------------------------------------------------------------------------
# the library: instance grids, characterization, pruning
# --------------------------------------------------------------------------

TABLE_III = {"add8": 31, "add12": 26, "add16": 21, "sub10": 12,
             "mul8": 35, "mul8x4": 32, "sqrt18": 7}


def _by_level(units: List[Unit]) -> List[Unit]:
    return [units[0]] + sorted(units[1:], key=lambda u: (u.level, u.family))


def _grid(kind: Kind) -> List[Unit]:
    n, m = kind.width_a, kind.width_b
    out = [Unit(kind, "exact", 0)]
    if kind.op == "add":
        for fam in ("trunc", "loa", "lox", "aca", "seg"):
            for k in range(1 if fam != "seg" else 2, n):
                out.append(Unit(kind, fam, k, (k,)))
    elif kind.op == "sub":
        for fam in ("trunc", "loa"):
            for k in range(1, n - 2):
                out.append(Unit(kind, fam, k, (k,)))
    elif kind.op == "mul":
        out += [Unit(kind, "rtrunc", k, (k,)) for k in range(1, n)]
        out += [Unit(kind, "otrunc", ka + kb, (ka, kb))
                for ka in range(0, min(n, 6)) for kb in range(0, min(m, 4))
                if ka or kb]
        out += [Unit(kind, "broken", k, (k,)) for k in range(1, min(m, 5))]
        out += [Unit(kind, "mitchell", 8 - c, (c,)) for c in (0, 1, 2, 3)]
        out += [Unit(kind, "drum", 8 - q, (q,)) for q in (3, 4, 5, 6)]
    else:
        out += [Unit(kind, "itrunc", k, (k,)) for k in (1, 2, 3, 4)]
        out += [Unit(kind, "pwl", 6, (4,)), Unit(kind, "newton", 2, (4,))]
        return out
    return _by_level(out)


@dataclass(frozen=True)
class Entry:
    unit: Unit
    mae: float
    mre: float
    mse: float
    wce: float
    area: float
    power: float
    latency: float


def _char_inputs(kind: Kind):
    na, nb = kind.width_a, kind.width_b
    if kind.op == "sqrt":
        a = np.arange(1 << min(na, 18), dtype=np.int32)
        return jnp.asarray(a), jnp.asarray(np.zeros_like(a))
    if na + nb <= 20:
        a = np.repeat(np.arange(1 << na, dtype=np.int32), 1 << nb)
        b = np.tile(np.arange(1 << nb, dtype=np.int32), 1 << na)
    else:
        rng = np.random.default_rng(0xA55A)
        a = rng.integers(0, 1 << na, 1 << 16, dtype=np.int32)
        b = rng.integers(0, 1 << nb, 1 << 16, dtype=np.int32)
    return jnp.asarray(a), jnp.asarray(b)


def _errors(u: Unit, a, b) -> Dict[str, float]:
    exact = Unit(u.kind, "exact", 0).fn()(a, b)
    err = (u.fn()(a, b) - exact).astype(jnp.float32)
    den = jnp.maximum(jnp.abs(exact.astype(jnp.float32)), 1.0)
    return {"mae": float(jnp.mean(jnp.abs(err))),
            "mre": float(jnp.mean(jnp.abs(err) / den)),
            "mse": float(jnp.mean(err ** 2)),
            "wce": float(jnp.max(jnp.abs(err) / den))}


_FA = (4.5, 2.0, 2.5)       # full adder: area, delay, power
_GATE = (1.0, 0.6, 0.5)


def _hash_jitter(name: str, salt: str) -> float:
    h = int(hashlib.sha256(f"{name}:{salt}".encode()).hexdigest()[:8], 16)
    return 1.0 + ((h % 600) - 300) / 10_000.0


def _ppa(u: Unit) -> Dict[str, float]:
    n, m, f, p = u.kind.width_a, u.kind.width_b, u.family, u.param
    FA, FD, FP = _FA
    GA, GD, GP = _GATE
    if u.kind.op in ("add", "sub"):
        cut = p[0] if p else 0
        eff = n - cut
        if f == "exact":
            a, d, w = n * FA, n * FD, n * FP
        elif f == "trunc":
            a, d, w = eff * FA, eff * FD, eff * FP
        elif f in ("loa", "lox"):
            a, d, w = eff * FA + cut * GA, eff * FD + GD, eff * FP + cut * GP
        elif f == "aca":
            a = eff * FA + cut * FA * 0.6 + GA
            d, w = eff * FD + GD, eff * FP + cut * FP * 0.5
        else:
            a, d, w = n * FA * 1.05, p[0] * FD + GD, n * FP * 0.9
    elif u.kind.op == "mul":
        cells, base = n * m, (n + m) * FD * 0.75
        if f == "exact":
            a, d, w = cells * FA, base, cells * FP * 0.8
        elif f == "rtrunc":
            eff = cells - p[0] * (p[0] + 1) // 2
            a, d, w = eff * FA, base * (1 - 0.3 * p[0] / (n + m)), eff * FP * 0.8
        elif f == "otrunc":
            eff = (n - p[0]) * (m - p[1])
            a, d, w = eff * FA, (n - p[0] + m - p[1]) * FD * 0.75, eff * FP * 0.8
        elif f == "broken":
            eff = n * (m - p[0])
            a, d, w = eff * FA, (n + m - p[0]) * FD * 0.75, eff * FP * 0.8
        elif f == "mitchell":
            c = p[0]
            a = (3 * (n + m) + c * 4) * FA * 0.5
            d, w = (math.log2(n) * 2 + c) * FD, (2 * (n + m) + c * 3) * FP * 0.4
        else:
            q = p[0]
            a = (q * q + 2 * (n + m)) * FA * 0.7
            d, w = (2 * q + math.log2(n)) * FD * 0.8, (q * q + n + m) * FP * 0.6
    else:
        st = n // 2
        if f == "exact":
            a, d, w = st * (n / 2) * FA, st * FD * 1.5, st * (n / 2) * FP * 0.7
        elif f == "itrunc":
            eff = (n - 2 * p[0]) // 2
            a, d = eff * (n / 2 - p[0]) * FA, eff * FD * 1.5
            w = eff * (n / 2 - p[0]) * FP * 0.7
        elif f == "pwl":
            a, d, w = 4 * n * FA * 0.4, (math.log2(n) + 3) * FD, 3 * n * FP * 0.3
        else:
            a = (4 * n + n * n / 8) * FA * 0.5
            d, w = (math.log2(n) + 8) * FD, (3 * n + n * n / 10) * FP * 0.4
    j = _hash_jitter(u.name, "ppa")
    return {"area": a * j, "power": w * j,
            "latency": d * _hash_jitter(u.name, "lat")}


@functools.lru_cache(maxsize=None)
def library(kind_name: str) -> Tuple[Entry, ...]:
    kind = KINDS[kind_name]
    a, b = _char_inputs(kind)
    return tuple(Entry(u, **_errors(u, a, b), **_ppa(u))
                 for u in _grid(kind)[:TABLE_III[kind_name]])


def _vec(e: Entry) -> np.ndarray:
    return np.array([e.mse, e.area, e.power, e.latency])


def _kmeans_assign(X, k, seed=0, iters=50):
    rng = np.random.default_rng(seed)
    centers = X[rng.choice(len(X), size=k, replace=False)]
    assign = np.zeros(len(X), np.int64)
    for _ in range(iters):
        new = ((X[:, None] - centers[None]) ** 2).sum(-1).argmin(-1)
        if np.all(new == assign):
            break
        assign = new
        for c in range(k):
            if (assign == c).any():
                centers[c] = X[assign == c].mean(0)
    return assign


@functools.lru_cache(maxsize=None)
def pruned(kind_name: str, theta: float) -> Tuple[Entry, ...]:
    """The design space of one unit kind: dominated entries out, then one
    entry per K-means cluster of diameter <= theta, exact kept."""
    full = library(kind_name)
    V = np.stack([_vec(e) for e in full])
    valid = [e for i, e in enumerate(full)
             if not any(np.all(V[j] <= V[i]) and np.any(V[j] < V[i])
                        for j in range(len(full)) if j != i)]
    if len(valid) <= 2:
        keep = list(valid)
    else:
        W = np.stack([_vec(e) for e in valid])
        Wn = W * (1.0 / (W.std(0) + 1e-9))
        for k in range(1, len(valid) + 1):
            assign = _kmeans_assign(Wn, k)
            ok = True
            for c in range(k):
                pts = Wn[assign == c]
                if len(pts) > 1 and np.sqrt(((pts[:, None] - pts[None]) ** 2
                                             ).sum(-1)).max() \
                        > theta * np.sqrt(Wn.shape[1]):
                    ok = False
                    break
            if ok:
                break
        keep = []
        for c in range(k):
            members = [i for i in range(len(valid)) if assign[i] == c]
            exact = [i for i in members if valid[i].unit.level == 0]
            keep.append(valid[(exact or members)[0]])
        keep.sort(key=lambda e: (e.unit.level, e.unit.name))
    if not any(e.mse == 0 for e in keep):
        keep.insert(0, full[0])
    return tuple(keep)


# --------------------------------------------------------------------------
# accelerators: the configuration's DAG plus its functional model
# --------------------------------------------------------------------------

def _roll(img, dy, dx):
    return jnp.roll(img, (-dy, -dx), axis=(-2, -1))


def _run_sobel(u, g):
    p = {(dy, dx): _roll(g, dy, dx) for dy in (-1, 0, 1) for dx in (-1, 0, 1)}
    gxp = u["a12_1"](u["a8_1"](p[(-1, 1)], p[(1, 1)]), p[(0, 1)] << 1)
    gxn = u["a12_1"](u["a8_1"](p[(-1, -1)], p[(1, -1)]), p[(0, -1)] << 1)
    gyp = u["a12_2"](u["a8_2"](p[(1, -1)], p[(1, 1)]), p[(1, 0)] << 1)
    gyn = u["a12_2"](u["a8_2"](p[(-1, -1)], p[(-1, 1)]), p[(-1, 0)] << 1)
    mag = u["a12_2"](jnp.abs(u["s10"](gxp, gxn)), jnp.abs(u["s10"](gyp, gyn)))
    return jnp.clip(mag >> 3, 0, 255)


_GAUSS = ((-1, -1, 1), (-1, 0, 2), (-1, 1, 1), (0, -1, 2), (0, 0, 4),
          (0, 1, 2), (1, -1, 1), (1, 0, 2), (1, 1, 1))


def _run_gaussian(u, g):
    m = [u[f"m{i}"](_roll(g, dy, dx), jnp.full_like(g, w))
         for i, (dy, dx, w) in enumerate(_GAUSS)]
    t5 = u["a4"](u["a0"](m[0], m[1]), u["a1"](m[2], m[3]))
    t6 = u["a5"](u["a2"](m[4], m[5]), u["a3"](m[6], m[7]))
    return jnp.clip(u["a7"](u["a6"](t5, t6), m[8]) >> 4, 0, 255)


_CENTERS = np.array([[70, 80, 90], [180, 170, 160]], np.int32)


def _run_kmeans(u, img):
    dists = []
    for c in range(2):
        sq = []
        for j, ch in enumerate("rgb"):
            x = img[..., j]
            d = jnp.abs(u[f"s_{c}{ch}"](x, jnp.full_like(x, int(_CENTERS[c, j]))))
            sq.append(u[f"m_{c}{ch}"](d, d) >> 2)
        acc = u[f"a_{c}"](u[f"a_{c}"](sq[0], sq[1]), sq[2])
        dists.append(u[f"q_{c}"](acc << 2, None))
    gray = jnp.asarray(_CENTERS.mean(axis=1).astype(np.int32))
    return gray[(dists[1] < dists[0]).astype(jnp.int32)]


_DCT = np.round(np.array(
    [[(1.0 / np.sqrt(8) if q == 0 else 0.5) * np.cos((2 * k + 1) * q * np.pi / 16)
      for k in range(4)] for q in range(8)]) * 29).astype(np.int32)


def _dct_1d(u, v):
    s = [u[f"b{k}"](v[..., k], v[..., 7 - k]) for k in range(4)]
    d = [u[f"d{k}"](v[..., k], v[..., 7 - k]) for k in range(4)]
    outs = []
    for q in range(8):
        src = s if q % 2 == 0 else d
        pr = []
        for k in range(4):
            c = int(_DCT[q, k])
            x = src[k]
            m = u[f"m{k}"](jnp.abs(x), jnp.full_like(x, abs(c)))
            pr.append(jnp.where((x < 0) ^ (c < 0), -m, m))
        outs.append(u["a2"](u["a0"](pr[0], pr[1]), u["a1"](pr[2], pr[3])))
    return jnp.stack(outs, -1)


def _run_dct8(u, img):
    N, H, W = img.shape
    h8, w8 = (H // 8) * 8, (W // 8) * 8
    rows = _dct_1d(u, img[:, :h8, :w8].reshape(N, h8, w8 // 8, 8)) >> 6
    t = rows.reshape(N, h8, w8).transpose(0, 2, 1).reshape(N, w8, h8 // 8, 8)
    out = (_dct_1d(u, t) >> 6).reshape(N, w8, h8).transpose(0, 2, 1)
    return jnp.clip(out, -255, 255)


def _run_fir15(u, g):
    tap = {k: jnp.roll(g, -k, axis=-1) for k in range(-7, 8)}
    pre = [u[f"p{k}"](tap[k - 7], tap[7 - k]) for k in range(7)]
    pr = [u[f"m{k}"](pre[k], jnp.full_like(g, k + 1)) for k in range(7)]
    pr.append(u["m7"](tap[0], jnp.full_like(g, 8)))
    t5 = u["a0"](u["a0"](pr[0], pr[1]), u["a1"](pr[2], pr[3]))
    t6 = u["a1"](u["a2"](pr[4], pr[5]), u["a3"](pr[6], pr[7]))
    return jnp.clip(u["a2"](t5, t6) >> 6, 0, 255)


RUNS = {"sobel": _run_sobel, "gaussian": _run_gaussian,
        "kmeans": _run_kmeans, "dct8": _run_dct8, "fir15": _run_fir15}


@functools.lru_cache(maxsize=16)
def image_set(n: int, size: int, seed: int = 500) -> np.ndarray:
    """(n, size, size, 3) uint8 synthetic natural-like images."""
    rng = np.random.default_rng(seed)
    yy, xx = np.mgrid[0:size, 0:size].astype(np.float32) / size
    imgs = []
    for _ in range(n):
        base = np.zeros((size, size, 3), np.float32)
        for _ in range(3):
            fx, fy = rng.uniform(0.5, 4, 2)
            ph = rng.uniform(0, 2 * np.pi)
            amp = rng.uniform(20, 60)
            wave = amp * np.sin(2 * np.pi * (fx * xx + fy * yy) + ph)
            base += wave[..., None] * rng.uniform(0.4, 1.0, 3)
        for _ in range(4):
            cy, cx = rng.uniform(0.1, 0.9, 2)
            r = rng.uniform(0.05, 0.3)
            base[((yy - cy) ** 2 + (xx - cx) ** 2) < r ** 2] += \
                rng.uniform(-70, 70, 3)
        base += rng.normal(0, 6, base.shape)
        base = base - base.min()
        imgs.append(base / max(base.max(), 1e-6) * 255.0)
    return np.stack(imgs).astype(np.uint8)


def app_inputs(app: str, n: int, size: int, seed: int = 500):
    imgs = image_set(n, size, seed)
    if app == "kmeans":
        return jnp.asarray(imgs.astype(np.int32))
    w = np.array([0.299, 0.587, 0.114], np.float32)
    return jnp.asarray((imgs.astype(np.float32) @ w).astype(np.int32))


def ssim(a, b, data_range=255.0):
    """Mean SSIM over 8x8 windows of an (N, H, W) image pair, in float32."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    N, H, W = a.shape
    h8, w8 = (H // 8) * 8, (W // 8) * 8
    aw = a[:, :h8, :w8].reshape(N, h8 // 8, 8, w8 // 8, 8)
    bw = b[:, :h8, :w8].reshape(N, h8 // 8, 8, w8 // 8, 8)
    mu_a, mu_b = aw.mean((2, 4)), bw.mean((2, 4))
    var_a, var_b = aw.var((2, 4)), bw.var((2, 4))
    cov = (aw * bw).mean((2, 4)) - mu_a * mu_b
    c1, c2 = (0.01 * data_range) ** 2, (0.03 * data_range) ** 2
    s = ((2 * mu_a * mu_b + c1) * (2 * cov + c2)) / (
        (mu_a ** 2 + mu_b ** 2 + c1) * (var_a + var_b + c2))
    return s.mean()


# --------------------------------------------------------------------------
# one configuration: the accelerator, its space, synthesis and features
# --------------------------------------------------------------------------

FIXED_PPA = {"mem": (220.0, 35.0, 4.0), "abs": (12.0, 3.0, 2.5),
             "cmp": (18.0, 4.0, 3.0), "div": (450.0, 60.0, 0.0),
             "shift": (2.0, 0.5, 0.5)}
WIRE_PER_FANOUT = 0.35
KIND_VOCAB = ("add8", "add12", "add16", "sub10", "mul8", "mul8x4", "sqrt18",
              "mem", "div", "cmp", "abs", "shift")
# feature schema v2: 8 unit stats, 7 timing columns (the crit bit first),
# the kind one-hot; `NORMALIZE` marks the standardized columns
UNIT_STATS = 8
CRIT_COL = 8
DYN = ("slack", "criticality", "err_mae", "err_wce", "probe_err8",
       "probe_err16")
N_FEAT = UNIT_STATS + 1 + len(DYN) + len(KIND_VOCAB)
NORMALIZE = np.array([True] * UNIT_STATS + [False] + [True] * len(DYN)
                     + [False] * len(KIND_VOCAB))
PROBE_SEED = 77


class Accelerator:
    """One configuration's accelerator: DAG, design space, oracle."""

    def __init__(self, cfg: Dict):
        self.name = cfg["app"]
        self.run = RUNS[self.name]
        self.nodes = [(n["id"], n["kind"], bool(n["fixed"]))
                      for n in cfg["nodes"]]
        self.edges = [tuple(e) for e in cfg["edges"]]
        self.units = [(i, k) for i, k, f in self.nodes if not f]
        self.space = [pruned(k, float(cfg["theta"])) for _, k in self.units]
        self.n_pad = int(cfg["n_pad"])
        self._dag()
        self._graph()

    # -- structure ---------------------------------------------------------

    def _dag(self):
        g = nx.DiGraph()
        g.add_nodes_from(i for i, _, _ in self.nodes)
        for u, v in self.edges:
            if u == v:
                continue
            g.add_edge(u, v)
            if not nx.is_directed_acyclic_graph(g):
                g.remove_edge(u, v)      # registered feedback edge
        self.order = list(nx.topological_sort(g))
        self.succ = {i: [v for _, v in g.out_edges(i)] for i in self.order}
        self.wire = {i: WIRE_PER_FANOUT * max(g.out_degree(i), 1)
                     for i in self.order}

    def _graph(self):
        """The simplified graph: fixed nodes with the same kind, the same
        predecessors and the same successor kinds merge, to fixpoint."""
        kind = {i: k for i, k, _ in self.nodes}
        fixed = {i: f for i, _, f in self.nodes}
        ids = [i for i, _, _ in self.nodes]
        preds = {i: set() for i in ids}
        succs = {i: set() for i in ids}
        for u, v in self.edges:
            preds[v].add(u)
            succs[u].add(v)
        groups = {i: (i,) for i in ids}
        changed = True
        while changed:
            changed = False
            sig: Dict = {}
            for i in ids:
                if fixed[i]:
                    sig.setdefault((kind[i], frozenset(preds[i]),
                                    frozenset(kind[x] for x in succs[i])),
                                   []).append(i)
            for same in sig.values():
                if len(same) < 2:
                    continue
                keep = same[0]
                for r in same[1:]:
                    for p in preds[r]:
                        succs[p].discard(r)
                        succs[p].add(keep)
                        preds[keep].add(p)
                    for s in succs[r]:
                        preds[s].discard(r)
                        preds[s].add(keep)
                        succs[keep].add(s)
                    ids.remove(r)
                    groups[keep] += groups[r]
                    del groups[r], preds[r], succs[r]
                changed = True
        n = len(ids)
        idx = {i: k for k, i in enumerate(ids)}
        a = np.zeros((n, n), np.float32)
        for i in ids:
            for s in succs[i]:
                if s in idx:
                    a[idx[i], idx[s]] = 1.0
        a = np.minimum(a + a.T + np.eye(n, dtype=np.float32), 1.0)
        dinv = 1.0 / np.sqrt(np.maximum(a.sum(-1), 1e-6))
        adj = np.zeros((self.n_pad, self.n_pad), np.float32)
        adj[:n, :n] = (a * dinv[:, None]) * dinv[None, :]
        self.adj = adj
        self.mask = np.zeros(self.n_pad, np.float32)
        self.mask[:n] = 1.0
        self.gnodes = [(i, kind[i], fixed[i], groups[i]) for i in ids]

    # -- one configuration -------------------------------------------------

    def choice(self, config: Sequence[int]) -> Dict[str, Entry]:
        return {nid: self.space[j][int(c)]
                for j, ((nid, _), c) in enumerate(zip(self.units, config))}

    def _delays(self, ch):
        out = {}
        for i, k, f in self.nodes:
            lat = FIXED_PPA[k][2] if f else ch[i].latency
            out[i] = lat + self.wire[i]
        return out

    def exact_output(self, images):
        return self.run(_impls({i: library(k)[0] for i, k in self.units}),
                        images)

    def timing(self, config) -> Dict[str, Dict[str, float]]:
        """Per app node: slack, criticality, accumulated mae/wce."""
        ch = self.choice(config)
        delay = self._delays(ch)
        arrive = {i: delay[i] for i in self.order}
        for i in self.order:
            for v in self.succ[i]:
                arrive[v] = max(arrive[v], arrive[i] + delay[v])
        tmax = max(arrive.values())
        req = {i: (tmax if not self.succ[i] else float("inf"))
               for i in self.order}
        for i in reversed(self.order):
            for v in self.succ[i]:
                req[i] = min(req[i], req[v] - delay[v])
        out = {i: {"slack": (req[i] - arrive[i]) / tmax,
                   "criticality": arrive[i] / tmax} for i in self.order}
        for key in ("mae", "wce"):
            acc = {i: (0.0 if f else float(getattr(ch[i], key)))
                   for i, _, f in self.nodes}
            for i in self.order:
                for v in self.succ[i]:
                    acc[v] += acc[i]
            for i in self.order:
                out[i][f"err_{key}"] = acc[i]
        return out

    @functools.cached_property
    def _accuracy_fn(self):
        """jit(vmap) over configs: each unit picks its entry's function by
        the config's index among all of the space's functions."""
        fns = [[e.unit.fn() for e in sp] for sp in self.space]
        ids = [nid for nid, _ in self.units]

        def pick(options, k):
            return lambda a, b=None: jnp.stack([f(a, b) for f in options])[k]

        def one(c, images, exact):
            impls = {nid: pick(fns[j], c[j]) for j, nid in enumerate(ids)}
            return ssim(self.run(impls, images), exact)

        return jax.jit(jax.vmap(one, in_axes=(0, None, None)))

    def accuracy_batch(self, configs, images, exact,
                       block: int = 64) -> np.ndarray:
        """(B,) float64 SSIM of each config's output against ``exact``."""
        C = np.asarray(configs, np.int32).reshape(len(configs), -1)
        out = np.empty(len(C), np.float64)
        for lo in range(0, len(C), block):
            part = C[lo:lo + block]
            pad = np.concatenate([part, np.repeat(part[-1:], block - len(part),
                                                  0)])
            out[lo:lo + len(part)] = np.asarray(
                self._accuracy_fn(jnp.asarray(pad), images, exact)
            )[:len(part)]
        return out

    @functools.cached_property
    def probes(self):
        out = []
        for size in (8, 16):
            inp = app_inputs(self.name, 1, size, PROBE_SEED)
            out.append((inp, self.exact_output(inp)))
        return out

    def raw_features(self, configs) -> np.ndarray:
        """(B, n_pad, N_FEAT) float32 schema-v2 features, crit bit 0."""
        probe = np.stack([1.0 - self.accuracy_batch(configs, inp, ex)
                          for inp, ex in self.probes], 1)
        x = np.zeros((len(configs), self.n_pad, N_FEAT), np.float32)
        for b, config in enumerate(configs):
            ch = self.choice(config)
            tim = self.timing(config)
            for r, (nid, k, f, members) in enumerate(self.gnodes):
                if f:
                    a, p, l = FIXED_PPA[k]
                    x[b, r, :UNIT_STATS] = [a, p, l, 0, 0, 0, 0, 0]
                else:
                    e = ch[nid]
                    x[b, r, :UNIT_STATS] = [e.area, e.power, e.latency, e.mae,
                                            e.mre, e.mse, e.wce,
                                            float(e.unit.level)]
                for c, fld in enumerate(DYN):
                    if fld.startswith("probe"):
                        v = probe[b, c - 4]
                    else:
                        vals = [tim[m][fld] for m in members]
                        v = min(vals) if fld == "slack" else max(vals)
                        if fld.startswith("err"):
                            v = float(np.log1p(v))
                    x[b, r, CRIT_COL + 1 + c] = np.float32(v)
                x[b, r, CRIT_COL + 1 + len(DYN) + KIND_VOCAB.index(k)] = 1.0
        return x


def _impls(choice: Dict[str, Entry]) -> Dict[str, Callable]:
    return {nid: e.unit.fn() for nid, e in choice.items()}


def normalize(x: np.ndarray, mask: np.ndarray, x_mean: np.ndarray,
              x_std: np.ndarray) -> np.ndarray:
    return ((x - x_mean) / x_std * mask[:, None]).astype(np.float32)


# --------------------------------------------------------------------------
# the two-stage GraphSAGE-mean surrogate
# --------------------------------------------------------------------------

def _split(x):
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)


def _mm(a, b, prec: str):
    """a @ b at a stated precision: ``highest`` is float32; ``high`` is the
    three-pass bfloat16 product (each operand split into a bfloat16 head
    and tail, the tail-by-tail pass dropped), written out so that it reads
    the same on every platform."""
    hi = jax.lax.Precision.HIGHEST
    if prec == "highest":
        return jnp.matmul(a, b, precision=hi)
    if prec == "high_native":       # the platform's own three-pass product
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGH)
    (ah, al), (bh, bl) = _split(a), _split(b)
    return (jnp.matmul(ah, bh, precision=hi) + jnp.matmul(ah, bl, precision=hi)
            + jnp.matmul(al, bh, precision=hi))


def _stack(p, adj, x, mask, prec):
    deg = jnp.maximum(adj.sum(-1, keepdims=True), 1e-6)
    h = x * mask[..., None]
    for lp in p["layers"]:
        nbr = _mm(adj, h, prec) / deg
        h = _mm(h, lp["w_self"], prec) + _mm(nbr, lp["w_nbr"], prec) + lp["b"]
        h = jax.nn.relu(h) * mask[..., None]
    return h


def _node_head(p, h, prec):
    z = jax.nn.relu(_mm(h, p["ro_w1"], prec) + p["ro_b1"])
    return (_mm(z, p["ro_w2"], prec) + p["ro_b2"])[..., 0]


def _graph_head(p, h, mask, prec):
    mean = (h * mask[..., None]).sum(1) / jnp.maximum(
        mask.sum(-1, keepdims=True), 1.0)
    mx = jnp.where(mask[..., None] > 0, h, -1e30).max(1)
    g = jax.nn.relu(_mm(jnp.concatenate([mean, mx], -1), p["ro_w1"], prec)
                    + p["ro_b1"])
    return _mm(g, p["ro_w2"], prec) + p["ro_b2"]


@functools.partial(jax.jit, static_argnames=("prec",))
def crit_logits(params, adj, x, mask, prec):
    """(B, N) stage-1 logits; x has the crit column at 0."""
    B = x.shape[0]
    A = jnp.broadcast_to(adj, (B,) + adj.shape)
    M = jnp.broadcast_to(mask, (B,) + mask.shape)
    return _node_head(params[0], _stack(params[0], A, x, M, prec), prec)


@functools.partial(jax.jit, static_argnames=("prec",))
def targets(params, adj, x, mask, bits, prec):
    """(B, 4) normalized stage-2 targets with the crit column set to
    ``bits`` (B, N)."""
    B = x.shape[0]
    A = jnp.broadcast_to(adj, (B,) + adj.shape)
    M = jnp.broadcast_to(mask, (B,) + mask.shape)
    x2 = x.at[..., CRIT_COL].set(bits * M)
    return _graph_head(params[1], _stack(params[1], A, x2, M, prec), M, prec)


AMBIGUOUS_LOGIT = 1e-3
MAX_AMBIGUOUS = 6


def surrogate_gaps(params, adj, mask, X, got_norm, prec: str) -> np.ndarray:
    """Per row, the largest |got - reference| over the four normalized
    targets. The stage-1 bit is a threshold (logit > 0): where a node's
    reference logit lies within `AMBIGUOUS_LOGIT` of it, either bit is
    a sound answer, and the row takes the closer of the alternatives."""
    p = prec
    A, M = jnp.asarray(adj), jnp.asarray(mask)
    logits = np.asarray(crit_logits(params, A, jnp.asarray(X), M, prec=p))
    bits = (logits > 0).astype(np.float32)
    want = np.asarray(targets(params, A, jnp.asarray(X), M, jnp.asarray(bits),
                              prec=p))
    gaps = np.abs(np.asarray(got_norm) - want).max(1)
    amb = (np.abs(logits) < AMBIGUOUS_LOGIT) & (mask[None, :] > 0)
    for r in np.where(amb.any(1))[0]:
        nodes = np.where(amb[r])[0][:MAX_AMBIGUOUS]
        alts = []
        for flip in range(1, 1 << len(nodes)):
            b = bits[r].copy()
            for j, n in enumerate(nodes):
                if flip >> j & 1:
                    b[n] = 1.0 - b[n]
            alts.append(b)
        w = np.asarray(targets(params, A, jnp.asarray(X[r:r + 1]).repeat(
            len(alts), 0), M, jnp.asarray(np.stack(alts)), prec=p))
        gaps[r] = min(gaps[r], np.abs(got_norm[r][None] - w).max(1).min())
    return gaps
