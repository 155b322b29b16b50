"""Benchmark accelerators: Sobel, Gaussian, K-means, DCT-8, FIR-15.

Each accelerator is (a) a dataflow graph over *physical* arithmetic-unit
instances (Table-II-style counts: Sobel 2xadd8+2xadd12+1xsub10, Gaussian
8xadd16+9xmul8x4, Kmeans 2xadd16+6xsub10+6xmul8+2xsqrt18, DCT-8
4xadd8+4xsub10+4xmul8x4+3xadd16, FIR-15 7xadd8+8xmul8x4+4xadd16) plus
fixed components (memories, abs, comparators, dividers), and (b) a
vectorized functional model: the same physical unit is REUSED for every
operation mapped onto it, exactly like the streamed RTL the paper
synthesizes (the DCT butterfly runs both the row and the column pass of
the 2D transform; the FIR adder tree folds 7 additions onto 4 adders).

Accuracy = mean SSIM between approximate and exact outputs on the image set.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Callable, Dict, List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.accel import library as lib
from repro.accel import units as units_lib


@dataclass(frozen=True)
class Node:
    id: str
    kind: str                 # unit kind ("add8"...) or fixed kind
    fixed: bool = False


@dataclass(frozen=True)
class AccelDef:
    name: str
    nodes: Tuple[Node, ...]
    edges: Tuple[Tuple[str, str], ...]
    run: Callable                 # (impls: {unit_id: fn}, images) -> images

    @property
    def unit_nodes(self) -> List[Node]:
        return [n for n in self.nodes if not n.fixed]

    def space_size(self, counts=None) -> float:
        s = 1.0
        L = lib.TABLE_III if counts is None else counts
        for n in self.unit_nodes:
            s *= L[n.kind]
        return s


def _win(img: jax.Array, dy: int, dx: int) -> jax.Array:
    """3x3 neighbor with replicate padding; img: (..., H, W) int32."""
    return jnp.roll(img, (-dy, -dx), axis=(-2, -1))


# --------------------------------------------------------------------------
# Sobel
# --------------------------------------------------------------------------

def _sobel_run(impls: Dict[str, Callable], images: jax.Array) -> jax.Array:
    """images: (N,H,W) grayscale int32 [0,255] -> edge magnitude (N,H,W)."""
    g = images
    p = {(dy, dx): _win(g, dy, dx) for dy in (-1, 0, 1) for dx in (-1, 0, 1)}
    a8_1, a8_2 = impls["a8_1"], impls["a8_2"]
    a12_1, a12_2, s10 = impls["a12_1"], impls["a12_2"], impls["s10"]
    # Gx = (p(+1 col) + 2 mid) - (p(-1 col) + 2 mid)
    gxp = a12_1(a8_1(p[(-1, 1)], p[(1, 1)]), p[(0, 1)] << 1)
    gxn = a12_1(a8_1(p[(-1, -1)], p[(1, -1)]), p[(0, -1)] << 1)
    gyp = a12_2(a8_2(p[(1, -1)], p[(1, 1)]), p[(1, 0)] << 1)
    gyn = a12_2(a8_2(p[(-1, -1)], p[(-1, 1)]), p[(-1, 0)] << 1)
    gx = jnp.abs(s10(gxp, gxn))          # abs is fixed logic
    gy = jnp.abs(s10(gyp, gyn))
    mag = a12_2(gx, gy)                  # reuse a12_2 for |gx|+|gy|
    return jnp.clip(mag >> 3, 0, 255)


SOBEL = AccelDef(
    name="sobel",
    nodes=(
        Node("img_mem", "mem", fixed=True),
        Node("a8_1", "add8"), Node("a8_2", "add8"),
        Node("a12_1", "add12"), Node("a12_2", "add12"),
        Node("s10", "sub10"),
        Node("abs1", "abs", fixed=True), Node("abs2", "abs", fixed=True),
        Node("out_mem", "mem", fixed=True),
    ),
    edges=(
        ("img_mem", "a8_1"), ("img_mem", "a8_2"),
        ("img_mem", "a12_1"), ("img_mem", "a12_2"),
        ("a8_1", "a12_1"), ("a8_2", "a12_2"),
        ("a12_1", "s10"), ("a12_2", "s10"),
        ("s10", "abs1"), ("s10", "abs2"),
        ("abs1", "a12_2"), ("abs2", "a12_2"),
        ("a12_2", "out_mem"),
    ),
    run=_sobel_run,
)


# --------------------------------------------------------------------------
# Gaussian 3x3 (coeffs 1,2,1 / 2,4,2 / 1,2,1, /16)
# --------------------------------------------------------------------------

_GAUSS_W = {(-1, -1): 1, (-1, 0): 2, (-1, 1): 1,
            (0, -1): 2, (0, 0): 4, (0, 1): 2,
            (1, -1): 1, (1, 0): 2, (1, 1): 1}


def _gauss_run(impls: Dict[str, Callable], images: jax.Array) -> jax.Array:
    g = images
    taps = list(_GAUSS_W.items())
    m = [impls[f"m{i}"](_win(g, dy, dx), jnp.full_like(g, w))
         for i, ((dy, dx), w) in enumerate(taps)]
    a = impls
    t1 = a["a0"](m[0], m[1])
    t2 = a["a1"](m[2], m[3])
    t3 = a["a2"](m[4], m[5])
    t4 = a["a3"](m[6], m[7])
    t5 = a["a4"](t1, t2)
    t6 = a["a5"](t3, t4)
    t7 = a["a6"](t5, t6)
    t8 = a["a7"](t7, m[8])
    return jnp.clip(t8 >> 4, 0, 255)


GAUSSIAN = AccelDef(
    name="gaussian",
    nodes=tuple(
        [Node("img_mem", "mem", fixed=True), Node("coeff_rom", "mem", fixed=True)]
        + [Node(f"m{i}", "mul8x4") for i in range(9)]
        + [Node(f"a{i}", "add16") for i in range(8)]
        + [Node("shift", "shift", fixed=True), Node("out_mem", "mem", fixed=True)]),
    edges=tuple(
        [("img_mem", f"m{i}") for i in range(9)]
        + [("coeff_rom", f"m{i}") for i in range(9)]
        + [("m0", "a0"), ("m1", "a0"), ("m2", "a1"), ("m3", "a1"),
           ("m4", "a2"), ("m5", "a2"), ("m6", "a3"), ("m7", "a3"),
           ("a0", "a4"), ("a1", "a4"), ("a2", "a5"), ("a3", "a5"),
           ("a4", "a6"), ("a5", "a6"), ("a6", "a7"), ("m8", "a7"),
           ("a7", "shift"), ("shift", "out_mem")]),
    run=_gauss_run,
)


# --------------------------------------------------------------------------
# K-means (2 clusters x RGB, one assignment pass, AxBench-style segmentation)
# --------------------------------------------------------------------------

_CENTERS = np.array([[70, 80, 90], [180, 170, 160]], np.int32)


def _kmeans_run(impls: Dict[str, Callable], images: jax.Array) -> jax.Array:
    """images: (N,H,W,3) int32 RGB -> segmented grayscale (N,H,W)."""
    dists = []
    for c in range(2):
        sq = []
        for j, ch in enumerate("rgb"):
            d = impls[f"s_{c}{ch}"](images[..., j],
                                    jnp.full_like(images[..., j],
                                                  int(_CENTERS[c, j])))
            d = jnp.abs(d)                        # fixed abs
            sq.append(impls[f"m_{c}{ch}"](d, d) >> 2)   # fixed >>2 rescale
        acc = impls[f"a_{c}"](sq[0], sq[1])
        acc = impls[f"a_{c}"](acc, sq[2])         # physical adder reused
        dists.append(impls[f"q_{c}"](acc << 2, None))
    assign = (dists[1] < dists[0]).astype(jnp.int32)     # fixed comparator
    gray_centers = jnp.asarray(_CENTERS.mean(axis=1).astype(np.int32))
    return gray_centers[assign]


KMEANS = AccelDef(
    name="kmeans",
    nodes=tuple(
        [Node("img_mem", "mem", fixed=True), Node("cluster_mem", "mem", fixed=True),
         Node("center_mem1", "mem", fixed=True), Node("center_mem2", "mem", fixed=True),
         Node("center_mem3", "mem", fixed=True)]
        + [Node(f"s_{c}{ch}", "sub10") for c in range(2) for ch in "rgb"]
        + [Node(f"m_{c}{ch}", "mul8") for c in range(2) for ch in "rgb"]
        + [Node(f"a_{c}", "add16") for c in range(2)]
        + [Node(f"q_{c}", "sqrt18") for c in range(2)]
        + [Node("div1", "div", fixed=True), Node("div2", "div", fixed=True),
           Node("div3", "div", fixed=True), Node("cmp", "cmp", fixed=True)]),
    edges=tuple(
        [("img_mem", f"s_{c}{ch}") for c in range(2) for ch in "rgb"]
        + [(f"center_mem{j + 1}", f"s_{c}{ch}")
           for c in range(2) for j, ch in enumerate("rgb")]
        + [(f"s_{c}{ch}", f"m_{c}{ch}") for c in range(2) for ch in "rgb"]
        + [(f"m_{c}{ch}", f"a_{c}") for c in range(2) for ch in "rgb"]
        + [(f"a_{c}", f"q_{c}") for c in range(2)]
        + [(f"q_{c}", "cmp") for c in range(2)]
        + [("cmp", "cluster_mem")]
        + [("cluster_mem", f"div{j}") for j in (1, 2, 3)]
        + [(f"div{j}", f"center_mem{j}") for j in (1, 2, 3)]),
    run=_kmeans_run,
)

# --------------------------------------------------------------------------
# DCT-8 (2D 8x8 block transform, even/odd butterfly decomposition)
# --------------------------------------------------------------------------

# C[u,k] = alpha(u) cos((2k+1) u pi / 16), alpha(0)=sqrt(1/8) else 1/2,
# quantized to 4-bit magnitudes (scale 29 -> |c| <= 15). Symmetry
# cos((2(7-k)+1) u pi/16) = (-1)^u cos((2k+1) u pi/16) halves the
# multiplies: even-u rows consume the butterfly sums s_k = x_k + x_{7-k},
# odd-u rows the differences d_k = x_k - x_{7-k}.
_DCT_SCALE = 29
_DCT_C = np.round(np.array(
    [[(1.0 / np.sqrt(8) if u == 0 else 0.5)
      * np.cos((2 * k + 1) * u * np.pi / 16) for k in range(4)]
     for u in range(8)]) * _DCT_SCALE).astype(np.int32)


def _signed_mul(impl: Callable, x: jax.Array, c: int) -> jax.Array:
    """Sign-magnitude use of an unsigned multiplier: |x| * |c| through the
    physical unit, sign reapplied by fixed logic."""
    p = impl(jnp.abs(x), jnp.full_like(x, abs(int(c))))
    return jnp.where((x < 0) ^ (c < 0), -p, p)


def _dct8_1d(impls: Dict[str, Callable], v: jax.Array) -> jax.Array:
    """1D DCT-8 along the last axis (length 8); v signed int32."""
    s = [impls[f"b{k}"](v[..., k], v[..., 7 - k]) for k in range(4)]
    d = [impls[f"d{k}"](v[..., k], v[..., 7 - k]) for k in range(4)]
    outs = []
    for u in range(8):
        src = s if u % 2 == 0 else d
        prods = [_signed_mul(impls[f"m{k}"], src[k], int(_DCT_C[u, k]))
                 for k in range(4)]
        t0 = impls["a0"](prods[0], prods[1])
        t1 = impls["a1"](prods[2], prods[3])
        outs.append(impls["a2"](t0, t1))
    return jnp.stack(outs, -1)


def _dct8_run(impls: Dict[str, Callable], images: jax.Array) -> jax.Array:
    """images: (N,H,W) grayscale int32 -> 2D DCT coefficient blocks
    (same physical butterfly streams the row pass, then the column pass)."""
    N, H, W = images.shape
    h8, w8 = (H // 8) * 8, (W // 8) * 8
    g = images[:, :h8, :w8]
    rows = g.reshape(N, h8, w8 // 8, 8)
    rowed = _dct8_1d(impls, rows) >> 6              # fixed rescale shift
    t = rowed.reshape(N, h8, w8).transpose(0, 2, 1)
    cols = t.reshape(N, w8, h8 // 8, 8)
    coled = _dct8_1d(impls, cols) >> 6
    out = coled.reshape(N, w8, h8).transpose(0, 2, 1)
    return jnp.clip(out, -255, 255)


DCT8 = AccelDef(
    name="dct8",
    nodes=tuple(
        [Node("img_mem", "mem", fixed=True),
         Node("coeff_rom", "mem", fixed=True)]
        + [Node(f"b{k}", "add8") for k in range(4)]
        + [Node(f"d{k}", "sub10") for k in range(4)]
        + [Node(f"m{k}", "mul8x4") for k in range(4)]
        + [Node(f"a{k}", "add16") for k in range(3)]
        + [Node("shift", "shift", fixed=True),
           Node("out_mem", "mem", fixed=True)]),
    edges=tuple(
        [("img_mem", f"b{k}") for k in range(4)]
        + [("img_mem", f"d{k}") for k in range(4)]
        + [("coeff_rom", f"m{k}") for k in range(4)]
        + [(f"b{k}", f"m{k}") for k in range(4)]     # even-pass operands
        + [(f"d{k}", f"m{k}") for k in range(4)]     # odd-pass operands
        + [("m0", "a0"), ("m1", "a0"), ("m2", "a1"), ("m3", "a1"),
           ("a0", "a2"), ("a1", "a2"),
           ("a2", "shift"), ("shift", "out_mem")]),
    run=_dct8_run,
)


# --------------------------------------------------------------------------
# FIR-15 (symmetric 15-tap lowpass, pre-add folding + reused adder tree)
# --------------------------------------------------------------------------

# triangular window, sum 64; pair taps k and -k share coefficient k+1,
# center tap weight 8 — all 4-bit magnitudes for the mul8x4 port
_FIR_W = (1, 2, 3, 4, 5, 6, 7, 8)


def _fir15_run(impls: Dict[str, Callable], images: jax.Array) -> jax.Array:
    """images: (N,H,W) grayscale int32 -> horizontally lowpassed (N,H,W)."""
    g = images
    tap = {k: jnp.roll(g, -k, axis=-1) for k in range(-7, 8)}
    pre = [impls[f"p{k}"](tap[k - 7], tap[7 - k]) for k in range(7)]
    prods = [impls[f"m{k}"](pre[k], jnp.full_like(g, _FIR_W[k]))
             for k in range(7)]
    prods.append(impls["m7"](tap[0], jnp.full_like(g, _FIR_W[7])))
    t1 = impls["a0"](prods[0], prods[1])
    t2 = impls["a1"](prods[2], prods[3])
    t3 = impls["a2"](prods[4], prods[5])
    t4 = impls["a3"](prods[6], prods[7])
    t5 = impls["a0"](t1, t2)                        # physical adders reused
    t6 = impls["a1"](t3, t4)
    y = impls["a2"](t5, t6)
    return jnp.clip(y >> 6, 0, 255)


FIR15 = AccelDef(
    name="fir15",
    nodes=tuple(
        [Node("img_mem", "mem", fixed=True),
         Node("coeff_rom", "mem", fixed=True)]
        + [Node(f"p{k}", "add8") for k in range(7)]
        + [Node(f"m{k}", "mul8x4") for k in range(8)]
        + [Node(f"a{k}", "add16") for k in range(4)]
        + [Node("shift", "shift", fixed=True),
           Node("out_mem", "mem", fixed=True)]),
    edges=tuple(
        [("img_mem", f"p{k}") for k in range(7)]
        + [("img_mem", "m7")]                        # center tap
        + [("coeff_rom", f"m{k}") for k in range(8)]
        + [(f"p{k}", f"m{k}") for k in range(7)]
        + [("m0", "a0"), ("m1", "a0"), ("m2", "a1"), ("m3", "a1"),
           ("m4", "a2"), ("m5", "a2"), ("m6", "a3"), ("m7", "a3"),
           ("a1", "a0"),                             # t5 = a0(t1, t2)
           ("a2", "a1"), ("a3", "a1"),               # t6 = a1(t3, t4)
           ("a0", "a2"), ("a1", "a2"),               # y  = a2(t5, t6)
           ("a2", "shift"), ("shift", "out_mem")]),
    run=_fir15_run,
)

APPS: Dict[str, AccelDef] = {"sobel": SOBEL, "gaussian": GAUSSIAN,
                             "kmeans": KMEANS, "dct8": DCT8, "fir15": FIR15}


# --------------------------------------------------------------------------
# configuration -> functional model + SSIM accuracy
# --------------------------------------------------------------------------

def make_impls(app: AccelDef, choice: Dict[str, lib.LibEntry]
               ) -> Dict[str, Callable]:
    out = {}
    for n in app.unit_nodes:
        entry = choice[n.id]
        fn = entry.inst.fn()
        if entry.inst.kind.op == "sqrt":
            out[n.id] = lambda a, b=None, f=fn: f(a)
        else:
            out[n.id] = fn
    return out


def exact_choice(app: AccelDef) -> Dict[str, lib.LibEntry]:
    return {n.id: lib.build_library(n.kind)[0] for n in app.unit_nodes}


def ssim(a: jax.Array, b: jax.Array, data_range: float = 255.0) -> jax.Array:
    """Mean SSIM, 8x8 uniform windows, per image pair (N,H,W)."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    N, H, W = a.shape
    h8, w8 = (H // 8) * 8, (W // 8) * 8
    aw = a[:, :h8, :w8].reshape(N, h8 // 8, 8, w8 // 8, 8)
    bw = b[:, :h8, :w8].reshape(N, h8 // 8, 8, w8 // 8, 8)
    ax = (2, 4)
    mu_a = aw.mean(ax)
    mu_b = bw.mean(ax)
    var_a = aw.var(ax)
    var_b = bw.var(ax)
    cov = (aw * bw).mean(ax) - mu_a * mu_b
    c1 = (0.01 * data_range) ** 2
    c2 = (0.03 * data_range) ** 2
    s = ((2 * mu_a * mu_b + c1) * (2 * cov + c2)) / (
        (mu_a ** 2 + mu_b ** 2 + c1) * (var_a + var_b + c2))
    return s.mean()


def accuracy_ssim(app: AccelDef, choice: Dict[str, lib.LibEntry],
                  images: jax.Array, exact_out: jax.Array | None = None
                  ) -> float:
    approx = app.run(make_impls(app, choice), images)
    if exact_out is None:
        exact_out = app.run(make_impls(app, exact_choice(app)), images)
    return float(ssim(approx, exact_out))


# --------------------------------------------------------------------------
# functional probe (schema-v2 dynamic features)
# --------------------------------------------------------------------------
#
# Static unit error profiles (mae/wce over uniform operands) miss how an
# app actually exercises its units: gaussian/dct8 multipliers see FIXED
# coefficient operands, and the composition (shifts, clips, adder trees)
# reshapes the error before it reaches the output. The probe runs the
# REAL config-batched functional model on one tiny image per scale and
# reports the distortion 1 - SSIM — two graph-level features that carry
# the composed error structure no per-unit table can. Two scales on
# purpose: the 8x8 probe resolves block-local distortion (one DCT block,
# strong signal for smoothing kernels), the 16x16 probe the longer-range
# structure. Tiny images keep it hot-path cheap: 64-256 pixels vs the
# 4x64x64 labeling set, through the SAME cached `_batch_label_fn`.

PROBE_SIZES = (8, 16)
PROBE_SEED = 77
PROBE_FIELDS = tuple(f"probe_err{s}" for s in PROBE_SIZES)


@functools.lru_cache(maxsize=None)
def probe_inputs(app_name: str, size: int) -> Tuple[jax.Array, jax.Array]:
    """(images, exact_out) for the functional probe at one scale —
    deterministic (PROBE_SEED), computed once per (app, size)."""
    from repro.data import images as images_lib
    app = APPS[app_name]
    imgs = images_lib.image_set(1, size, seed=PROBE_SEED)
    if app_name == "kmeans":
        inp = jnp.asarray(imgs.astype(np.int32))
    else:
        inp = jnp.asarray(images_lib.gray(imgs))
    exact_out = app.run(make_impls(app, exact_choice(app)), inp)
    return inp, exact_out


def probe_scalar(app: AccelDef, choice: Dict[str, lib.LibEntry]
                 ) -> Dict[str, float]:
    """Scalar-reference probe distortions {probe_err8, probe_err16} for
    one configuration (the loop labeling backend / parity tests; the
    batched path is `batch_oracle.probe_batch`)."""
    out = {}
    for size in PROBE_SIZES:
        inp, exact_out = probe_inputs(app.name, size)
        out[f"probe_err{size}"] = 1.0 - accuracy_ssim(app, choice, inp,
                                                      exact_out)
    return out


# --------------------------------------------------------------------------
# config-batched functional model (batched ground-truth labeling)
# --------------------------------------------------------------------------
#
# `accuracy_ssim` re-traces and re-dispatches the whole functional model
# once per configuration — the dataset-construction hot spot. The batched
# path evaluates a (B, n_units) block of configurations through ONE traced
# program:
#
#   * multipliers and sqrt (the transcendental-heavy families: mitchell,
#     drum, pwl, newton) go through stacked LUT truth tables
#     (`library.stacked_lut`) with the per-config library choice folded
#     into the table index, read by one XLA gather on every platform; a
#     squarer, fed one operand twice, reads its row of the tables'
#     diagonals (`library.lut_diagonal`) instead, by a compare-select-sum
#     on a TPU and a gather elsewhere (`lut_square`);
#   * adders/subtractors, whose widened truth tables would need 2^24-2^32
#     entries, are evaluated analytically with the family id and cut
#     parameter as traced per-config scalars (`units.addsub_batched`);
#   * the per-config closure is vmapped over the config axis and jitted,
#     so each app traces once per (entries, image-shape) instead of once
#     per config, and the vectorized SSIM reduces straight to (B,) scores.


class LutDomainError(RuntimeError):
    """An app drove a LUT-tabulated unit outside its table domain."""


def lut_gather(table: jax.Array, a: jax.Array, b: jax.Array,
               wb: int) -> jax.Array:
    """Read a truth table at ``(a << wb) | b``: one XLA gather (the
    labeler's LUT path on every platform), under the name scope
    ``lut_gather``, which the compiled ops carry in their metadata."""
    with jax.named_scope("lut_gather"):
        return jnp.take(table, (a << wb) | b, axis=0)


def lut_square_select(rows: jax.Array, e: jax.Array,
                      a: jax.Array) -> jax.Array:
    """A squarer's read, ``m(a, a)``, as an exact int32 compare-select-sum
    of ``a`` against entry ``e``'s row of its tables' diagonals
    (`library.lut_diagonal`): one nonzero term, 0 where ``a`` leaves the
    row, which the caller's guard reports."""
    hit = a[..., None] == jnp.arange(rows.shape[-1], dtype=a.dtype)
    return jnp.sum(jnp.where(hit, rows[e], 0), axis=-1)


def lut_square_take(rows: jax.Array, e: jax.Array,
                    a: jax.Array) -> jax.Array:
    """The same read as `lut_square_select`, by a gather of the flattened
    diagonals at ``(e << w) | a`` (`lut_gather`)."""
    return lut_gather(rows.reshape(-1), e, a, rows.shape[-1].bit_length() - 1)


def lut_square(rows: jax.Array, e: jax.Array, a: jax.Array) -> jax.Array:
    """A squarer's read, ``m(a, a)``, from entry ``e``'s row of its
    tables' diagonals, in the form that suits the platform; both return
    the same integers on the table's domain. On a TPU the compare-select-
    sum (`lut_square_select`): a v5e gather costs about as much per index
    from a 47 KB table as from a 24 MB one, and at the probe's shapes the
    compare takes about a ninth of it (``benchmarks/lut_square_forms.py``).
    Elsewhere the gather (`lut_square_take`), which a CPU runs over a
    hundred times faster than the 512-lane compare."""
    return jax.lax.platform_dependent(rows, e, a, tpu=lut_square_select,
                                      default=lut_square_take)


class Labeler(NamedTuple):
    """The compiled labeler of one app over one library (`batch_labeler`).

    ``fn(C, images, exact_out) -> ((B,) ssim, guards)``; ``guard_meta``
    maps each guard tag to its unit kind and LUT domain; both are filled
    when the model is traced. ``lut_reads(images)`` is the number of
    table entries one configuration's model reads on ``images``, and
    ``lut_square_reads(images)`` those of them a squarer reads from its
    table's diagonal, both counted while tracing it."""
    fn: Callable
    guard_meta: Dict[str, Tuple[str, int, int]]
    lut_reads: Callable[[jax.Array], int]
    lut_square_reads: Callable[[jax.Array], int]


def _entries_items(app: AccelDef, entries: Dict[str, Sequence]
                   ) -> Tuple[Tuple[str, Tuple[lib.LibEntry, ...]], ...]:
    """Hashable (kind, entries) signature restricted to the app's kinds."""
    kinds = {n.kind for n in app.unit_nodes}
    return tuple(sorted((k, tuple(entries[k])) for k in kinds))


@functools.lru_cache(maxsize=64)
def _batch_label_fn(app_name: str, entries_items) -> Labeler:
    """Compiled labeler: (C (B,U) int32, images, exact_out) -> ((B,) ssim,
    guard dict); two jitted stages (vmapped functional model, vmapped
    SSIM). `guard_meta` maps guard tags to LUT domains, read by the
    caller to validate table coverage; it and the table reads per
    configuration and image shape are filled at trace time (`Labeler`)."""
    app = APPS[app_name]
    entries = dict(entries_items)
    guard_meta: Dict[str, Tuple[str, int, int]] = {}
    reads: Dict[Tuple[int, ...], Tuple[int, int]] = {}

    tables: Dict[Tuple[str, Callable], jax.Array] = {}

    def device_table(kind_name, host_fn):
        # ``host_fn`` (`library.stacked_lut` or `lut_diagonal`) of one LUT
        # kind on the device, shared by the app's units of that kind and
        # uploaded by the first trace that reads it (kmeans's squarers
        # never read their full 24 MB table)
        if (kind_name, host_fn) not in tables:
            ea, eb = lib.lut_domain(app_name, kind_name)
            with jax.ensure_compile_time_eval():
                tables[kind_name, host_fn] = jnp.asarray(
                    host_fn(tuple(entries[kind_name]), ea, eb))
        return tables[kind_name, host_fn]

    node_data = []
    for node in app.unit_nodes:
        ent = tuple(entries[node.kind])
        kind = units_lib.KINDS[node.kind]
        if node.kind in lib.LUT_DOMAINS:
            ea, eb = lib.lut_domain(app_name, node.kind)
            node_data.append(("lut", node, kind, ea, eb))
        else:
            fam, k, seg = lib.addsub_dispatch(ent)
            node_data.append(("analytic", node, kind, jnp.asarray(fam),
                              jnp.asarray(k), jnp.asarray(seg)))

    def _lut_impl(node, kind, ea, eb, e, guards, counts, n_read):
        unary = kind.op == "sqrt"

        def excess(x, bits):
            # >0 iff x leaves [0, 2^bits), by how much; ONE reduction per
            # operand (reductions are costly here: a consuming reduction
            # makes XLA CPU re-evaluate the operand's fused producers)
            return jnp.max(jnp.maximum(-x, x - ((1 << bits) - 1)))

        def impl(a, b=None):
            tag = f"{node.id}#{counts.setdefault(node.id, 0)}"
            counts[node.id] += 1
            guard_meta[tag] = (kind.name, ea, eb)
            n_read[0] += a.size          # one table entry per operand
            zero = jnp.zeros((), jnp.int32)
            if b is a:
                # one operand fed twice (kmeans's m(d, d)): only the
                # table's diagonal can be read
                ex = excess(a, min(ea, eb))
                guards[tag] = (ex, ex)
                n_read[1] += a.size
                return lut_square(device_table(node.kind, lib.lut_diagonal),
                                  e, a)
            table = device_table(node.kind, lib.stacked_lut)
            if unary:
                guards[tag] = (excess(a, ea), zero)
                af = ((e << ea) | a).reshape(-1)
                return lut_gather(table, af, jnp.zeros_like(af), 0
                                  ).reshape(a.shape)
            const_b = None
            if not isinstance(b, jax.core.Tracer):
                vals = np.unique(np.asarray(b))
                if vals.size == 1 and 0 <= int(vals[0]) < (1 << eb):
                    const_b = int(vals[0])
            af = ((e << ea) | a).reshape(-1)
            if const_b is not None:
                # constant coefficient operand (gaussian taps, FIR weights,
                # DCT cosines): checked at trace time, and its column is
                # sliced out of the table up front so the gather runs
                # against a 2^ea-per-entry table that lives in cache
                guards[tag] = (excess(a, ea), zero)
                sub = table.reshape(-1, 1 << eb)[:, const_b]
                out = lut_gather(sub, af, jnp.zeros_like(af), 0)
            else:
                guards[tag] = (excess(a, ea), excess(b, eb))
                out = lut_gather(table, af, b.reshape(-1), eb)
            return out.reshape(a.shape)

        return impl

    def _analytic_impl(kind, fam_arr, k_arr, seg_arr, e):
        def impl(a, b):
            return units_lib.addsub_batched(kind.op, kind.width_a,
                                            fam_arr[e], k_arr[e],
                                            seg_arr[e], a, b)
        return impl

    def model_chunk(C, images):
        n_read = [0, 0]             # table entries read, of them squares

        def model_one(cfg):
            impls, guards, counts = {}, {}, {}
            for j, nd in enumerate(node_data):
                if nd[0] == "lut":
                    _, node, kind, ea, eb = nd
                    impls[node.id] = _lut_impl(node, kind, ea, eb, cfg[j],
                                               guards, counts, n_read)
                else:
                    _, node, kind, fam, k, seg = nd
                    impls[node.id] = _analytic_impl(kind, fam, k, seg,
                                                    cfg[j])
            return app.run(impls, images), guards
        out = jax.vmap(model_one)(C)
        reads[tuple(images.shape)] = tuple(n_read)
        return out

    def ssim_chunk(out, exact_out):
        return jax.vmap(lambda o: ssim(o, exact_out))(out)

    # two jits on purpose: compiled together, XLA CPU fuses the whole
    # model into each SSIM moment reduction and re-evaluates it once per
    # moment (optimization_barrier does not stop it); materializing the
    # (B, ...) outputs between the stages keeps the model single-pass
    def run_chunk(C, images, exact_out):
        out, guards = _jit_model(C, images)
        return _jit_ssim(out, exact_out), guards

    def lut_counts(images) -> Tuple[int, int]:
        if not any(nd[0] == "lut" for nd in node_data):
            return 0, 0
        if tuple(images.shape) not in reads:
            # trace (never compile) one configuration's model
            jax.eval_shape(model_chunk, jax.ShapeDtypeStruct(
                (1, len(node_data)), jnp.int32), images)
        return reads[tuple(images.shape)]

    _jit_model = jax.jit(model_chunk)
    _jit_ssim = jax.jit(ssim_chunk)
    return Labeler(run_chunk, guard_meta,
                   lambda images: lut_counts(images)[0],
                   lambda images: lut_counts(images)[1])


def _check_lut_guards(app: AccelDef, guard_meta, guards) -> None:
    for tag, (ex_a, ex_b) in guards.items():
        kind_name, ea, eb = guard_meta[tag]
        over_a, over_b = int(np.max(ex_a)), int(np.max(ex_b))
        if over_a > 0 or over_b > 0:
            raise LutDomainError(
                f"{app.name}: unit {tag} ({kind_name}) left its LUT domain "
                f"(2^{ea}, 2^{eb}) by up to a:{max(over_a, 0)} "
                f"b:{max(over_b, 0)}; widen "
                f"repro.accel.library.LUT_DOMAINS[{kind_name!r}] (or the "
                f"APP_LUT_DOMAINS override for {app.name!r})")


def batch_labeler(app: AccelDef, entries: Dict[str, Sequence]) -> Labeler:
    """The compiled labeler of `app` over `entries` (`_batch_label_fn`).
    A caller that labels many batches resolves it once: the cache lookup
    hashes and compares every library entry."""
    return _batch_label_fn(app.name, _entries_items(app, entries))


def _padded_chunks(C: np.ndarray, chunk: int):
    """Yield ``(lo, take, block)`` over ``C`` in chunks of at most `chunk`
    rows. Ragged blocks are padded up to a power-of-two bucket (capped at
    the chunk size) with a repeated row, so the jit cache holds at most
    log2(chunk)+1 model shapes no matter what batch sizes callers send —
    same policy as the engine's fixed-shape chunking; ``block[:take]``
    are the real rows."""
    for lo in range(0, C.shape[0], chunk):
        Cc = C[lo:lo + chunk]
        take = Cc.shape[0]
        bucket = 1
        while bucket < take:
            bucket <<= 1
        bucket = min(bucket, chunk)
        if take < bucket:
            Cc = np.concatenate([Cc, np.repeat(Cc[-1:], bucket - take, 0)])
        yield lo, take, Cc


def accuracy_ssim_batch(app: AccelDef, entries: Dict[str, Sequence],
                        configs, images: jax.Array,
                        exact_out: jax.Array | None = None, *,
                        chunk: int = 256) -> np.ndarray:
    """SSIM labels for a batch of configurations: (B,) float64.

    ``configs`` is a (B, n_units) int block of library-entry indices (the
    `dataset.sample_configs` layout). Images are evaluated through the
    config-batched functional model in fixed-size chunks
    (`ssim_batch_on_device`); once every chunk is dispatched the LUT
    guards are checked and the scores read back.
    """
    if exact_out is None:
        exact_out = app.run(make_impls(app, exact_choice(app)), images)
    (scores,), check = ssim_batch_on_device(
        app, batch_labeler(app, entries), configs, [(images, exact_out)],
        chunk=chunk)
    check()
    return np.asarray(scores, np.float64)


def ssim_batch_on_device(app: AccelDef, labeler, configs,
                         image_sets: Sequence[Tuple[jax.Array, jax.Array]],
                         *, chunk: int = 256
                         ) -> Tuple[Tuple[jax.Array, ...], Callable[[], None]]:
    """SSIM of a config block against several image sets, left on the
    device: the compiled programs of ``labeler`` (`batch_labeler`) on
    padded chunks (`_padded_chunks`), each chunk sent to the device once
    for all the ``(images, exact_out)`` sets, nothing read back.

    Returns one (B,) float32 device array of SSIM scores per image set,
    and ``check()``, which reads the LUT guards and raises
    `LutDomainError` if any unit left its table's domain; call it before
    trusting the scores. `accuracy_ssim_batch` is this with one image set,
    read back.
    """
    fn, guard_meta = labeler.fn, labeler.guard_meta
    C = np.asarray(configs, np.int32).reshape(len(configs), -1)
    parts: List[List[jax.Array]] = [[] for _ in image_sets]
    guards = []
    for _, take, Cc in _padded_chunks(C, chunk):
        Cd = jax.device_put(Cc)
        for part, (images, exact_out) in zip(parts, image_sets):
            scores, g = fn(Cd, images, exact_out)
            part.append(scores if take == scores.shape[0] else scores[:take])
            guards.append(g)

    def check() -> None:
        # one fetch of every chunk's guards: their copies overlap, where
        # one read per guard would wait a round trip each
        for g in jax.device_get(guards):
            _check_lut_guards(app, guard_meta, g)

    return tuple(p[0] if len(p) == 1 else jnp.concatenate(p)
                 for p in parts), check
