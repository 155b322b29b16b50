"""Approximate arithmetic unit families (EvoApprox-style, JAX-vectorized).

Every unit is a pure elementwise function on int32 arrays, so the functional
accelerator models evaluate whole images in one vectorized call. Families
mirror the published approximate-circuit literature:

  adders/subtractors : TRUNC (truncated LSBs), LOA (lower-bits OR, Mahdiani),
                       ACA (approximate carry), SEG (segmented, ETAII-like)
  multipliers        : RTRUNC (result truncation), OTRUNC (operand
                       truncation, possibly asymmetric), BROKEN (broken-array
                       rows, Kulkarni-style), MITCHELL (log multiplier w/
                       correction terms), DRUM (dynamic-range unbiased)
  sqrt               : ITRUNC (input truncation), PWL (piecewise-linear seed),
                       NEWTON (1 Newton iteration from PWL seed)

The instance grid is generated in library.py to match the paper's Table III
counts exactly (31/26/21 adders, 12 sub, 35+32 mult, 7 sqrt).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable, Tuple

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class UnitKind:
    op: str          # add | sub | mul | sqrt
    width_a: int
    width_b: int     # 0 for sqrt

    @property
    def name(self) -> str:
        if self.op == "mul" and self.width_a != self.width_b:
            return f"mul{self.width_a}x{self.width_b}"
        if self.op == "sqrt":
            return f"sqrt{self.width_a}"
        return f"{self.op}{self.width_a}"


ADD8 = UnitKind("add", 8, 8)
ADD12 = UnitKind("add", 12, 12)
ADD16 = UnitKind("add", 16, 16)
SUB10 = UnitKind("sub", 10, 10)
MUL8 = UnitKind("mul", 8, 8)
MUL8X4 = UnitKind("mul", 8, 4)
SQRT18 = UnitKind("sqrt", 18, 0)

KINDS = {k.name: k for k in (ADD8, ADD12, ADD16, SUB10, MUL8, MUL8X4, SQRT18)}


def host():
    """Context in which unit functions are evaluated for a truth table or a
    characterization: the host's CPU, whose float32 arithmetic is IEEE.
    The float families (mitchell, drum, pwl, newton) truncate results of
    float32 ``log2``, ``exp2`` and division to integers, so an accelerator
    that rounds one ulp elsewhere moves a table entry by one; on the host
    every platform builds the tables the plain reference defines."""
    return jax.default_device(jax.devices("cpu")[0])


def _mask(k: int) -> int:
    return (1 << k) - 1


# --------------------------------------------------------------------------
# adders / subtractors
# --------------------------------------------------------------------------

def add_exact(a, b, n):
    return a + b


def add_trunc(a, b, n, k):
    return ((a >> k) + (b >> k)) << k


def add_loa(a, b, n, k):
    lower = (a | b) & _mask(k)
    return (((a >> k) + (b >> k)) << k) | lower


def add_aca(a, b, n, k):
    """Approximate carry: carry into the upper part is a_{k-1} & b_{k-1}."""
    carry = (a >> (k - 1)) & (b >> (k - 1)) & 1
    lower = (a + b) & _mask(k)
    return (((a >> k) + (b >> k) + carry) << k) | lower


def add_lox(a, b, n, k):
    """LOA variant: lower k bits XOR'ed (no carry generate at all)."""
    lower = (a ^ b) & _mask(k)
    return (((a >> k) + (b >> k)) << k) | lower


def add_seg(a, b, n, k):
    """Segmented (ETAII-like): carry chains cut every k bits."""
    out = jnp.zeros_like(a)
    for lo in range(0, n, k):
        sa = (a >> lo) & _mask(k)
        sb = (b >> lo) & _mask(k)
        out = out | (((sa + sb) & _mask(k)) << lo)
    # keep the top segment's carry-out so magnitude is preserved
    top = n - (n % k or k)
    sa = (a >> top)
    sb = (b >> top)
    return (out & _mask(top)) | ((sa + sb) << top)


def sub_exact(a, b, n):
    return a - b


def sub_trunc(a, b, n, k):
    return ((a >> k) - (b >> k)) << k


def sub_loa(a, b, n, k):
    lower = (a ^ b) & _mask(k)
    return (((a >> k) - (b >> k)) << k) | lower


# --------------------------------------------------------------------------
# multipliers
# --------------------------------------------------------------------------

def mul_exact(a, b, na, nb):
    return a * b


def mul_rtrunc(a, b, na, nb, k):
    return ((a * b) >> k) << k


def mul_otrunc(a, b, na, nb, ka, kb):
    return ((a >> ka) * (b >> kb)) << (ka + kb)


def mul_broken(a, b, na, nb, k):
    """Broken-array: the k least-significant partial-product rows dropped."""
    return a * ((b >> k) << k)


def _ilog2(x):
    xf = jnp.maximum(x, 1).astype(jnp.float32)
    return jnp.floor(jnp.log2(xf)).astype(jnp.int32)


def mul_mitchell(a, b, na, nb, c):
    """Mitchell log multiplier with c correction bits on the fraction add."""
    za = _ilog2(a)
    zb = _ilog2(b)
    fa = (a.astype(jnp.float32) / jnp.exp2(za.astype(jnp.float32))) - 1.0
    fb = (b.astype(jnp.float32) / jnp.exp2(zb.astype(jnp.float32))) - 1.0
    if c > 0:  # quantize fractions to c bits (the "correction" datapath width)
        q = float(1 << c)
        fa = jnp.floor(fa * q) / q
        fb = jnp.floor(fb * q) / q
    s = fa + fb
    exp = (za + zb).astype(jnp.float32)
    approx = jnp.where(s < 1.0, jnp.exp2(exp) * (1.0 + s),
                       jnp.exp2(exp + 1.0) * s)
    approx = jnp.where((a == 0) | (b == 0), 0.0, approx)
    return approx.astype(jnp.int32)


def mul_drum(a, b, na, nb, m):
    """DRUM: keep the m MSBs of each operand, set dropped LSB for unbiasing."""
    def trim(x, n):
        z = _ilog2(x)
        sh = jnp.maximum(z - (m - 1), 0)
        return (((x >> sh) | 1) << sh) * (x > 0)
    return trim(a, na) * trim(b, nb)


# --------------------------------------------------------------------------
# sqrt
# --------------------------------------------------------------------------

def _isqrt_exact(x):
    """Integer sqrt via float + fixup (exact for x < 2^24)."""
    r = jnp.floor(jnp.sqrt(x.astype(jnp.float32))).astype(jnp.int32)
    r = jnp.where((r + 1) * (r + 1) <= x, r + 1, r)
    r = jnp.where(r * r > x, r - 1, r)
    return jnp.maximum(r, 0)


def sqrt_exact(x, n):
    return _isqrt_exact(x)


def sqrt_itrunc(x, n, k):
    """sqrt(x >> 2k) << k — drops 2k input LSBs."""
    return _isqrt_exact(x >> (2 * k)) << k


def sqrt_pwl(x, n, seg):
    """Piecewise-linear: r = 2^(z/2) * (1 + f/2) with f quantized to `seg`."""
    z = _ilog2(x)
    f = x.astype(jnp.float32) / jnp.exp2(z.astype(jnp.float32)) - 1.0
    if seg > 0:
        q = float(1 << seg)
        f = jnp.floor(f * q) / q
    r = jnp.exp2(z.astype(jnp.float32) / 2.0) * (1.0 + f / 2.0)
    return jnp.where(x == 0, 0, r.astype(jnp.int32))


def sqrt_newton(x, n, seg):
    r0 = sqrt_pwl(x, n, seg).astype(jnp.float32)
    r0 = jnp.maximum(r0, 1.0)
    r = 0.5 * (r0 + x.astype(jnp.float32) / r0)
    return jnp.where(x == 0, 0, r.astype(jnp.int32))


# --------------------------------------------------------------------------
# config-batched dispatch (batched ground-truth labeling)
# --------------------------------------------------------------------------

# family ids for the analytic per-config adder/subtractor dispatch used by
# the batched functional model (apps.accuracy_ssim_batch). The multiplier
# and sqrt families are evaluated through LUT truth tables instead
# (library.stacked_lut), so they need no ids here.
FAM_IDS = {"exact": 0, "trunc": 1, "loa": 2, "lox": 3, "aca": 4, "seg": 5}


def seg_kill_mask(n: int, k: int) -> int:
    """Carry-kill mask for `add_seg(n, k)`: one bit below every segment
    boundary (multiples of ``k`` strictly inside the ``n``-bit word)."""
    return sum(1 << (c - 1) for c in range(k, n, k))


def addsub_batched(op: str, n: int, fam, k, seg_mask, a, b):
    """Approximate add/sub with the library choice as *traced* values.

    ``fam``/``k``/``seg_mask`` are per-config scalars (family id from
    FAM_IDS, cut parameter, `seg_kill_mask`), so one trace covers every
    configuration in a batch; the scalar functions above treat them as
    Python constants and would retrace per config. Bit-exact vs the
    scalar families: each branch is the same expression with the
    parameter sanitized where another family's ``k`` would be out of
    range. ``seg``'s per-segment Python loop becomes a SWAR partitioned
    add — clearing the bit below each boundary in both operands stops
    the carry from crossing it, and the xor restores that bit's true
    sum — which is the segmented sum for *any* cut with the boundary
    pattern as data.
    """
    if op == "sub":
        k_t = jnp.where(fam == FAM_IDS["trunc"], k, 0)
        res = ((a >> k_t) - (b >> k_t)) << k_t       # exact == trunc @ k=0
        loa = (((a >> k) - (b >> k)) << k) | ((a ^ b) & ((1 << k) - 1))
        return jnp.where(fam == FAM_IDS["loa"], loa, res)
    if op != "add":
        raise ValueError(f"addsub_batched handles add/sub, not {op!r}")
    k_t = jnp.where(fam == FAM_IDS["trunc"], k, 0)
    res = ((a >> k_t) + (b >> k_t)) << k_t           # exact == trunc @ k=0
    upper = ((a >> k) + (b >> k)) << k
    m = (1 << k) - 1
    res = jnp.where(fam == FAM_IDS["loa"], upper | ((a | b) & m), res)
    res = jnp.where(fam == FAM_IDS["lox"], upper | ((a ^ b) & m), res)
    k1 = jnp.maximum(k, 1)                           # aca needs k >= 1
    carry = (a >> (k1 - 1)) & (b >> (k1 - 1)) & 1
    aca = ((((a >> k1) + (b >> k1)) + carry) << k1) | ((a + b) & ((1 << k1) - 1))
    res = jnp.where(fam == FAM_IDS["aca"], aca, res)
    seg = ((a & ~seg_mask) + (b & ~seg_mask)) ^ ((a ^ b) & seg_mask)
    return jnp.where(fam == FAM_IDS["seg"], seg, res)


# --------------------------------------------------------------------------
# instance descriptor
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class UnitInstance:
    kind: UnitKind
    family: str
    level: int       # approximation level, 0 = exact
    param: Tuple[int, ...] = ()

    @property
    def name(self) -> str:
        p = "_".join(str(x) for x in self.param)
        return f"{self.kind.name}_{self.family}" + (f"_{p}" if p else "")

    def fn(self) -> Callable:
        k = self.kind
        fam, prm = self.family, self.param
        if k.op == "add":
            table = {"exact": lambda a, b: add_exact(a, b, k.width_a),
                     "trunc": lambda a, b: add_trunc(a, b, k.width_a, *prm),
                     "loa": lambda a, b: add_loa(a, b, k.width_a, *prm),
                     "lox": lambda a, b: add_lox(a, b, k.width_a, *prm),
                     "aca": lambda a, b: add_aca(a, b, k.width_a, *prm),
                     "seg": lambda a, b: add_seg(a, b, k.width_a, *prm)}
        elif k.op == "sub":
            table = {"exact": lambda a, b: sub_exact(a, b, k.width_a),
                     "trunc": lambda a, b: sub_trunc(a, b, k.width_a, *prm),
                     "loa": lambda a, b: sub_loa(a, b, k.width_a, *prm)}
        elif k.op == "mul":
            table = {"exact": lambda a, b: mul_exact(a, b, k.width_a, k.width_b),
                     "rtrunc": lambda a, b: mul_rtrunc(a, b, k.width_a,
                                                       k.width_b, *prm),
                     "otrunc": lambda a, b: mul_otrunc(a, b, k.width_a,
                                                       k.width_b, *prm),
                     "broken": lambda a, b: mul_broken(a, b, k.width_a,
                                                       k.width_b, *prm),
                     "mitchell": lambda a, b: mul_mitchell(a, b, k.width_a,
                                                           k.width_b, *prm),
                     "drum": lambda a, b: mul_drum(a, b, k.width_a,
                                                   k.width_b, *prm)}
        else:  # sqrt (unary: b ignored)
            table = {"exact": lambda a, b=None: sqrt_exact(a, k.width_a),
                     "itrunc": lambda a, b=None: sqrt_itrunc(a, k.width_a, *prm),
                     "pwl": lambda a, b=None: sqrt_pwl(a, k.width_a, *prm),
                     "newton": lambda a, b=None: sqrt_newton(a, k.width_a, *prm)}
        return table[fam]

    def lut(self, ea: int | None = None, eb: int | None = None) -> jax.Array:
        """Materialized truth table over a (possibly widened) input domain.

        ``ea``/``eb`` are the *effective* operand bit widths; they default
        to the nominal kind widths but the batched functional model widens
        them (library.LUT_DOMAINS) because app dataflows legally feed
        values beyond the nominal width (e.g. DCT butterfly sums into the
        mul8x4 port). The unit functions are well defined on the wider
        ints, so the widened table agrees with direct evaluation. Unary
        sqrt tables use ``eb=0`` -> (2^ea,). Computed on the host's CPU
        (`host`), where the table stays until a caller moves it.
        """
        ea = self.kind.width_a if ea is None else ea
        eb = self.kind.width_b if eb is None else eb
        fn = self.fn()
        with host():
            if self.kind.op == "sqrt":
                return fn(jnp.arange(1 << ea, dtype=jnp.int32)
                          ).astype(jnp.int32)
            a = jnp.repeat(jnp.arange(1 << ea, dtype=jnp.int32), 1 << eb)
            b = jnp.tile(jnp.arange(1 << eb, dtype=jnp.int32), 1 << ea)
            return fn(a, b).astype(jnp.int32)
