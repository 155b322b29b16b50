"""Time three forms of the functional probe's squarer lookup on one device.

    PYTHONPATH=src python benchmarks/lut_square_forms.py [--reps 20]

kmeans squares each channel distance through a ``mul8`` unit, ``m(d, d)``,
whose values lie in the stacked (9, 9) truth table. The three forms below
return the same integers at the probe's shapes (a block of 512
configurations, vmapped, over the probe's 16x16 and 8x8 images, one library
entry per configuration, six squarers):

* ``full``: `apps.lut_gather` into the stacked table, ``entries << 18``
  values;
* ``diag``: `apps.lut_square_take`, a gather from the tables' diagonals,
  ``entries << 9`` values;
* ``select``: `apps.lut_square_select`, the configuration's 512-value
  diagonal row, then an int32 compare-select-sum of the operand against
  ``iota(512)``.

The tables are random (a gather's time does not depend on the values it
reads); the operands lie in [0, 384), as kmeans's ``|sub10|`` outputs do.
Each timed program runs the six lookups ``--reps`` times in a
``fori_loop``, so dispatch is paid once. Prints one JSON line: milliseconds
per 512-configuration chunk for each form and image size, and whether the
forms agree.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.accel import apps

W = 9                      # LUT_DOMAINS["mul8"] = (9, 9)
ENTRIES = 23               # kmeans's pruned mul8 entries
CONFIGS = 512              # the engine's chunk
SQUARERS = 6


def forms(table: np.ndarray):
    diag = table.reshape(-1, 1 << W, 1 << W)[:, np.arange(1 << W),
                                             np.arange(1 << W)]
    full_t = jnp.asarray(table)
    rows_t = jnp.asarray(diag)

    def full(e, a):
        return apps.lut_gather(full_t, (e << W) | a, a, W)

    return {"full": full,
            "diag": lambda e, a: apps.lut_square_take(rows_t, e, a),
            "select": lambda e, a: apps.lut_square_select(rows_t, e, a)}


def chunk_program(lookup, reps: int):
    """``reps`` rounds of six vmapped lookups; each round's entries and
    operands depend on the round index, so nothing (not even ``select``'s
    read of a configuration's row) is hoisted out of the loop."""
    one = jax.vmap(lookup)

    def body(i, acc):
        e, a, tot = acc
        outs = [one((e + i + s) % ENTRIES, (a + i + s) % 384)
                for s in range(SQUARERS)]
        return e, a, tot + sum(o.sum() for o in outs)

    def run(e, a):
        return jax.lax.fori_loop(0, reps, body, (e, a, jnp.int32(0)))[2]

    return jax.jit(run)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--trials", type=int, default=5)
    args = ap.parse_args()

    rng = np.random.default_rng(0)
    table = rng.integers(0, 1 << 17, ENTRIES << (2 * W), dtype=np.int32)
    fs = forms(table)
    e = jnp.asarray(rng.integers(0, ENTRIES, CONFIGS, dtype=np.int32))
    out = {"device": jax.devices()[0].device_kind, "reps": args.reps,
           "ms_per_chunk": {}}
    agree = True
    for side in (16, 8):
        a = jnp.asarray(rng.integers(0, 384, (CONFIGS, 1, side, side),
                                     dtype=np.int32))
        ref = np.asarray(jax.jit(jax.vmap(fs["full"]))(e, a))
        for name, f in fs.items():
            agree &= bool(np.array_equal(
                np.asarray(jax.jit(jax.vmap(f))(e, a)), ref))
            prog = chunk_program(f, args.reps)
            prog(e, a).block_until_ready()          # compile and warm
            best = float("inf")
            for _ in range(args.trials):
                t0 = time.perf_counter()
                prog(e, a).block_until_ready()
                best = min(best, time.perf_counter() - t0)
            out["ms_per_chunk"][f"{name}.{side * side}px"] = \
                1e3 * best / args.reps
    out["agree"] = agree
    print(json.dumps(out))


if __name__ == "__main__":
    main()
