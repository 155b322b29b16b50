"""The ``wave`` traffic: back-to-back waves of fresh configurations through
one engine call each (the screening and random-sampler traffic of a
search).

Parameters, from the traffic file: ``wave`` (configurations per engine
call); ``max_rate`` (configurations per second that the window's pool of
fresh configurations is drawn for: a window that outruns the pool starts
it again with the engine's memo cleared, so no answer comes from the
memo); ``check_rows`` (rows the check compares with the reference);
``eval_devices`` (the engine's ``devices``, default 1).

A loop file defines ``Loop(cfg, traffic, seed, seconds, program)`` with

* ``setup()``: ``build()`` what the cell serves and warm up every shape
  its traffic will use, then ``prepare()`` the window's inputs from the
  seed (all timed as set-up; `calibrate.py` builds once and prepares once
  per seed);
* ``window()``: the measured loop of ``seconds``, inside the
  ``bench.window`` span, every call into a layer inside a ``bench.*`` span
  of its own; ``t0`` is the window's start;
* ``e2e()``: the cell's end-to-end numbers; ``attempted``; ``counters``,
  what the per-layer readers may read;
* ``check(control=False)``: the comparison with the plain reference, as
  ``(numbers, failed)``; with ``control`` the reference itself, computed
  one precision below what the configuration states, stands in the
  program's place.

Every seed draws the same amount of work of the same sizes.
"""
from __future__ import annotations

import math
import time
from typing import Dict, List

import numpy as np
from jax.profiler import TraceAnnotation

import reference as ref
from surrogate import Surrogate, draw_configs

WINDOW = "bench.window"


class Loop:

    def __init__(self, cfg, traffic, seed, seconds, program):
        self.cfg, self.t, self.seed, self.program = cfg, traffic, seed, program
        self.seconds = seconds
        self.counters: Dict = {}

    def setup(self):
        self.build()
        self.prepare()

    def build(self):
        self.s = Surrogate(self.cfg, self.program,
                           devices=int(self.t.get("eval_devices", 1)))
        self.warmed: set = set()
        warm = draw_configs(np.random.default_rng([self.seed, 2]),
                            self.s.sizes, 2 * int(self.cfg["eval_chunk"]),
                            self.warmed)
        self.s.engine(warm)

    def prepare(self):
        rng = np.random.default_rng([self.seed, 4])
        wave = int(self.t["wave"])
        n_max = int(math.ceil(self.seconds * float(self.t["max_rate"])
                              / wave)) + 1
        taken = set(self.warmed)
        self.pool = [draw_configs(rng, self.s.sizes, wave, taken)
                     for _ in range(n_max)]

    def window(self):
        eng = self.s.engine
        eng.reset_stats()
        self.rows: List[np.ndarray] = []
        t0 = self.t0 = time.perf_counter()
        with TraceAnnotation(WINDOW):
            while time.perf_counter() - t0 < self.seconds:
                k = len(self.rows) % len(self.pool)
                if k == 0 and self.rows:
                    eng.clear_cache()
                with TraceAnnotation("bench.wave"):
                    self.rows.append(eng(self.pool[k]))
        self.elapsed = time.perf_counter() - t0
        self.configs = np.concatenate([self.pool[k % len(self.pool)]
                                       for k in range(len(self.rows))])
        st = eng.stats
        self.counters = {"window_s": self.elapsed, "configs": len(self.configs),
                         "featurize_s": st.featurize_s,
                         "collect_s": st.collect_s,
                         "chunks": st.chunks, "padded": st.padded}

    def e2e(self):
        return {"configs_per_s": len(self.configs) / self.elapsed}

    @property
    def attempted(self):
        return len(self.configs)

    def check(self, control: bool = False):
        """The widest gap between a seeded sample of the window's rows and
        the reference, run on the host's CPU; and whether the program's
        pruned space is the reference's."""
        rows = np.concatenate(self.rows)
        failed = int((~np.isfinite(rows).all(1)).sum())
        rng = np.random.default_rng([self.seed, 3])
        idx = np.sort(rng.choice(len(rows), int(self.t["check_rows"]),
                                 replace=False))
        cfgs = self.checked = [tuple(c) for c in self.configs[idx].tolist()]
        with ref.host():
            gaps = (self.s.control_gaps(cfgs) if control else
                    self.s.gaps(cfgs, rows[idx]))
            space_ok = self.s.space_ok
        return {"row_gap": float(gaps.max()),
                "space_mismatch": float(not space_ok)}, failed
