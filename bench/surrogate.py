"""What the surrogate cells share: the program's engine at one configuration,
built from the benchmark's own weights and feature scales, and the check of
its rows against the plain reference.

The weights come from the configuration's ``weights_seed``, not from the
run's seed: the program's engine bakes its parameters into every program
it compiles, so weights that changed from run to run would miss the
persistent compilation cache and recompile every shape in every run. The
feature scales are the configuration's too (``feature_scales``). The
configurations a run evaluates come from the run's seed.
"""
from __future__ import annotations

import functools
import math
import time
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import reference as ref

def draw_configs(rng: np.random.Generator, sizes: Sequence[int], n: int,
                 taken: set) -> np.ndarray:
    """(n, units) configurations uniform over the space, none repeated and
    none whose flat index is in `taken` (which grows)."""
    hi = np.asarray(sizes, np.int64)
    out: List[np.ndarray] = []
    got = 0
    while got < n:
        block = rng.integers(0, hi, size=(2 * (n - got) + 16, len(hi)))
        flat = np.ravel_multi_index(tuple(block.T), hi)
        first = np.sort(np.unique(flat, return_index=True)[1])
        fresh = [i for i in first.tolist() if flat[i] not in taken][:n - got]
        taken.update(flat[fresh].tolist())
        out.append(block[fresh])
        got += len(fresh)
    return np.concatenate(out)


def make_weights(cfg: Dict):
    """(stage 1, stage 2) parameters in float32, made on the device in one
    jitted call from the configuration's weights seed: uniform in
    +-1/sqrt(fan_in), biases included."""
    H, L, F = int(cfg["hidden"]), int(cfg["n_layers"]), ref.N_FEAT

    def dense(k, fan_in, shape):
        s = 1.0 / math.sqrt(fan_in)
        return jax.random.uniform(k, shape, jnp.float32, -s, s)

    def stage(key, node_level, out_dim):
        ks = jax.random.split(key, 3 * L + 4)
        layers, d = [], F
        for i in range(L):
            layers.append({"w_self": dense(ks[3 * i], d, (d, H)),
                           "w_nbr": dense(ks[3 * i + 1], d, (d, H)),
                           "b": dense(ks[3 * i + 2], d, (H,))})
            d = H
        ro_in = H if node_level else 2 * H
        return {"layers": layers,
                "ro_w1": dense(ks[-4], ro_in, (ro_in, H)),
                "ro_b1": dense(ks[-3], ro_in, (H,)),
                "ro_w2": dense(ks[-2], H, (H, out_dim)),
                "ro_b2": dense(ks[-1], H, (out_dim,))}

    @jax.jit
    def init(key):
        k1, k2 = jax.random.split(key)
        return stage(k1, True, 1), stage(k2, False, 4)

    return init(jax.random.PRNGKey(int(cfg["weights_seed"])))


class Surrogate:
    """The program's `SurrogateEngine` for one configuration, and what the
    check needs to judge its rows."""

    def __init__(self, cfg: Dict, program, devices: int = 1):
        P, D, E, M, G, GR = (program.pipeline, program.dataset,
                             program.engine, program.models, program.gnn,
                             program.graph)
        t = [time.perf_counter()]
        self.cfg = cfg
        self.params = make_weights(cfg)
        t.append(time.perf_counter())
        ctx = P.app_context(cfg["app"], float(cfg["theta"]))
        t.append(time.perf_counter())
        self.app, self.entries = ctx.app, ctx.entries
        self.sizes = [len(ctx.entries[n.kind]) for n in ctx.app.unit_nodes]
        scales = cfg["feature_scales"]
        self.x_mean = np.asarray(scales["x_mean"], np.float32)
        self.x_std = np.asarray(scales["x_std"], np.float32)
        # target scales: the identity. The weights are untrained, so the
        # outputs are in the model's own units; scaling them up to a
        # label's mean in float32 would round away the last bits that the
        # check compares
        self.y_mean = np.zeros(4, np.float32)
        self.y_std = np.ones(4, np.float32)
        graph = GR.build_graph(ctx.app)
        n_pad, F = int(cfg["n_pad"]), ref.N_FEAT
        z = np.zeros((1, n_pad), np.float32)
        ds = D.AccelDataset(
            cfg["app"], graph, np.zeros((1, n_pad, n_pad), np.float32),
            np.zeros((1, n_pad, F), np.float32), z, z,
            np.zeros((1, 4), np.float32), np.zeros((1, 4), np.float32), z,
            [], self.y_mean, self.y_std, self.x_mean, self.x_std,
            schema_version=int(cfg["feature_schema"]))
        two_cfg = M.TwoStageConfig(
            gnn=G.GNNConfig(arch=cfg["gnn_arch"], n_layers=int(cfg["n_layers"]),
                            hidden=int(cfg["hidden"]), feature_dim=F),
            use_critical_path=bool(cfg["use_critical_path"]),
            schema_version=int(cfg["feature_schema"]))
        params = M.TwoStageParams(*self.params)
        self.ds = ds
        self.engine = E.SurrogateEngine.from_gnn(
            two_cfg, params, ds, ctx.app, ctx.entries,
            chunk_size=int(cfg["eval_chunk"]),
            use_kernel=cfg["use_kernel"], devices=devices)
        t.append(time.perf_counter())
        self.phases = dict(zip(("weights", "program_library", "engine"),
                               np.diff(t).tolist()))

    @functools.cached_property
    def acc(self) -> "ref.Accelerator":
        """The reference's view of the configuration, built for the check
        (after the window)."""
        return ref.Accelerator(self.cfg)

    @functools.cached_property
    def host_params(self):
        """The weights, copied to the host's CPU for the reference."""
        return jax.device_put(self.params, jax.devices("cpu")[0])

    @property
    def space_ok(self) -> bool:
        """The program's design space is the reference's, entry for entry."""
        return all([e.inst.name for e in self.entries[k]]
                   == [e.unit.name for e in sp]
                   for (_, k), sp in zip(self.acc.units, self.acc.space))

    def normalized(self, rows: np.ndarray) -> np.ndarray:
        """Engine rows ([area, power, latency, 1-ssim]) back to the model's
        normalized targets."""
        y = np.array(rows, np.float64, copy=True)
        y[:, 3] = 1.0 - y[:, 3]
        return (y - self.y_mean) / self.y_std

    def gaps(self, configs: Sequence[Tuple[int, ...]], rows: np.ndarray,
             prec: str = "highest", block: int = 64) -> np.ndarray:
        """Per configuration, the widest gap between `rows` and the
        reference forward at ``prec`` (normalized targets)."""
        out = []
        for lo in range(0, len(configs), block):
            part = list(configs[lo:lo + block])
            X = ref.normalize(self.acc.raw_features(part),
                              self.acc.mask, self.x_mean, self.x_std)
            out.append(ref.surrogate_gaps(self.host_params, self.acc.adj,
                                          self.acc.mask, X,
                                          self.normalized(rows[lo:lo + block]),
                                          prec))
        return np.concatenate(out) if out else np.zeros(0)

    def control_gaps(self, configs, prec: str = "high_native"
                     ) -> np.ndarray:
        """The control: the reference itself at ``prec`` in the program's
        place, judged by the same comparison. ``high_native`` is the
        chip's own three-pass `Precision.HIGH`, run on the chip with the
        chip's copy of the weights (on a CPU it is float32, no control);
        ``high`` is the three-pass product written out, on the host."""
        native = prec == "high_native"
        dev = jax.devices()[0] if native else jax.devices("cpu")[0]
        params = self.params if native else self.host_params
        out = []
        for lo in range(0, len(configs), 64):
            part = list(configs[lo:lo + 64])
            X = ref.normalize(self.acc.raw_features(part),
                              self.acc.mask, self.x_mean, self.x_std)
            with jax.default_device(dev):
                A, Mk = jnp.asarray(self.acc.adj), jnp.asarray(self.acc.mask)
                Xd = jnp.asarray(X)
                lg = ref.crit_logits(params, A, Xd, Mk, prec=prec)
                bits = (np.asarray(lg) > 0).astype(np.float32)
                low = np.asarray(ref.targets(params, A, Xd, Mk,
                                             jnp.asarray(bits), prec=prec))
            out.append(ref.surrogate_gaps(self.host_params, self.acc.adj,
                                          self.acc.mask, X, low, "highest"))
        return np.concatenate(out) if out else np.zeros(0)
