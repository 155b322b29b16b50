"""Training subsystem for the two-stage GNN models.

Paper setup (Sec IV-A): Adam, lr 1e-3, batch 5, 100 epochs, dropout/lr
tuned on the test split. Defaults here are CPU-scaled (bigger batch, fewer
epochs); pass `TrainConfig.paper_faithful()` to reproduce the original
schedule.

Three layers, all sharing one step function (`_make_step`):

``fit_two_stage``
    The production path: ONE jitted `lax.scan` over (epochs x steps) with
    a donated (params, opt) carry — zero per-epoch Python dispatch.
    Dropout is live (per-step PRNG keys threaded through `models.losses`
    -> `gnn.apply`); the ragged final batch is pad-and-masked (sample
    weight 0) instead of silently dropped; optional early stopping tracks
    a best-params snapshot against a held-out split inside the scan.

``fit_two_stage_loop``
    The per-epoch Python loop kept as the reference implementation: same
    preamble, same batch plan, same key derivation, so scanned-vs-loop
    parity is exact (asserted in tests/test_training.py) — including at
    dropout > 0, because per-step dropout keys are derived by
    `fold_in(key, global step)` in both.

``fit_ensemble``
    `jax.vmap` of the whole scanned training run over a member axis
    (stacked init params + per-member batch/dropout key streams), so an
    8-member ensemble trains as one XLA program, each member
    bit-compatible with its own `fit_two_stage` run
    (tests/test_training.py). Members may span different GNN
    architectures: members are grouped per arch (param pytrees differ
    between archs) and each group trains under one vmap.
    Ensemble mean/std feed `engine.SurrogateEngine.from_gnn_ensemble` as
    the DSE uncertainty column.

Data-parallel sharding: ``TrainConfig(data_parallel=True)`` places the
sample-axis of the dataset tensors on a 1-D device mesh
(`repro.distributed.meshes.data_parallel_mesh`); the minibatch gather and
loss all-reduce are then partitioned by XLA. A no-op on one device.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, replace
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gnn, models
from repro.core.dataset import AccelDataset


@dataclass(frozen=True)
class TrainConfig:
    lr: float = 1e-3
    batch_size: int = 64
    epochs: int = 40
    seed: int = 0
    patience: int = 0            # >0 enables early stopping on a val split
    val_frac: float = 0.1        # held-out fraction when patience > 0
    min_delta: float = 0.0       # required val-loss improvement
    data_parallel: bool = False  # shard the sample axis over devices

    @staticmethod
    def paper_faithful() -> "TrainConfig":
        return TrainConfig(lr=1e-3, batch_size=5, epochs=100)


@dataclass
class FitHistory:
    """Per-epoch training trace returned by `fit_two_stage(..., return_history=True)`."""
    train_loss: np.ndarray          # (epochs, steps) per-step total loss
    val_loss: Optional[np.ndarray]  # (epochs,) or None when no val split
    epochs_run: int                 # < epochs when early stopping fired


@dataclass
class EnsembleParams:
    """Stacked per-member parameters, grouped by architecture.

    groups[i] = (two_stage_cfg, stacked_params) where every leaf of
    stacked_params carries a leading member axis. `member_arch` lists the
    arch of each global member index (group order, then member order).
    """
    groups: List[Tuple[models.TwoStageConfig, models.TwoStageParams]]
    member_arch: List[str]

    @property
    def n_members(self) -> int:
        return len(self.member_arch)


# --------------------------------------------------------------------------
# optimizer
# --------------------------------------------------------------------------

def _adam_init(params):
    z = jax.tree.map(jnp.zeros_like, params)
    return {"m": z, "v": jax.tree.map(jnp.zeros_like, params),
            "t": jnp.zeros((), jnp.int32)}


def _adam_update(params, grads, state, lr, b1=0.9, b2=0.999, eps=1e-8):
    t = state["t"] + 1
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, state["m"], grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g,
                     state["v"], grads)
    mh = jax.tree.map(lambda m_: m_ / (1 - b1 ** t), m)
    vh = jax.tree.map(lambda v_: v_ / (1 - b2 ** t), v)
    params = jax.tree.map(lambda p, m_, v_: p - lr * m_ /
                          (jnp.sqrt(v_) + eps), params, mh, vh)
    return params, {"m": m, "v": v, "t": t}


# --------------------------------------------------------------------------
# data plumbing
# --------------------------------------------------------------------------

_DATA_KEYS = ("adj", "x", "mask", "unit_mask", "y", "crit")


def _as_data(ds: AccelDataset) -> Dict[str, jnp.ndarray]:
    return {k: jnp.asarray(getattr(ds, k)) for k in _DATA_KEYS}


def _shard_data(data: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
    """Place the sample axis on a 1-D data mesh (no-op on one device)."""
    from repro.distributed import meshes as M
    mesh = M.data_parallel_mesh()
    if mesh is None:
        return data
    from jax.sharding import NamedSharding, PartitionSpec as P

    def one(a):
        if a.shape[0] % mesh.shape["data"] != 0:
            return a
        spec = P(*(("data",) + (None,) * (a.ndim - 1)))
        return jax.device_put(a, NamedSharding(mesh, spec))
    return {k: one(v) for k, v in data.items()}


def _shard_members(tree, n_members: int):
    """Shard the leading (member) axis of a pytree over host devices.

    Member programs are fully independent (no cross-member ops), so SPMD
    partitioning the leading axis runs members in parallel across devices
    with ZERO communication — per-member results stay bit-identical to
    the unsharded run. Delegates to `meshes.shard_leading_axis` (shared
    with the island DSE fleet); a no-op on one device."""
    from repro.distributed import meshes as M
    return M.shard_leading_axis(tree, n_members, axis_name="member")


def _plan_for(tc: TrainConfig, n: int, bs: int):
    """(idx, w, dropout_key) for one training run, derived from tc.seed
    the same way in the scan and the loop (and per member in
    `fit_ensemble`)."""
    pkey, dkey = jax.random.split(jax.random.PRNGKey(tc.seed + 1))
    idx, w = _batch_plan(pkey, n, bs, tc.epochs)
    return idx, w, dkey


@functools.lru_cache(maxsize=64)
def _perm_fn(n: int):
    """Cached jitted (E,2)-keys -> (E,n) permutations program. Without the
    cache every fit (and every ensemble member) recompiled the sort."""
    return jax.jit(jax.vmap(lambda k: jax.random.permutation(k, n)))


def _batch_plan(key: jax.Array, n: int, bs: int, epochs: int):
    """(epochs, steps, bs) index + weight arrays; pad-and-mask tail.

    Every sample appears exactly once per epoch: the ragged final batch is
    padded with index 0 rows carrying weight 0, so `models.losses` masks
    them out of both loss terms (the old path truncated `perm[:steps*bs]`
    and silently never trained on n % bs samples each epoch)."""
    steps = -(-n // bs)
    pad = steps * bs - n
    perms = _perm_fn(n)(jax.random.split(key, epochs))    # (E, n)
    idx = jnp.concatenate(
        [perms, jnp.zeros((epochs, pad), perms.dtype)], axis=1)
    w = jnp.concatenate(
        [jnp.ones((epochs, n), jnp.float32),
         jnp.zeros((epochs, pad), jnp.float32)], axis=1)
    return idx.reshape(epochs, steps, bs), w.reshape(epochs, steps, bs)


def _split_const(data: Dict[str, jnp.ndarray]):
    """(varying, constant-row) split of the dataset tensors.

    Every config of one accelerator shares the graph topology, so adj /
    mask / unit_mask are usually identical across the sample axis; the
    per-step minibatch gather of a (bs, N, N) adjacency block is then
    pure memory traffic. Detect constancy once and keep a single row that
    the step broadcasts lazily (the same trick the inference engine's
    `ConfigFeaturizer` uses for its cached constant columns)."""
    var, const = {}, {}
    for k, v in data.items():
        if k in ("adj", "mask", "unit_mask") and v.shape[0] > 1 and \
                bool(jnp.all(v == v[:1])):
            const[k] = v[0]
        else:
            var[k] = v
    return var, const


def _make_step(cfg: models.TwoStageConfig, tc: TrainConfig, data,
               use_dropout: bool):
    """(params, opt, idx, w, gstep, drop_key) -> (params, opt, loss)."""
    var, const = _split_const(data)

    def step(params, opt, idx, w, gstep, drop_key):
        batch = {k: v[idx] for k, v in var.items()}
        bs = idx.shape[0]
        for k, row in const.items():
            batch[k] = jnp.broadcast_to(row, (bs,) + row.shape)
        batch["w"] = w
        rng = jax.random.fold_in(drop_key, gstep) if use_dropout else None
        (loss, _parts), grads = jax.value_and_grad(
            lambda p: models.losses(cfg, p, batch, rng=rng),
            has_aux=True)(params)
        params, opt = _adam_update(params, grads, opt, tc.lr)
        return params, opt, loss
    return step


# --------------------------------------------------------------------------
# single-model training
# --------------------------------------------------------------------------

def _build_scan_fit(cfg: models.TwoStageConfig, tc: TrainConfig, data,
                    n: int, val_data=None):
    """Returns f(params0, idx, w, dkey) -> (params, (train (E,S), val
    (E,), active (E,))) — pure, vmappable, one lax.scan over epochs with
    an inner scan over steps. The (idx, w) batch plan and the dropout key
    are produced OUTSIDE (see `_plan_for`), which keeps the permutation
    sort out of the big compiled program."""
    bs = min(tc.batch_size, n)
    steps = -(-n // bs)
    use_do = cfg.gnn.dropout > 0
    step = _make_step(cfg, tc, data, use_do)
    early = tc.patience > 0 and val_data is not None

    def val_loss_of(params):
        return models.losses(cfg, params, val_data)[0]

    def fit(params0, idx, w, dkey):
        gsteps = jnp.arange(tc.epochs * steps,
                            dtype=jnp.int32).reshape(tc.epochs, steps)

        def body(carry, one):
            p, o = carry
            i, wt, g = one
            p, o, loss = step(p, o, i, wt, g, dkey)
            return (p, o), loss

        if not early:
            # no per-epoch bookkeeping needed: ONE flat scan over
            # (epochs * steps) — about half the compile time of the
            # nested epoch/step scan below
            flat = (idx.reshape(-1, bs), w.reshape(-1, bs),
                    gsteps.reshape(-1))
            (params, opt), losses = jax.lax.scan(
                body, (params0, _adam_init(params0)), flat)
            tr_loss = losses.reshape(tc.epochs, steps)
            vls = jnp.full((tc.epochs,), jnp.nan, jnp.float32)
            act = jnp.ones((tc.epochs,), bool)
            return params, (tr_loss, vls, act)

        def run_epoch(params, opt, inp):
            (params, opt), losses = jax.lax.scan(body, (params, opt), inp)
            return params, opt, losses

        def epoch_body(carry, inp):
            params, opt, best, best_val, bad, stopped = carry
            p2, o2, losses = run_epoch(params, opt, inp)
            if early:
                # once stopped, freeze the carry (scan has a static trip
                # count; the selected-out epochs are dead weight but the
                # best snapshot and the reported epochs_run are exact)
                keep = lambda a, b_: jnp.where(stopped, a, b_)
                params = jax.tree.map(keep, params, p2)
                opt = jax.tree.map(keep, opt, o2)
                vl = val_loss_of(params)
                improved = jnp.logical_and(~stopped,
                                           vl < best_val - tc.min_delta)
                best = jax.tree.map(
                    lambda b_, p_: jnp.where(improved, p_, b_), best, params)
                best_val = jnp.where(improved, vl, best_val)
                bad = jnp.where(improved, 0, bad + 1)
                active = ~stopped
                stopped = jnp.logical_or(stopped, bad >= tc.patience)
                losses = jnp.where(active, losses, jnp.nan)
            else:
                params, opt = p2, o2
                vl = jnp.float32(jnp.nan)
                active = jnp.bool_(True)
            return (params, opt, best, best_val, bad, stopped), \
                (losses, vl, active)

        opt0 = _adam_init(params0)
        carry0 = (params0, opt0, params0, jnp.float32(jnp.inf),
                  jnp.int32(0), jnp.bool_(False))
        carry, (tr_loss, vls, act) = jax.lax.scan(
            epoch_body, carry0, (idx, w, gsteps))
        params, _opt, best, best_val, _bad, _stopped = carry
        out = best if early else params
        return out, (tr_loss, vls, act)

    return fit


def _fit_data(ds_train: AccelDataset, tc: TrainConfig,
              ds_val: Optional[AccelDataset]):
    """(data, n, val_data) for every fit, with the validation split carved
    off the tail of `ds_train` when early stopping asks for one."""
    n_total = ds_train.y.shape[0]
    val_data = None
    if tc.patience > 0:
        if ds_val is None:
            n_tr = max(int(n_total * (1.0 - tc.val_frac)), 1)
            ds_train, ds_val = ds_train.split((n_tr + 0.5) / n_total)
        val_data = _as_data(ds_val)
    data = _as_data(ds_train)
    if tc.data_parallel:
        data = _shard_data(data)
    return data, ds_train.y.shape[0], val_data


def _params0(cfg: models.TwoStageConfig, tc: TrainConfig,
             params0: Optional[models.TwoStageParams]):
    if params0 is None:
        return models.init(jax.random.PRNGKey(tc.seed), cfg)
    return jax.tree.map(jnp.asarray, params0)


def _fit_result(params, tr_loss, vls, act, val_data, return_history):
    if return_history:
        return params, FitHistory(
            train_loss=np.asarray(tr_loss),
            val_loss=np.asarray(vls) if val_data is not None else None,
            epochs_run=int(np.asarray(act).sum()))
    return params


def fit_two_stage(cfg: models.TwoStageConfig, ds_train: AccelDataset,
                  tc: TrainConfig = TrainConfig(),
                  log_every: int = 0, return_history: bool = False,
                  ds_val: Optional[AccelDataset] = None,
                  params0: Optional[models.TwoStageParams] = None):
    """Train the two-stage model; returns params (and FitHistory if asked).

    Runs one jitted lax.scan over (epochs x steps) with a donated carry
    (`fit_two_stage_loop` is its per-epoch reference). With
    `tc.patience > 0`, a validation split (`ds_val`, or `tc.val_frac`
    carved off the tail of `ds_train`) drives early stopping and the
    best-val params snapshot is returned.

    ``params0`` warm-starts from existing parameters (numpy leaves are
    re-deviced) — the fine-tune leg of `evaluate_transfer` and cached
    cross-app params from the artifact store both enter here."""
    data, n, val_data = _fit_data(ds_train, tc, ds_val)
    params0 = _params0(cfg, tc, params0)
    idx, w, dkey = _plan_for(tc, n, min(tc.batch_size, n))
    fit = jax.jit(_build_scan_fit(cfg, tc, data, n, val_data),
                  donate_argnums=(0,))
    params, (tr_loss, vls, act) = fit(params0, idx, w, dkey)

    tr_loss = np.asarray(tr_loss)
    act = np.asarray(act)
    if log_every:
        for ep in range(tc.epochs):
            if act[ep] and (ep + 1) % log_every == 0:
                print(f"  epoch {ep + 1}/{tc.epochs} "
                      f"loss={float(np.nanmean(tr_loss[ep])):.4f}")
    return _fit_result(params, tr_loss, vls, act, val_data, return_history)


def fit_two_stage_loop(cfg: models.TwoStageConfig, ds_train: AccelDataset,
                       tc: TrainConfig = TrainConfig(),
                       log_every: int = 0, return_history: bool = False,
                       ds_val: Optional[AccelDataset] = None,
                       params0: Optional[models.TwoStageParams] = None):
    """Reference for `fit_two_stage`: the same arguments and result,
    trained by a per-epoch Python loop over one jitted epoch, with the
    same batch plan and key streams, so the two are parity-testable."""
    data, n, val_data = _fit_data(ds_train, tc, ds_val)
    params0 = _params0(cfg, tc, params0)
    bs = min(tc.batch_size, n)
    steps = -(-n // bs)
    use_do = cfg.gnn.dropout > 0
    step = _make_step(cfg, tc, data, use_do)
    idx, w, dkey = _plan_for(tc, n, bs)

    @jax.jit
    def epoch(params, opt, idx_e, w_e, g_e):
        def body(carry, one):
            p, o = carry
            i, wt, g = one
            p, o, loss = step(p, o, i, wt, g, dkey)
            return (p, o), loss
        (params, opt), losses = jax.lax.scan(body, (params, opt),
                                             (idx_e, w_e, g_e))
        return params, opt, losses

    @jax.jit
    def val_loss_of(params):
        return models.losses(cfg, params, val_data)[0]

    params, opt = params0, _adam_init(params0)
    best, best_val, bad = params, float("inf"), 0
    tr_hist, val_hist = [], []
    for ep in range(tc.epochs):
        g_e = jnp.arange(ep * steps, (ep + 1) * steps, dtype=jnp.int32)
        params, opt, losses = epoch(params, opt, idx[ep], w[ep], g_e)
        tr_hist.append(np.asarray(losses))
        if val_data is not None and tc.patience > 0:
            vl = float(val_loss_of(params))
            val_hist.append(vl)
            if vl < best_val - tc.min_delta:
                best, best_val, bad = params, vl, 0
            else:
                bad += 1
            if bad >= tc.patience:
                break
        else:
            val_hist.append(float("nan"))
        if log_every and (ep + 1) % log_every == 0:
            print(f"  epoch {ep + 1}/{tc.epochs} "
                  f"loss={float(losses.mean()):.4f}")
    pad_eps = tc.epochs - len(tr_hist)
    tr = np.concatenate([np.stack(tr_hist),
                         np.full((pad_eps, steps), np.nan)]) \
        if pad_eps else np.stack(tr_hist)
    vl_arr = np.concatenate([np.asarray(val_hist, np.float32),
                             np.full((pad_eps,), np.nan, np.float32)])
    act = np.concatenate([np.ones(len(tr_hist), bool),
                          np.zeros(pad_eps, bool)])
    out = best if (val_data is not None and tc.patience > 0) else params
    return _fit_result(out, tr, vl_arr, act, val_data, return_history)


# --------------------------------------------------------------------------
# ensemble training (vmapped whole runs)
# --------------------------------------------------------------------------

def fit_ensemble(cfg: models.TwoStageConfig, ds_train: AccelDataset,
                 tc: TrainConfig = TrainConfig(), n_members: int = 8,
                 archs: Optional[Sequence[str]] = None,
                 ds_val: Optional[AccelDataset] = None
                 ) -> Tuple[EnsembleParams, Dict[str, np.ndarray]]:
    """Train `n_members` independent models as vmapped scanned runs.

    Member m uses seed `tc.seed + m` for BOTH init and its batch/dropout
    key stream, so member m is bit-compatible with a single
    `fit_two_stage(..., TrainConfig(seed=tc.seed + m))` run (asserted in
    tests/test_training.py). `archs` optionally assigns each member a GNN
    architecture from {gcn, gsae, gat, mpnn}; members are grouped per arch
    (param pytrees differ across archs) and each group trains under one
    `jax.vmap` over the member axis.

    Returns (EnsembleParams, history dict with per-member (M, E, S) train
    losses and (M,) epochs_run)."""
    if n_members < 1:
        raise ValueError("n_members must be >= 1")
    member_arch = list(archs) if archs else [cfg.gnn.arch] * n_members
    if len(member_arch) != n_members:
        raise ValueError("len(archs) must equal n_members")

    data, n, val_data = _fit_data(ds_train, tc, ds_val)

    groups: List[Tuple[models.TwoStageConfig, models.TwoStageParams]] = []
    hist_tr, hist_eps = [], []
    order: List[str] = []
    bs = min(tc.batch_size, n)
    for arch in dict.fromkeys(member_arch):          # stable unique order
        members = [m for m, a in enumerate(member_arch) if a == arch]
        g_cfg = replace(cfg, gnn=replace(cfg.gnn, arch=arch))
        init_keys = jnp.stack(
            [jax.random.PRNGKey(tc.seed + m) for m in members])
        params0 = jax.vmap(lambda k: models.init(k, g_cfg))(init_keys)
        # per-member batch plans + dropout keys, derived exactly as a
        # single fit with seed tc.seed + m would (member == single parity)
        plans = [_plan_for(replace(tc, seed=tc.seed + m), n, bs)
                 for m in members]
        idx = jnp.stack([p[0] for p in plans])
        w = jnp.stack([p[1] for p in plans])
        dkeys = jnp.stack([p[2] for p in plans])
        if not tc.data_parallel:
            # member sharding and batch-axis data sharding commit arrays
            # to different meshes (members use a devs[:k] prefix, data
            # the full device set) and jit rejects the mix — when the
            # caller asked for data_parallel, that mesh wins
            params0, idx, w, dkeys = _shard_members(
                (params0, idx, w, dkeys), len(members))
        fit = _build_scan_fit(g_cfg, tc, data, n, val_data)
        fitted = jax.jit(jax.vmap(fit), donate_argnums=(0,))
        params, (tr_loss, _vls, act) = fitted(params0, idx, w, dkeys)
        groups.append((g_cfg, params))
        hist_tr.append(np.asarray(tr_loss))
        hist_eps.append(np.asarray(act).sum(-1))
        order.extend([arch] * len(members))

    history = {"train_loss": np.concatenate(hist_tr, 0),
               "epochs_run": np.concatenate(hist_eps, 0)}
    return EnsembleParams(groups=groups, member_arch=order), history


def ensemble_predict(ens: EnsembleParams, adj, x, mask
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """All-member predictions: (mean (B,4), std (B,4), stacked (M,B,4)).

    Deterministic — no rng reaches `models.predict`, so dropout is off at
    inference exactly as in `evaluate`."""
    adj, x, mask = jnp.asarray(adj), jnp.asarray(x), jnp.asarray(mask)
    ys = []
    for g_cfg, params in ens.groups:
        y = jax.vmap(
            lambda p: models.predict(g_cfg, p, adj, x, mask)[0])(params)
        ys.append(y)
    Y = jnp.concatenate(ys, axis=0)
    return Y.mean(0), Y.std(0), Y


def evaluate_ensemble(ens: EnsembleParams, ds: AccelDataset,
                      ds_test: AccelDataset) -> Dict[str, Dict]:
    """`evaluate` on the ensemble-mean prediction + per-target mean std
    (denormalized), the uncertainty column the DSE acquisition path sees."""
    adj, x, mask = (jnp.asarray(ds_test.adj), jnp.asarray(ds_test.x),
                    jnp.asarray(ds_test.mask))
    mean, std, _ = ensemble_predict(ens, adj, x, mask)
    y_pred = ds.denorm_y(np.asarray(mean))
    std_dn = np.asarray(std) * np.asarray(ds.y_std)
    y_true = ds_test.y_raw
    out: Dict[str, Dict] = {}
    for i, t in enumerate(models.TARGETS):
        out[t] = {"r2": r2_score(y_true[:, i], y_pred[:, i]),
                  "mape": mape(y_true[:, i], y_pred[:, i]),
                  "mean_std": float(std_dn[:, i].mean())}
    crit_probs = jnp.concatenate([
        jax.nn.sigmoid(jax.vmap(
            lambda p, g_cfg=g_cfg: models.predict_critical(
                g_cfg, p, adj, x, mask))(params))
        for g_cfg, params in ens.groups], axis=0)      # (M, B, N)
    pred_bits = (crit_probs.mean(0) > 0.5)
    um = ds_test.unit_mask > 0
    correct = np.asarray(pred_bits) == (ds_test.crit > 0.5)
    out["critical_path"] = {
        "accuracy": float(correct[um].mean()) if um.any() else 1.0}
    return out


# --------------------------------------------------------------------------
# evaluation / metrics
# --------------------------------------------------------------------------

def evaluate(cfg: models.TwoStageConfig, params: models.TwoStageParams,
             ds: AccelDataset, ds_test: AccelDataset) -> Dict[str, Dict]:
    """R2 + MAPE per target (denormalized), + critical-path accuracy.

    Never passes rng: evaluation/prediction is deterministic regardless of
    `cfg.gnn.dropout`."""
    y_pred, crit_logits = models.predict(
        cfg, params, jnp.asarray(ds_test.adj), jnp.asarray(ds_test.x),
        jnp.asarray(ds_test.mask))
    y_pred = ds.denorm_y(np.asarray(y_pred))
    y_true = ds_test.y_raw
    out = {}
    for i, t in enumerate(models.TARGETS):
        out[t] = {"r2": r2_score(y_true[:, i], y_pred[:, i]),
                  "mape": mape(y_true[:, i], y_pred[:, i])}
    pred_bits = (jax.nn.sigmoid(crit_logits) > 0.5)
    um = ds_test.unit_mask > 0
    correct = np.asarray(pred_bits) == (ds_test.crit > 0.5)
    out["critical_path"] = {
        "accuracy": float(correct[um].mean()) if um.any() else 1.0}
    return out


def evaluate_merged(cfg: models.TwoStageConfig,
                    params: models.TwoStageParams,
                    mds) -> Dict[str, Dict]:
    """`evaluate` for a `dataset.MergedDataset` (or a `.view(app)` of one):
    predictions denormalized per row with each row's own app stats."""
    y_pred, crit_logits = models.predict(
        cfg, params, jnp.asarray(mds.adj), jnp.asarray(mds.x),
        jnp.asarray(mds.mask))
    y_pred = mds.denorm_rows(np.asarray(y_pred))
    y_true = mds.y_raw
    out: Dict[str, Dict] = {}
    for i, t in enumerate(models.TARGETS):
        out[t] = {"r2": r2_score(y_true[:, i], y_pred[:, i]),
                  "mape": mape(y_true[:, i], y_pred[:, i])}
    pred_bits = (jax.nn.sigmoid(crit_logits) > 0.5)
    um = mds.unit_mask > 0
    correct = np.asarray(pred_bits) == (mds.crit > 0.5)
    out["critical_path"] = {
        "accuracy": float(correct[um].mean()) if um.any() else 1.0}
    return out


def fit_unified(datasets: Dict[str, AccelDataset],
                cfg: models.TwoStageConfig, tc: TrainConfig = TrainConfig(),
                split: float = 0.9, n_pad: Optional[int] = None,
                params0: Optional[models.TwoStageParams] = None):
    """Fit ONE shared two-stage GNN over the union of per-app datasets.

    Returns (params, merged, metrics) where ``metrics`` holds the overall
    test-split quality plus a per-app breakdown (``metrics["per_app"]``).
    ``cfg.gnn.feature_dim`` must be `graph.MERGED_FEATURE_DIM` (the merged
    feature layout is app-subset independent)."""
    from repro.core import dataset as ds_lib
    from repro.core.graph import MERGED_FEATURE_DIM

    if cfg.gnn.feature_dim != MERGED_FEATURE_DIM:
        raise ValueError(
            f"unified surrogate needs feature_dim={MERGED_FEATURE_DIM} "
            f"(got {cfg.gnn.feature_dim}); build the GNNConfig with "
            f"feature_dim=graph.MERGED_FEATURE_DIM")
    merged = ds_lib.merge(datasets, n_pad=n_pad)
    tr, te = merged.split(split)
    params = fit_two_stage(cfg, tr, tc, params0=params0)
    metrics = evaluate_merged(cfg, params, te)
    metrics["per_app"] = {
        a: evaluate_merged(cfg, params, te.view(a))
        for a in merged.app_names if (te.app_ids ==
                                      merged.app_names.index(a)).any()}
    return params, merged, metrics


def evaluate_transfer(datasets: Dict[str, AccelDataset], holdout: str,
                      cfg: models.TwoStageConfig,
                      tc: TrainConfig = TrainConfig(),
                      finetune_epochs: int = 5,
                      split: float = 0.9) -> Dict[str, object]:
    """Leave-one-app-out transfer quality of the unified surrogate.

    Trains the shared model on every app EXCEPT ``holdout``, then reports
    per-objective R2/MAPE on the holdout app's test split twice:

    * ``zero_shot``  — the shared params as-is. The holdout's app-identity
      column never fired during training (its input was all-zero), so
      those weights sit at init: this measures pure cross-app structure
      transfer, ApproxGNN-style.
    * ``fine_tuned`` — after ``finetune_epochs`` warm-started epochs on
      the holdout's train split (`fit_two_stage(params0=shared)`), i.e.
      new-scenario onboarding at a fraction of a from-scratch fit.

    Returns {holdout, shared_apps, shared_metrics, zero_shot, fine_tuned,
    finetune_epochs}."""
    from repro.core import dataset as ds_lib

    if holdout not in datasets:
        raise ValueError(f"holdout {holdout!r} not in {sorted(datasets)}")
    rest = {a: d for a, d in datasets.items() if a != holdout}
    if not rest:
        raise ValueError("evaluate_transfer needs >= 2 apps")
    n_pad = max(d.x.shape[1] for d in datasets.values())
    params, _merged, shared_metrics = fit_unified(rest, cfg, tc, split,
                                                  n_pad=n_pad)
    hold = ds_lib.merge({holdout: datasets[holdout]}, n_pad=n_pad)
    tr_h, te_h = hold.split(split)
    zero_shot = evaluate_merged(cfg, params, te_h)
    ft_tc = replace(tc, epochs=finetune_epochs, patience=0)
    ft_params = fit_two_stage(cfg, tr_h, ft_tc, params0=params)
    fine_tuned = evaluate_merged(cfg, ft_params, te_h)
    return {"holdout": holdout, "shared_apps": sorted(rest),
            "shared_metrics": shared_metrics, "zero_shot": zero_shot,
            "fine_tuned": fine_tuned, "finetune_epochs": finetune_epochs}


def r2_score(y, yh) -> float:
    ss_res = float(((y - yh) ** 2).sum())
    ss_tot = float(((y - y.mean()) ** 2).sum()) + 1e-12
    return 1.0 - ss_res / ss_tot


def mape(y, yh) -> float:
    denom = np.maximum(np.abs(y), 1e-6)
    return float(np.mean(np.abs(yh - y) / denom))
