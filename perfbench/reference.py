"""The plain reference: a factorization machine trained by FTRL (w) and
AdaGrad (V), in straightforward float32 ``jax.numpy``.

It follows the published DiFacto description (Li et al., WSDM 2016) and
its reference implementation's update rules, as the configuration files
state them. It imports nothing of the program and takes nothing that the
program has made: its table is drawn from the seed by the rule the
configuration states, its rows come from the generator. No fused rows, no
chunks, no kernels; it holds only the table rows that the compared steps
touch.

Model, per row with binary features ``x`` (the set ``F`` of its rows of
the table):
    pred = sum_F w + 0.5 * sum_k ((sum_F V_k)^2 - sum_F V_k^2), clipped
           to [-20, 20]
    loss = sum over rows of log(1 + exp(-y pred)),  y in {-1, +1}
A row's embedding takes part only when it is live and, with ``l1_shrk``,
its w is not zero; it becomes live once w != 0 and its count of
occurrences passes ``V_threshold``. Counts are pushed before the step.
At ``V_dim = 0`` the model is l1-regularised logistic regression (the
flat table: w, z, sqrt_g a row and no embedding): the same functions on a
V of no columns, and the numbers compared are of w, z and sqrt_g.

Departures from the paper, each the configuration's: features are hashed
into ``hash_capacity`` rows and share a row on collision; V is stored in
``V_dtype`` and so starts from values rounded to it (the reference then
computes in float32 and never rounds again).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

PRED_CLAMP = 20.0


@dataclasses.dataclass(frozen=True)
class Hyper:
    """The numbers of a configuration that the arithmetic uses."""
    V_dim: int
    lr: float = 0.01
    lr_beta: float = 1.0
    l1: float = 1.0
    l2: float = 0.0
    V_lr: float = 0.01
    V_lr_beta: float = 1.0
    V_l2: float = 0.01
    V_init_scale: float = 0.01
    V_threshold: float = 10.0
    l1_shrk: bool = True
    hash_capacity: int = 0
    V_dtype: str = "float32"

    @classmethod
    def of(cls, learner_kwargs: dict) -> "Hyper":
        names = {f.name: f.type for f in dataclasses.fields(cls)}
        kw = {}
        for k, v in learner_kwargs.items():
            if k not in names:
                continue
            if k in ("V_dim", "hash_capacity"):
                kw[k] = int(v)
            elif k == "l1_shrk":
                kw[k] = str(v).lower() in ("1", "true")
            elif k == "V_dtype":
                kw[k] = str(v)
            else:
                kw[k] = float(v)
        return cls(**kw)


class State(NamedTuple):
    w: jnp.ndarray       # f32[n]
    z: jnp.ndarray       # f32[n]    FTRL dual
    sg: jnp.ndarray      # f32[n]    FTRL sqrt of summed squared gradients
    cnt: jnp.ndarray     # f32[n]
    live: jnp.ndarray    # bool[n]
    V: jnp.ndarray       # f32[n, k]
    Vg: jnp.ndarray      # f32[n, k] AdaGrad sqrt of summed squared grads


def initial_V(seed: int, capacity: int, rows: np.ndarray, h: Hyper
              ) -> jnp.ndarray:
    """Rows ``rows`` of the table a seed gives: uniform on
    [-V_init_scale/2, V_init_scale/2) from ``jax.random.PRNGKey(seed)``
    over the whole [capacity, V_dim] table, rounded to the storage type.
    A table with no embedding has nothing to draw."""
    if h.V_dim == 0:
        return jnp.zeros((len(rows), 0), jnp.float32)
    return _draw_rows(jnp.int32(seed), jnp.asarray(rows, jnp.int32),
                      capacity, h.V_dim, h.V_init_scale, h.V_dtype)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _draw_rows(seed, rows, capacity, k, scale, dtype):
    u = jax.random.uniform(jax.random.PRNGKey(seed), (capacity, k),
                           dtype=jnp.float32)
    V = ((u - 0.5) * scale)[rows]
    return V.astype(jnp.dtype(dtype)).astype(jnp.float32)


def initial_state(V0: jnp.ndarray) -> State:
    n = V0.shape[0]
    z = jnp.zeros((n,), jnp.float32)
    return State(w=z, z=z, sg=z, cnt=z, live=jnp.zeros((n,), bool),
                 V=V0, Vg=jnp.zeros_like(V0))


def gradients(h: Hyper, s: State, idx: jnp.ndarray, y: jnp.ndarray,
              vals=None):
    """(loss, gw[n], gV[n, k]) of one batch. ``idx`` is int32[B, F] into
    the state's rows, ``y`` is 0/1, ``vals`` f32[B, F] or None where
    every feature is 1 (a cell with value 0 is padding)."""
    n, k = s.V.shape
    flat = idx.reshape(-1)
    x = jnp.ones(idx.shape, jnp.float32) if vals is None else vals
    vm = s.live & (s.w != 0) if h.l1_shrk else s.live
    Vm = s.V * vm[:, None]
    Vt = Vm[idx] * x[:, :, None]                    # [B, F, k]
    XV = Vt.sum(1)
    pred = (s.w[idx] * x).sum(1) \
        + 0.5 * (XV * XV - (Vt * Vt).sum(1)).sum(1)
    pred = jnp.clip(pred, -PRED_CLAMP, PRED_CLAMP)
    yy = jnp.where(y > 0, 1.0, -1.0)
    loss = jnp.sum(jnp.log1p(jnp.exp(-yy * pred)))
    p = -yy / (1.0 + jnp.exp(yy * pred))

    px = p[:, None] * x                             # [B, F]
    gw = jnp.zeros((n,), jnp.float32).at[flat].add(px.reshape(-1))
    t1 = jnp.zeros((n, k), jnp.float32).at[flat].add(
        (px[:, :, None] * XV[:, None, :]).reshape(flat.shape[0], k))
    xxp = gw if vals is None else jnp.zeros((n,), jnp.float32).at[
        flat].add((px * x).reshape(-1))
    gV = (t1 - xxp[:, None] * Vm) * vm[:, None]
    return loss, gw, gV


def step(h: Hyper, s: State, idx: jnp.ndarray, y: jnp.ndarray,
         push_counts: bool = True):
    """One training step -> (new state, loss). Counts are pushed before
    the step, as epoch 0 does."""
    n = s.V.shape[0]
    occ = jnp.zeros((n,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    present = occ > 0
    if push_counts:
        cnt = s.cnt + occ
        s = s._replace(cnt=cnt,
                       live=s.live | ((s.w != 0) & (cnt > h.V_threshold)))
    vm = s.live & (s.w != 0) if h.l1_shrk else s.live
    loss, gw, gV = gradients(h, s, idx, y)

    # FTRL-proximal on w
    g = gw + h.l2 * s.w
    sg_new = jnp.sqrt(s.sg * s.sg + g * g)
    z_new = s.z - (g - (sg_new - s.sg) / h.lr * s.w)
    eta = (h.lr_beta + sg_new) / h.lr
    w_new = jnp.where(jnp.abs(z_new) <= h.l1, 0.0,
                      (z_new - jnp.sign(z_new) * h.l1) / eta)
    live_new = s.live | ((w_new != 0) & (s.cnt > h.V_threshold))

    # AdaGrad on V, for rows whose embedding took part
    gv = gV + h.V_l2 * s.V
    Vg_new = jnp.sqrt(s.Vg * s.Vg + gv * gv)
    V_new = s.V - h.V_lr / (Vg_new + h.V_lr_beta) * gv
    upd = (present & vm)[:, None]

    return State(
        w=jnp.where(present, w_new, s.w),
        z=jnp.where(present, z_new, s.z),
        sg=jnp.where(present, sg_new, s.sg),
        cnt=s.cnt,
        live=jnp.where(present, live_new, s.live),
        V=jnp.where(upd, V_new, s.V),
        Vg=jnp.where(upd, Vg_new, s.Vg)), loss


@functools.lru_cache(maxsize=None)
def _jstep(h: Hyper, push_counts: bool = True):
    return jax.jit(lambda s, i, y: step(h, s, i, y, push_counts))


def norm(x) -> float:
    """The 2-norm, summed in float64 on the host."""
    x = np.asarray(x, np.float64)
    return float(np.sqrt(np.sum(x * x)))


ROWS = ("w", "z", "sg", "V", "Vg")      # the leaves compared row by row


def follow(h: Hyper, V0: jnp.ndarray, batches, lower=None) -> dict:
    """Follow the first steps from the seed's table. ``batches`` is a
    list of (idx int32[B, F] into the touched rows, y f32[B]). Returns the
    numbers that are compared: each step's loss, the norm of the first
    gradient of each leaf as the optimizer got it (w: FTRL's sqrt_g after
    step 1; V: AdaGrad's Vg after the first step that pulled an
    embedding, which is step 2, since every w is 0 before step 1), the
    norm of each leaf's change after the last step, and the touched rows
    after it (``rows``: w, z, sg, V, Vg). Where the table has no
    embedding there is no number of V.

    ``lower`` turns the reference into its own lower-precision control: a
    function applied to (V, Vg) wherever the table would store them."""
    store = lower or (lambda V, Vg: (V, Vg))
    jstep = _jstep(h)
    V0s, _ = store(V0, jnp.zeros_like(V0))
    s = initial_state(V0s)
    out = {"loss": [], "grad": {}, "change": {}}
    for t, (idx, y) in enumerate(batches, 1):
        s, loss = jstep(s, jnp.asarray(idx), jnp.asarray(y))
        V, Vg = store(s.V, s.Vg)
        s = s._replace(V=V, Vg=Vg)
        out["loss"].append(float(loss))
        if t == 1:
            out["grad"]["w"] = norm(s.sg)
        if t == 2 and h.V_dim:
            out["grad"]["V"] = norm(s.Vg)
    out["change"]["w"] = norm(s.w)
    if h.V_dim:
        out["change"]["V"] = norm(s.V - V0s)
    out["nnz_w"] = int(jnp.sum(s.w != 0))
    out["live"] = int(jnp.sum(s.live))
    out["rows"] = {k: getattr(s, k) for k in ROWS}
    return out


def follow_pair(h: Hyper, before: dict, batches, fault: str = "") -> dict:
    """Follow one call of the pair-replay program: two steps from the
    touched rows as the program held them just before the call
    (``before``: host arrays w, z, sg, cnt, live, V, Vg, the last two
    with no columns where the table has no embedding; the one thing
    the reference takes from the program, because the state after an
    epoch of bfloat16 steps cannot be had from the seed to better than
    the comparison's own limits). A replayed step pushes no counts.
    Returns each step's loss and the rows after the second.

    ``fault`` plants in the reference what a broken pair program would
    do, for the readings of ``calibrate.py``: "stale" lets the second
    step read the state from before the first, "half_batch" leaves out
    every second row of both batches."""
    s0 = State(*(jnp.asarray(before[k]) for k in
                 ("w", "z", "sg", "cnt", "live", "V", "Vg")))
    if fault == "half_batch":
        batches = [(i[::2], y[::2]) for i, y in batches]
    jstep = _jstep(h, False)
    (ia, ya), (ib, yb) = batches
    s1, la = jstep(s0, jnp.asarray(ia), jnp.asarray(ya))
    if fault == "stale":
        # the second step computed from the old rows; its rows overwrite
        # the first step's, the rest keep the first step's
        sb, lb = jstep(s0, jnp.asarray(ib), jnp.asarray(yb))
        inb = jnp.zeros(s0.w.shape, bool).at[jnp.asarray(ib).reshape(-1)
                                             ].set(True)
        s2 = State(*(jnp.where(inb if a.ndim == 1 else inb[:, None], b, a)
                     for a, b in zip(s1, sb)))
    else:
        s2, lb = jstep(s1, jnp.asarray(ib), jnp.asarray(yb))
    return {"loss": [float(la), float(lb)],
            "after": {k: np.asarray(getattr(s2, k)) for k in ROWS}}


def _rel(a, b, mask) -> float:
    """The norm of ``a - b`` over the norm of ``b``, both over the rows
    of ``mask``."""
    den = norm(np.asarray(b)[mask])
    return norm(np.asarray(a)[mask] - np.asarray(b)[mask]) / den \
        if den else float("inf")


def pair_numbers(prog: dict, ref: dict, gap) -> dict:
    """The pair call's numbers: the gap of each step's loss, the gaps of
    the norms of each leaf's change over the call, and row by row over
    the rows that the reference's two steps updated the norm of the
    difference over the reference's norm: of V, or of w where the table
    has no embedding. (The same of Vg was read
    and is not compared: neither planted fault reads three times what
    sound runs do, PERF.md section 2.)"""
    b, a, r = prog["before"], prog["after"], ref["after"]
    out = {f"pair_loss{t + 1}": gap(p, q) for t, (p, q)
           in enumerate(zip(prog["loss"], ref["loss"]))}
    flat = r["V"].shape[1] == 0
    for leaf in ("w",) if flat else ("w", "V"):
        out[f"pair_change_{leaf}"] = gap(norm(a[leaf] - b[leaf]),
                                         norm(r[leaf] - b[leaf]))
    moved = r["sg"] != b["sg"] if flat \
        else np.any(r["Vg"] != b["Vg"], axis=1)
    out[f"pair_round_{leaf}"] = _rel(a[leaf], r[leaf], moved)
    return out


def rel_diff(prog: dict, ref: dict) -> dict:
    """Row by row over the touched rows after the last step (``rows`` of
    the probe and of ``follow``): the norm of the difference over the
    reference's norm.

    Fused rows: of V over the rows that the reference never updated
    (``keep_V``: its Vg is still all zero) and over those it did
    (``round_V``), and of Vg (``round_Vg``).

    The flat table: of w, z and sqrt_g over the rows that the reference
    updated (``round_w``, ``round_z``, ``round_sg``: its sqrt_g is not
    zero), and ``zero_w``: the rows on which the two disagree whether w
    is exactly 0, as a share of those rows. l1's sparsity is the
    guarantee this model gives, and a gap of norms cannot see it."""
    if ref["V"].shape[1] == 0:
        upd = np.asarray(ref["sg"]) != 0
        out = {f"round_{k}": _rel(prog[k], ref[k], upd)
               for k in ("w", "z", "sg")}
        differ = (np.asarray(prog["w"]) == 0) != (np.asarray(ref["w"]) == 0)
        out["zero_w"] = float(differ.sum()) / max(float(upd.sum()), 1.0)
        return out
    ref_V, ref_Vg = ref["V"], ref["Vg"]
    V = jnp.asarray(prog["V"], jnp.float32)
    Vg = jnp.asarray(prog["Vg"], jnp.float32)
    updated = jnp.any(ref_Vg != 0, axis=1)[:, None]

    def rel(a, b, mask):
        num = jnp.sum(jnp.where(mask, (a - b) ** 2, 0.0))
        return float(jnp.sqrt(num / jnp.sum(jnp.where(mask, b * b, 0.0))))

    return {"keep_V": rel(V, ref_V, ~updated),
            "round_V": rel(V, ref_V, updated),
            "round_Vg": rel(Vg, ref_Vg, updated)}


def touched(slot_batches, pad_to: int = 1 << 17) -> tuple:
    """(rows int64[n], [idx int32[B, F]]): the table rows that a list of
    int64[B, F] row batches touches, ascending, and each batch as
    positions into them. ``rows`` is filled up to a multiple of ``pad_to``
    with row 0, which holds no feature and which no position names, so
    that every seed gives programs of one shape."""
    rows = np.unique(np.concatenate([b.reshape(-1) for b in slot_batches]))
    idx = [np.searchsorted(rows, b).astype(np.int32) for b in slot_batches]
    n = -(-len(rows) // pad_to) * pad_to
    return np.concatenate([rows, np.zeros(n - len(rows), rows.dtype)]), idx
