"""The fused SGD train/eval step — the single source of truth for the hot path.

One device program replaces the reference's 3-thread worker pipeline
(src/sgd/sgd_learner.h:85-102): gather [w, V] rows from the slot table
("Pull"), FM/logit forward, objective + AUC, backward, FTRL/AdaGrad scatter
update ("Push"). The learner (learners/sgd.py), the driver entry
(__graft_entry__.py) and the benchmark (perfbench/, through the learner)
all build their steps here so they can never drift apart.

Batches address the sorted-unique slot vector directly: in-batch collision
dedup happens on the HOST (store.map_keys_dedup / the producer-thread
np.unique), which rewrites the O(nnz) index array once per batch. The
device-side remap permutation that used to carry this for the cached
reader cost an unsorted u_cap-row permute + scatter-add per step — more
than the host gather it saved.

``train_auc`` picks the per-step training metric: "binned" (default) is the
O(B) histogram AUC — the sort-based exact AUC costs ~10 ms at 64k batches,
~12% of the step; "exact" restores the argsort; "none" skips it. Validation
always uses the exact metric (early stopping compares val-AUC deltas,
sgd_learner.cc:92-110).

**Bounded-delay contract** (``bounded_delay``/τ, learners/sgd.py): the
windowed schedule delays the HOST pipeline only — staging, the DCN
control exchange and the clock barrier all move off the device critical
path, while every gradient application still happens inside this fused
pull→step→push program against the state the previous step returned.
Delayed gradients therefore never bypass the kernel: there is no
host-side apply path, no second writer to the donated table, and τ>0
reuses these exact programs unchanged (the reference applies τ-stale
gradients server-side the same single-writer way, bounded by max_delay).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .losses import LossSpec
from .losses.metrics import auc_times_n_binned_jnp, auc_times_n_jnp
from .obs import names


def state_constrainer(state_shardings):
    """Pin a returned SGDState to its fs-sharded layout INSIDE the jitted
    program (``state_shardings`` is the NamedSharding pytree from
    parallel.sharding_tree(state, state_sharding(mesh))).

    This is how the mesh layout is threaded through the fused programs
    rather than left to GSPMD inference: the donated state argument
    arrives fs-sharded and the constrained output is guaranteed the SAME
    key-range layout, so XLA's buffer donation keeps the in-place table
    update across shards — the table never round-trips through a
    replicated or re-partitioned intermediate, whatever the surrounding
    batch shardings make the propagation pass prefer. ``None`` (no mesh)
    is the identity."""
    if state_shardings is None:
        return lambda state: state
    return lambda state: jax.lax.with_sharding_constraint(
        state, state_shardings)


def pull(fns, state, slots, own_cap: Optional[int] = None):
    """(params, rows-or-None) of the batch's unique ``slots``: a
    fused-row table keeps the gathered rows so the train step can hand
    them to the push (``own_cap``: see :func:`make_step_fns`)."""
    if fns.fused:
        rows = fns.pull_rows(state, slots, own_cap)
        return fns.rows_to_params(state, rows), rows
    return fns.get_rows(state, slots), None


def make_step_fns(fns, loss: LossSpec, train_auc: str = "binned",
                  state_shardings=None,
                  own_cap: Optional[int] = None) -> Tuple:
    """(forward, train_step, eval_step) over (state, batch, slots).

    ``fns`` is the updater namespace from updaters.sgd_updater.make_fns;
    all three returned callables are pure and jit-ready.
    ``state_shardings`` (mesh runs) pins the returned state to the
    table's fs key-range layout — see :func:`state_constrainer`.

    The one fork is the table's data format. A fused-row table
    (``fns.fused``: ``V_dim > 0``) takes ONE row gather whose result is
    THREADED from the pull to the push (apply_grad_rows), so the push
    never re-gathers; threading equals re-gathering bit for bit
    (tests/test_fused.py). A flat ``V_dim = 0`` table has no fused row
    and composes ``get_rows`` + ``apply_grad`` over its w/z/sqrt_g
    arrays.

    ``own_cap`` (None everywhere but the learner's mesh panel steps) is
    the counted bound on the slots one fs shard owns, a constant of the
    programs built here: with it the fused-row table legs run over each
    shard's owned run (ops/fused.gather_rows).
    """
    constrain = state_constrainer(state_shardings)
    fused = fns.fused

    def forward(state, batch, slots):
        params, _ = pull(fns, state, slots, own_cap)
        with names.scope(names.FORWARD):
            pred = loss.predict(params, batch)
            objv = loss.evaluate(pred, batch)
            auc = auc_times_n_jnp(batch.labels, pred, batch.row_mask)
        return params, pred, objv, auc

    def train_step(state, batch, slots):
        params, rows = pull(fns, state, slots, own_cap)
        # the forward hands its X·V to the backward so the fused step
        # gathers the [U, 1+k] token rows exactly once (round-4 profile:
        # the duplicate gather was ~15% of the step)
        with names.scope(names.FORWARD):
            pred, xv = loss.predict_xv(params, batch)
            objv = loss.evaluate(pred, batch)
            if train_auc == "binned":
                auc = auc_times_n_binned_jnp(batch.labels, pred,
                                             batch.row_mask)
            elif train_auc == "exact":
                auc = auc_times_n_jnp(batch.labels, pred, batch.row_mask)
            else:
                auc = jnp.float32(0.0)
        with names.scope(names.BACKWARD):
            gw, gV = loss.calc_grad(params, batch, pred, xv)
        if fused:
            state = fns.apply_grad_rows(state, slots, rows, gw, gV,
                                        params.v_mask, own_cap)
        else:
            state = fns.apply_grad(state, slots, gw, gV, params.v_mask)
        return constrain(state), objv, auc

    def eval_step(state, batch, slots):
        _, pred, objv, auc = forward(state, batch, slots)
        return pred, objv, auc

    return forward, train_step, eval_step


def fire_step_fault() -> None:
    """Chaos-harness injection point ``step.device`` (utils/faultinject):
    traversed on the HOST once per dispatched device step (the jitted
    programs themselves are pure and cannot host an injection site).
    ``err`` models a poisoned program / lost device surfacing at dispatch
    — it raises the same OSError-derived FaultInjected the IO paths use,
    so the learner's failure handling is exercised end to end; every
    armed fire also counts into ``faults_fired_total{point,kind}``."""
    from .utils import faultinject
    faultinject.act_default(faultinject.fire("step.device"))


def make_predict_fn(fns, loss: LossSpec):
    """Predict-only forward over (state, batch, slots) -> (pred, objv, auc).

    The serving subsystem's step (serve/executor.py): identical ops to
    make_step_fns' eval_step — gather [w, V] rows, loss forward, objective
    + exact AUC — without building the train step, so a read-only store
    (no optimizer state) can serve it. Sharing the op sequence is
    load-bearing: task=pred and task=serve dispatch the SAME program for
    the same batch shapes, which is what makes their outputs bit-identical
    (tests/test_serve.py golden test)."""

    def predict_step(state, batch, slots):
        params, _ = pull(fns, state, slots)
        with names.scope(names.FORWARD):
            pred = loss.predict(params, batch)
            objv = loss.evaluate(pred, batch)
            auc = auc_times_n_jnp(batch.labels, pred, batch.row_mask)
        return pred, objv, auc

    return predict_step
