"""Probe: the V16 step's width-independent ~38 ms floor (verdict weak #4).

The V64 and V16 steps cost the same wall clock even though V16 moves ~4x
fewer bytes. The round-5 trace put the residue in the forward tail: 39
per-column gathers of the combined [w | V] token rows. At V16 those rows
are 17 bf16 elements = 34 bytes — well under the 128-lane tile, so every
gather row is a misaligned read (the same pathology pad_v_rows fixed for
the VVg scatter, where 128-col rows ran 2.3x faster than 32-col at 4x
the bytes).

Variants timed on the real chip at the staged-criteo V16 shape:
  prod      : production step (compact [U, 17] wv gather source)
  pad32     : wv zero-padded to [U, 32] (one 64-byte sublane)
  pad64     : wv zero-padded to [U, 64]
  pad128    : wv zero-padded to [U, 128] (full lane tile)
  twocol    : two panel columns per gather ([2B] index vectors)
  fwd_only  : forward alone (prod), isolating the tail from the backward

Usage: python tools/probe_v16.py [--batch 32768] [--uniq 160000]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import numpy as np  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=32768)
    ap.add_argument("--vdim", type=int, default=16)
    ap.add_argument("--nnz-per-row", type=int, default=39)
    ap.add_argument("--uniq", type=int, default=160_000)
    ap.add_argument("--capacity", type=int, default=1 << 22)
    ap.add_argument("--steps", type=int, default=30)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from bench import build_step, make_batches
    from difacto_tpu.losses import create
    from difacto_tpu.losses.fm import (PRED_CLAMP, _p_vector, _vmask,
                                       _fm_grad_panel_chunked, logit_objv)
    from difacto_tpu.losses.metrics import auc_times_n_binned_jnp
    from difacto_tpu.step import make_step_fns
    from difacto_tpu.updaters.sgd_updater import (SGDUpdaterParam,
                                                  init_state, make_fns)

    k = args.vdim
    param = SGDUpdaterParam(V_dim=k, V_threshold=0, lr=0.1, l1=1e-4,
                            l2=1e-4, V_dtype="bfloat16")
    fns = make_fns(param)
    loss = create("fm", k)
    state0 = init_state(param, args.capacity)
    from difacto_tpu.updaters.sgd_updater import set_all_live
    state0 = set_all_live(param, state0)
    # host-side template: each variant donates its own device copy (a
    # shared device state would be deleted by the first donation)
    state0 = jax.tree.map(np.asarray, state0)

    host_batches = make_batches(4, args.batch, args.nnz_per_row, args.uniq,
                                args.capacity, "zipf")
    batches = [jax.device_put(b) for b, _ in host_batches]
    slots_l = [jnp.asarray(s) for _, s in host_batches]
    u_cap = slots_l[0].shape[0]

    def fwd_variant(pad_to: int = 0, twocol: bool = False):
        """fm_predict_panel_xv with a padded gather source / batched
        columns (experimental twins of losses/fm.py)."""
        def predict_xv(params, pb):
            dt = params.V.dtype
            B, F = pb.idx.shape
            Vm = params.V * _vmask(params).astype(dt)[:, None]
            wv = jnp.concatenate([params.w.astype(dt)[:, None], Vm], axis=1)
            if pad_to > 1 + k:
                wv = jnp.pad(wv, ((0, 0), (0, pad_to - 1 - k)))
            idxT = pb.idx.T
            pred = jnp.zeros((B,), jnp.float32)
            XV = jnp.zeros((B, k), jnp.float32)
            XXVV = jnp.zeros((B, k), jnp.float32)
            if twocol:
                for f in range(0, F - 1, 2):
                    ix = jnp.concatenate([idxT[f], idxT[f + 1]])
                    tok = wv[ix]                     # [2B, width]
                    t2 = tok[:, 1:1 + k].astype(jnp.float32)
                    wc = (tok[:B, 0] + tok[B:, 0]).astype(jnp.float32)
                    ta, tb = t2[:B], t2[B:]
                    pred = pred + wc
                    XV = XV + ta + tb
                    XXVV = XXVV + ta * ta + tb * tb
                for f in range(F - F % 2, F):
                    tok = wv[idxT[f]]
                    t = tok[:, 1:1 + k].astype(jnp.float32)
                    pred = pred + tok[:, 0].astype(jnp.float32)
                    XV = XV + t
                    XXVV = XXVV + t * t
            else:
                for f in range(F):
                    tok = wv[idxT[f]]
                    wc = tok[:, 0].astype(jnp.float32)
                    t = tok[:, 1:1 + k].astype(jnp.float32)
                    pred = pred + wc
                    XV = XV + t
                    XXVV = XXVV + t * t
            pred = pred + 0.5 * jnp.sum(XV * XV - XXVV, axis=1)
            return jnp.clip(pred, -PRED_CLAMP, PRED_CLAMP), XV
        return predict_xv

    def make_train(predict_xv):
        def train_step(state, batch, slots):
            from difacto_tpu.losses import FMParams
            w, V, vmask = fns.get_rows(state, slots)
            params = FMParams(w=w, V=V, v_mask=vmask)
            pred, xv = predict_xv(params, batch)
            objv = logit_objv(pred, batch)
            auc = auc_times_n_binned_jnp(batch.labels, pred, batch.row_mask)
            p = _p_vector(pred, batch)
            gw, gV = _fm_grad_panel_chunked(params, batch, p, xv)
            state = fns.apply_grad(state, slots, gw, gV, vmask)
            return state, objv, auc
        return train_step

    _, prod_step, _ = make_step_fns(fns, loss)

    def fwd_only(state, batch, slots):
        from difacto_tpu.losses import FMParams
        w, V, vmask = fns.get_rows(state, slots)
        pred, xv = loss.predict_xv(FMParams(w=w, V=V, v_mask=vmask), batch)
        return state, logit_objv(pred, batch), jnp.float32(0)

    variants = {
        "prod": prod_step,
        "fwd_only": fwd_only,
        "pad32": make_train(fwd_variant(pad_to=32)),
        "pad64": make_train(fwd_variant(pad_to=64)),
        "pad128": make_train(fwd_variant(pad_to=128)),
        "twocol": make_train(fwd_variant(twocol=True)),
    }

    out = {"batch": args.batch, "vdim": k, "u_cap": int(u_cap),
           "steps": args.steps}
    for name, raw in variants.items():
        # lint: ok(jax-recompile) the probe's PURPOSE is one fresh
        # compile per kernel variant — the loop iterates variants, not
        # steps
        step = jax.jit(raw, donate_argnums=0)
        state = jax.device_put(state0)
        state, objv, _ = step(state, batches[0], slots_l[0])
        # lint: ok(jax-host-sync) completion fence of the timing harness
        float(objv)  # compile + warm
        t0 = time.perf_counter()
        for i in range(args.steps):
            state, objv, _ = step(state, batches[i % 4], slots_l[i % 4])
        # lint: ok(jax-host-sync) completion fence of the timing harness
        float(objv)
        dt = (time.perf_counter() - t0) / args.steps
        out[name] = {"ms_per_step": round(dt * 1e3, 1),
                     "examples_per_sec": round(args.batch / dt, 1)}
        del state
        print(json.dumps({name: out[name]}), flush=True)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
