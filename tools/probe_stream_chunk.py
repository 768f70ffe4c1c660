"""Probe: should STREAMED (cache-less) panel training chunk on device?

Staged runs build the chunked-run backward layout once at staging time
and replay it. Streamed runs
currently dispatch the unsorted-scatter backward — the round-4 note
("a per-batch per-epoch argsort would eat the win") was measured for the
HOST-side sort in the old sorted-backward era. This probe times one mode
per process (fresh chip state; pass --mode):

  chunked  : host-prechunked batches + chunked step (the replay ceiling)
  unsorted : plain panel batches + unsorted-scatter backward (streaming
             today)
  devchunk : plain panel batches; each step first runs the jitted
             panel_chunk_tokens on device, then the chunked step (what a
             streamed run COULD do with zero host cost)

Usage: python tools/probe_stream_chunk.py --mode devchunk [--vdim 64]
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
    ap.add_argument("--mode", choices=("chunked", "unsorted", "devchunk"),
                    required=True)
    ap.add_argument("--vdim", type=int, default=64)
    ap.add_argument("--batch", type=int, default=65536)
    ap.add_argument("--uniq", type=int, default=1 << 17)
    ap.add_argument("--capacity", type=int, default=1 << 21)
    ap.add_argument("--steps", type=int, default=30)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from bench import build_step, make_batches
    from difacto_tpu.ops.batch import panel_chunk_tokens

    step_raw, state = build_step(args.vdim, args.capacity,
                                 "bfloat16")[:2]
    hb = make_batches(4, args.batch, 39, args.uniq, args.capacity, "zipf")
    u_cap = int(hb[0][1].shape[0])
    chunker = jax.jit(panel_chunk_tokens, static_argnums=(1,))
    batches = []
    for b, s in hb:
        bd = jax.device_put(b)
        if args.mode != "chunked":
            bd = bd._replace(chunk_idx=None, chunk_lane=None,
                             chunk_vals=None)
        batches.append((bd, jnp.asarray(s)))
    step = jax.jit(step_raw, donate_argnums=0)

    def one(state, i):
        b, s = batches[i % 4]
        if args.mode == "devchunk":
            # lint: ok(jax-recompile) u_cap is fixed for the probe's
            # lifetime (derived once from the generated batch set)
            b = chunker(b, u_cap)
        return step(state, b, s)

    state, objv, _ = one(state, 0)
    # lint: ok(jax-host-sync) completion fence of the timing harness
    float(objv)  # compile + warm
    t0 = time.perf_counter()
    for i in range(args.steps):
        state, objv, _ = one(state, i)
    # lint: ok(jax-host-sync) completion fence of the timing harness
    float(objv)
    dt = (time.perf_counter() - t0) / args.steps
    print(json.dumps({"mode": args.mode, "V": args.vdim, "B": args.batch,
                      "u_cap": u_cap, "ms": round(dt * 1e3, 1),
                      "eps": round(args.batch / dt)}))


if __name__ == "__main__":
    main()
