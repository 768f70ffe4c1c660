"""Benchmark: FM training-step throughput (examples/sec) on one chip.

Measures the full fused SGD hot path — gather [w,V] rows, FM forward
(SpMV + 2xSpMM sum-of-squares), logit objective + AUC, backward, FTRL/AdaGrad
scatter update — on synthetic Criteo-like batches (V_dim=64, 39 nnz/row),
the north-star config of BASELINE.md.

Defaults reflect the TPU-native operating point: batch 65536 (synchronous
large-batch steps replace the reference's 50-worker async pipelining,
SURVEY §7 hard part (b); distinct-feature rows saturate, so the per-row
table costs amortize), zipf-skewed feature draws (criteo categoricals are
heavy-tailed; --dist uniform gives the adversarial flat draw), bfloat16
embedding storage (V_dtype).

Prints ONE JSON line. ``vs_baseline`` compares against an *estimated*
32-worker ps-lite CPU aggregate (the reference publishes no numbers —
BASELINE.json.published is empty): 32 workers x ~15k ex/s/worker for FM
V_dim=64 ~= 5e5 ex/s. The driver-set target is vs_baseline >= 20 on a full
v5e-8 (>= 2.5 per chip x 8). ``roofline`` reports the step's HBM traffic
by the bench's own byte model and, on a TPU, its share of the chip's
published HBM peak (``HBM_PEAK_GBPS``), so progress is measurable without
the baseline fiction. Every JSON line carries a ``device`` block naming
what the process bound.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

# estimated 32-worker ps-lite CPU examples/sec on Criteo FM V_dim=64 (see
# module docstring; the reference repo publishes no quantitative baseline)
REF_PSLITE_32W_EPS = 5.0e5
# published HBM bandwidth per chip in GB/s, keyed by jax's device_kind.
# A TPU kind that is not here is an error, not a default.
HBM_PEAK_GBPS = {
    "TPU v5 lite": 819.0,  # Google Cloud documentation, "TPU v5e"
}


def hbm_peak_gbps():
    """The bound chip's published HBM peak, or None off-TPU (a CPU run
    prints bytes and no fraction of anything)."""
    from difacto_tpu.utils.device import bound_device
    dev = bound_device()
    if dev["platform"] != "tpu":
        return None
    if dev["device_kind"] not in HBM_PEAK_GBPS:
        raise KeyError(
            f"no published HBM peak for device_kind "
            f"{dev['device_kind']!r}: add it to bench.HBM_PEAK_GBPS "
            "with its source")
    return HBM_PEAK_GBPS[dev["device_kind"]]


def emit(result: dict) -> None:
    """Print one JSON line, stamped with the device it was taken on."""
    from difacto_tpu.utils.device import bound_device
    print(json.dumps({**result, "device": bound_device()}))


def build_step(V_dim: int, capacity: int, v_dtype: str,
               chunks_sorted: bool = True, fused_kernel: str = "auto",
               mesh=None):
    import dataclasses

    from difacto_tpu.losses import create
    from difacto_tpu.step import make_step_fns
    from difacto_tpu.updaters.sgd_updater import (SGDUpdaterParam, init_state,
                                                  make_fns)

    param = SGDUpdaterParam(V_dim=V_dim, V_threshold=0, lr=0.1, l1=1e-4,
                            l2=1e-4, V_dtype=v_dtype,
                            fused_kernel=fused_kernel)
    fns = make_fns(param, mesh=mesh)
    loss = create("fm", V_dim)
    if not chunks_sorted:
        loss = dataclasses.replace(loss, chunks_sorted=False)
    state = init_state(param, capacity)
    if V_dim:
        from difacto_tpu.updaters.sgd_updater import set_all_live
        state = set_all_live(param, state)

    # under a mesh the train step must pin its returned state to the fs
    # key-range layout (step.state_constrainer) — otherwise GSPMD output
    # inference is free to re-partition the donated table (the bench
    # would silently measure an unpinned program the product never runs)
    state_shardings = None
    if mesh is not None:
        from difacto_tpu.parallel import sharding_tree, state_sharding
        state_shardings = sharding_tree(state, state_sharding(mesh))
    _, train_step, _ = make_step_fns(fns, loss,
                                     state_shardings=state_shardings)
    # raw (unjitted) step: the bench jits it with a donated state and
    # dispatches per step, the production replay pattern
    return train_step, state, fns, loss, param


def make_batches(n: int, B: int, nnz_per_row: int, uniq_space: int,
                 capacity: int, dist: str, seed: int = 0,
                 chunk_multiple: int = 1):
    """Host-side localized PANEL batches (fixed-width [B, F] index matrix,
    the criteo layout) + sorted-unique slot vectors padded with ascending
    out-of-bounds indices (the device-kernel contract).
    ``chunk_multiple`` > 1 pads the chunk arrays' C axis up to a multiple
    (mesh runs shard C over the dp axis, which needs even division)."""
    from difacto_tpu.data.rowblock import RowBlock
    from difacto_tpu.ops.batch import bucket, pad_panel
    from difacto_tpu.store.local import pad_slots_oob

    rng = np.random.RandomState(seed)
    raw = []
    u_cap = 8
    for _ in range(n):
        if dist == "zipf":
            idx = ((rng.zipf(1.25, B * nnz_per_row) - 1)
                   % uniq_space).astype(np.int64)
        else:
            idx = rng.randint(0, uniq_space, B * nnz_per_row)
        uniq, inverse = np.unique(idx, return_inverse=True)
        raw.append((uniq, inverse))
        u_cap = max(u_cap, bucket(len(uniq)))

    import jax
    import jax.numpy as jnp

    from difacto_tpu.ops.batch import panel_chunk_tokens
    chunker = jax.jit(panel_chunk_tokens, static_argnums=(1,))

    out = []
    for uniq, inverse in raw:
        offset = np.arange(B + 1, dtype=np.int64) * nnz_per_row
        blk = RowBlock(
            offset=offset,
            label=rng.choice([0.0, 1.0], B).astype(np.float32),
            index=inverse.astype(np.uint32),
            value=None,  # binary features, like criteo
        )
        batch = pad_panel(blk, num_uniq=len(uniq), batch_cap=B,
                          width=nnz_per_row)
        # chunked-run backward layout: the bench models the steady-state
        # cached replay, which stages the layout once (panel_chunk_tokens)
        # and takes the chunked FM backward every step
        if chunk_multiple > 1:
            # mesh runs shard the C axis over dp: build host-side with C
            # rounded up (the same path learners/sgd.py _panel_host_batch
            # takes), instead of the device chunker
            from difacto_tpu.ops.batch import (chunk_cap,
                                               panel_chunk_tokens_np)
            C = -(-chunk_cap(u_cap, B * nnz_per_row) // chunk_multiple) \
                * chunk_multiple
            ci, cl, cv = panel_chunk_tokens_np(
                inverse.astype(np.int32), None, u_cap, B, nnz_per_row, C=C)
            batch = batch._replace(chunk_idx=jnp.asarray(ci),
                                   chunk_lane=jnp.asarray(cl),
                                   chunk_vals=cv)
        else:
            batch = chunker(batch, u_cap)
        slots = np.sort(rng.permutation(capacity - 1)[:len(uniq)] + 1)
        out.append((batch, pad_slots_oob(slots.astype(np.int32), u_cap,
                                         capacity)))
    return out


def roofline(nnz: int, u_cap: int, V_dim: int, v_bytes: int,
             dt_sec: float, vvg_cols: int = 0) -> dict:
    """Approximate HBM bytes moved per step; on a TPU also the share of
    the chip's published HBM peak that rate is.

    Models the production step as benched: storage-dtype forward token
    gather + the CHUNKED backward whose f32
    [~nnz, V_dim+1] contribution stream moves once through the chunk
    gather and once through the partial reduction, plus the chunk-layout
    index reads. ``vvg_cols`` is the ACTUAL stored row width (pad_v_rows
    lane-pads narrow V to the 128-lane tile; defaults to the compact
    2*V_dim)."""
    if not vvg_cols:
        vvg_cols = 2 * V_dim
    # fused-row g+s: the row carries V, Vg AND the FTRL scalar lanes
    # (updaters/sgd_updater.py row_layout), so there is no separate
    # scalar-table term; V_dim=0 keeps the flat f32 w/z/sqrt_g arrays
    table = (u_cap * vvg_cols * v_bytes * 2 if V_dim
             else u_cap * 3 * 4 * 2)
    tokens = (nnz * (V_dim + 1) * v_bytes      # fwd [w|V] token gather
              + nnz * (V_dim + 1) * 4 * 2      # bwd f32 contribs (chunk
                                               # gather + partial reduce)
              + nnz * 4 * 2)                   # chunk_idx/lane reads (~)
    total = table + tokens
    out = {
        "approx_bytes_per_step": int(total),
        "achieved_gbps": round(total / dt_sec / 1e9, 1),
    }
    peak = hbm_peak_gbps()
    if peak is not None:
        out["hbm_peak_gbps"] = peak
        out["bw_fraction"] = round(total / dt_sec / 1e9 / peak, 3)
    return out


def run_kernel_bench(args, host_batches, nnz: int) -> dict:
    """``kernel`` block (ISSUE 13 satellite): per-backend roofline
    attribution of the fused v64 step. For every available
    ``fused_kernel`` backend the FULL step is timed fresh (own table,
    donated dispatch chain — same harness as the headline), emitting
    examples/sec + the roofline block; then the step is split into its
    four legs — dedup / gather / interaction (forward+backward from
    pre-gathered rows) / scatter-update — each as its own jitted
    program over the same staged batches, so the roofline gap is
    attributed to a leg instead of guessed."""
    import jax
    import jax.numpy as jnp

    from difacto_tpu.losses import FMParams
    from difacto_tpu.ops import fused as fused_ops
    from difacto_tpu.utils import jaxtrace

    v_bytes = 2 if args.vdtype == "bfloat16" else 4
    # no pallas leg: interpret mode is a parity harness, and on a TPU
    # backend Mosaic refuses the kernels (ops/fused.PallasRefused)
    backends = ["off", "jnp"]
    steps = args.steps
    out: dict = {"requested": args.fused_kernel, "backends": {},
                 "measured": backends}

    def _chain(step, state, batches, slots_l):
        state, objv, _ = step(state, batches[0], slots_l[0])
        jaxtrace.fetch(objv, point="bench.fence")
        t0 = time.perf_counter()
        for i in range(steps):
            state, objv, _ = step(state, batches[i % len(batches)],
                                  slots_l[i % len(slots_l)])
        jaxtrace.fetch(objv, point="bench.fence")
        return time.perf_counter() - t0, state

    u_cap = len(host_batches[0][1])
    for b in backends:
        step_raw, state, _, _, _ = build_step(
            args.vdim, args.capacity, args.vdtype, fused_kernel=b)
        # lint: ok(jax-recompile) one jit per BACKEND leg — this loop
        # IS the kernel-bench matrix (off/jnp); each leg
        # compiles exactly once by construction
        step = jax.jit(step_raw, donate_argnums=0)
        batches = [jax.device_put(bb) for bb, _ in host_batches]
        slots_l = [jnp.asarray(s) for _, s in host_batches]
        dt, state = _chain(step, state, batches, slots_l)
        vvg_cols = int(state.VVg.shape[1])
        del state
        roof = roofline(args.batch_size * nnz, u_cap, args.vdim,
                        v_bytes, dt / steps, vvg_cols=vvg_cols)
        out["backends"][b] = {
            "examples_per_sec": round(steps * args.batch_size / dt, 1),
            **roof,
        }

    # ------------------------------------------------------------ legs
    resolved = fused_ops.resolve_backend(
        args.fused_kernel if args.fused_kernel != "off" else "auto",
        V_dim=args.vdim)
    step_raw, state, fns, loss, param = build_step(
        args.vdim, args.capacity, args.vdtype, fused_kernel=resolved)
    batches = [jax.device_put(bb) for bb, _ in host_batches]
    slots_l = [jnp.asarray(s) for _, s in host_batches]
    # token lanes in table-slot space: the device-dedup leg's input
    toks = [jnp.asarray(np.asarray(s)[np.asarray(bb.idx).reshape(-1)])
            for bb, s in host_batches]

    dedup_fn = jax.jit(
        lambda t: fused_ops.dedup_tokens(t, u_cap, args.capacity))
    gather_fn = jax.jit(
        lambda T, s: fused_ops.gather_rows(T, s, resolved))

    def interact(state, rows, pb):
        w, V, vm = fns.rows_to_params(state, rows)
        params = FMParams(w=w, V=V, v_mask=vm)
        pred, xv = loss.predict_xv(params, pb)
        objv = loss.evaluate(pred, pb)
        gw, gV = loss.calc_grad(params, pb, pred, xv)
        return objv, gw, gV, vm

    interact_fn = jax.jit(interact)
    scatter_fn = jax.jit(fns.apply_grad_rows, donate_argnums=0)

    n_bk = len(batches)
    rows_l = [gather_fn(state.VVg, s) for s in slots_l]
    grads_l = [interact_fn(state, rows_l[i], batches[i])
               for i in range(n_bk)]

    def _leg(fn, argsets, fence):
        # warm + chain like the headline: async dispatch pipelines the
        # RTT, the scalar fetch is the completion fence
        r = fn(*argsets[0])
        jaxtrace.fetch(fence(r), point="bench.fence")
        t0 = time.perf_counter()
        for i in range(steps):
            r = fn(*argsets[i % len(argsets)])
        jaxtrace.fetch(fence(r), point="bench.fence")
        return (time.perf_counter() - t0) / steps * 1e3

    legs = {
        "dedup_ms": _leg(dedup_fn, [(t,) for t in toks],
                         lambda r: r[2]),
        "gather_ms": _leg(gather_fn,
                          [(state.VVg, s) for s in slots_l],
                          lambda r: r[0, 0]),
        "interaction_ms": _leg(
            interact_fn,
            [(state, rows_l[i], batches[i]) for i in range(n_bk)],
            lambda r: r[0]),
    }
    # scatter leg donates/rebinds the table state
    _, gw0, gV0, vm0 = grads_l[0]
    st = state
    st = scatter_fn(st, slots_l[0], rows_l[0], gw0, gV0, vm0)
    jaxtrace.fetch(fns.evaluate(st)[0], point="bench.fence")
    t0 = time.perf_counter()
    for i in range(steps):
        j = i % n_bk
        _, gw_i, gV_i, vm_i = grads_l[j]
        st = scatter_fn(st, slots_l[j], rows_l[j], gw_i, gV_i, vm_i)
    jaxtrace.fetch(fns.evaluate(st)[0], point="bench.fence")
    legs["scatter_ms"] = (time.perf_counter() - t0) / steps * 1e3
    out["legs_ms"] = {k: round(v, 3) for k, v in legs.items()}
    out["legs_backend"] = resolved
    return out


def _gen_criteo_text(path: str, nrows: int, seed: int = 0) -> None:
    """Vectorised synthetic criteo-format text (zipf-skewed categoricals)."""
    rng = np.random.RandomState(seed)
    labels = rng.randint(0, 2, nrows).astype(str)
    ints = rng.randint(0, 1000, (nrows, 13)).astype(str)
    cats_raw = ((rng.zipf(1.25, (nrows, 26)) - 1) % 100000)
    cats = np.char.add("c", cats_raw.astype(str))
    cols = np.concatenate([labels[:, None], ints, cats], axis=1)
    with open(path, "w") as f:
        f.write("\n".join("\t".join(r) for r in cols) + "\n")


def run_e2e(args) -> dict:
    """End-to-end mode: criteo text -> rec binary cache (task=convert, the
    reference's CRB fast path, members aligned to the training batch size)
    -> training through the full stack (rec read -> hashed localize ->
    panel pack -> fused step). Reports BOTH steady-state regimes (round-4
    verdict weak #2 — the 1TB config cannot replay from HBM, so the
    streamed rate is the honest number at scale):

      replay   : epochs 1+ replay device-cached packed batches from HBM
                 (zero host->device traffic) — the small/cached-dataset
                 regime;
      streamed : device_cache_mb=0, every epoch runs the full host pack +
                 transfer + step pipeline — the >HBM-dataset regime.

    Epoch 0 (jit compiles + staging) is excluded from both."""
    import tempfile
    import time as _t

    from difacto_tpu.data.converter import Converter
    from difacto_tpu.learners import Learner

    nrows = args.e2e_rows
    epochs = 4
    with tempfile.TemporaryDirectory() as d:
        path = f"{d}/criteo.txt"
        _gen_criteo_text(path, nrows)

        conv = Converter()
        conv.init([("data_in", path), ("data_format", "criteo"),
                   ("data_out", f"{d}/criteo.rec"),
                   ("data_out_format", "rec"),
                   # align members to the training batch so cached batches
                   # never straddle members and shapes stay on the pinned
                   # schedule (round-3 verdict #1c)
                   ("rec_batch_size", str(args.e2e_batch))])
        conv.run()
        # per-stage convert accounting (ISSUE 7 satellite): Converter.run
        # fills stats with rows/eps/convert_s plus parse_s/write_s and the
        # worker-process count, so a convert regression localizes to a
        # stage just like the streamed epochs do
        convert_stats = dict(conv.stats)
        convert_eps = convert_stats.get("eps", 0.0)

        def train(cache_mb: int, n_epochs: int,
                  producer_mode: str = "thread"):
            learner = Learner.create("sgd")
            learner.init([("data_in", f"{d}/criteo.rec"),
                          ("data_format", "rec"),
                          ("loss", "fm"), ("V_dim", str(args.vdim)),
                          ("V_threshold", "0"), ("lr", "0.1"),
                          ("l1", "1e-4"),
                          ("batch_size", str(args.e2e_batch)),
                          ("shuffle", "0"),
                          ("max_num_epochs", str(n_epochs)),
                          ("num_jobs_per_epoch", "1"),
                          ("report_interval", "0"), ("stop_rel_objv", "0"),
                          ("V_dtype", args.vdtype),
                          ("device_cache_mb", str(cache_mb)),
                          ("producer_mode", producer_mode),
                          ("hash_capacity", str(args.capacity))])
            marks = []
            learner.add_epoch_end_callback(
                lambda e, t, v: marks.append(_t.perf_counter()))
            learner.run()
            rate = (n_epochs - 1) * nrows / (marks[-1] - marks[0])
            return rate, learner.device_cache_info(), learner.stage_stats()

        # the streamed regime has no staging warm-up to amortize, so a
        # shorter window (2 timed epochs) keeps the bench bounded; its
        # epoch count is reported alongside so the two regimes are never
        # mistaken for like-for-like windows
        streamed_epochs = 3
        # 4 GB cache: the 1.8M-row window at batch 65536 stages ~2.2 GB of
        # packed+chunked batches — comfortably inside this 16 GB chip next
        # to the ~1.1 GB fused-row table, and the bigger batch halves the
        # per-step dispatch overhead
        replay, cache_info, _ = train(4096, epochs)
        # the streamed run drives the requested producer transport
        # (--producer-mode; auto = process on multi-core hosts) and keeps
        # the per-stage decomposition so the headline is attributable:
        # pack/transfer overlapping the device steps shows up as epoch
        # wall-clock < the serial stage sum
        streamed, _, streamed_stages = train(
            0, streamed_epochs, producer_mode=args.producer_mode)
    # a frozen training cache means the "replay" window was a MIXED
    # regime (staged prefix replayed, tail streamed) — label it so the
    # number is never mistaken for full-HBM replay at larger --e2e-rows
    from difacto_tpu.learners.sgd import K_TRAINING
    train_cache = cache_info.get(K_TRAINING, {})
    out = {
        "metric": "fm_e2e_criteo_examples_per_sec",
        "value": round(replay, 1),
        "unit": "examples/sec",
        "vs_baseline": round(replay / REF_PSLITE_32W_EPS, 3),
        "replay_cache": train_cache,
        "streamed": {
            "metric": "fm_e2e_criteo_streamed_examples_per_sec",
            "value": round(streamed, 1),
            "vs_baseline": round(streamed / REF_PSLITE_32W_EPS, 3),
            "epochs_timed": streamed_epochs - 1,
            # which producer transport ran, and where the run's seconds
            # went (whole-run totals incl. epoch 0), SOURCED FROM THE OBS
            # REGISTRY (learner.stage_stats over stage_seconds_total —
            # ISSUE 4): parse/pack/ring-wait arrive from the producer
            # worker processes through their snapshot channel, so the
            # breakdown survives the process boundary and a streamed
            # regression localizes to a stage instead of hiding in the
            # headline
            "producer_mode": streamed_stages.pop("producer_mode"),
            "stages": streamed_stages,
        },
        "config": {"rows": nrows, "batch": args.e2e_batch,
                   "epochs_timed": epochs - 1,
                   "text_to_rec_convert_eps": round(convert_eps, 1)},
        "convert": convert_stats,
    }
    return out


def _gen_serve_rows(n_rows: int, nnz_per_row: int, id_space: int,
                    seed: int = 0) -> list:
    """Synthetic libsvm request lines for the serving bench."""
    rng = np.random.RandomState(seed)
    rows = []
    for _ in range(n_rows):
        ids = np.sort(rng.choice(id_space, nnz_per_row, replace=False))
        rows.append(("0 " + " ".join(f"{i}:1" for i in ids)).encode())
    return rows


def run_serve_bench(args) -> dict:
    """serve.* section: online-serving latency/throughput trajectory,
    tracked like the training numbers. An in-process ServeServer over a
    synthetic hashed model takes an open-loop Poisson load (tools/
    loadgen.py) at --serve-qps; a short warmup run compiles the shape
    buckets first, so ``steady_state_compiles`` reports the acceptance
    gate directly (0 = every measured dispatch was a bucket hit)."""
    import os
    import sys

    import tempfile
    import time as _time

    from difacto_tpu.serve import ModelReloader, ServeClient, ServeServer
    from difacto_tpu.store.local import SlotStore
    from difacto_tpu.updaters.sgd_updater import (SGDUpdaterParam,
                                                  set_all_live)
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    from loadgen import run_loadgen

    # l1_shrk off so the all-zero-w synthetic model still exercises the
    # full [w|V] gather + FM interaction path the real service pays
    param = SGDUpdaterParam(V_dim=args.serve_vdim, l1_shrk=False,
                            hash_capacity=args.serve_capacity)
    store = SlotStore(param, read_only=True)
    if args.serve_vdim:
        store.state = set_all_live(param, store.state)
    rows = _gen_serve_rows(512, args.nnz_per_row, 1 << 17)
    # takeover=True (SO_REUSEPORT): the takeover-gap measurement below
    # binds a successor to the same port, and the kernel requires every
    # binder of the pair to set the option
    server = ServeServer(store, batch_size=args.serve_batch,
                         max_delay_ms=args.serve_delay_ms,
                         queue_cap=args.serve_queue_cap, takeover=True)
    server.start()
    drain_s = 0.0
    bluegreen_ms = 0.0
    warm_parallel_ms = 0.0
    takeover_gap_ms = 0.0
    reload_ms: list = []
    try:
        # warmup at the TARGET rate: micro-batch occupancy (and so the
        # sticky shape caps) depends on the arrival rate, so warming at a
        # lower rate would leave the measured window to pay the compiles
        run_loadgen(server.host, server.port, rows, qps=args.serve_qps,
                    duration_s=2.0)
        before = server.executor.stats()["buckets_compiled"]
        rep = run_loadgen(server.host, server.port, rows,
                          qps=args.serve_qps,
                          duration_s=args.serve_seconds,
                          zipf_alpha=args.zipf_alpha)
        after = server.executor.stats()["buckets_compiled"]
        snap = server.stats_snapshot()
        # resilience cost (ISSUE 3): hot-reload latency over the wire —
        # save the serving table as a real checkpoint, then time full
        # #reload cycles (verify + weights-only load + atomic swap)
        with tempfile.TemporaryDirectory() as td:
            model = os.path.join(td, "model")
            store.save(model)
            server.reloader = ModelReloader(server.executor, model,
                                            server=server)
            with ServeClient(server.host, server.port) as c:
                for _ in range(5):
                    store.save(model)  # bump the generation
                    t0 = _time.monotonic()
                    res = c.reload()
                    dt = (_time.monotonic() - t0) * 1e3
                    if res.get("ok"):
                        reload_ms.append(dt)
                # blue/green cost (ISSUE 5): a GEOMETRY-CHANGING reload
                # (different V_dim) warms a second executor on the live
                # warm-set and swaps it under the batcher — time the
                # whole build+warm+swap the old design answered with
                # "restart the server"
                param2 = SGDUpdaterParam(
                    V_dim=args.serve_vdim + 4, l1_shrk=False,
                    hash_capacity=args.serve_capacity)
                store2 = SlotStore(param2, read_only=True)
                store2.state = set_all_live(param2, store2.state)
                model2 = os.path.join(td, "model2")
                store2.save(model2)
                t0 = _time.monotonic()
                res = c.reload(model2)
                if res.get("ok"):
                    bluegreen_ms = (_time.monotonic() - t0) * 1e3
                    # the warm-set portion alone, now compiled on a
                    # thread pool (serve/reload.py warm_workers) — the
                    # number the parallel-warm satellite moves
                    warm_parallel_ms = server.reloader.last_warm_ms
        # SO_REUSEPORT takeover gap: bind a successor to the SAME port,
        # drain the incumbent, and measure handoff-start -> first fresh
        # connection answered ready by the successor (the client-visible
        # upper bound; the successor accepts throughout, so ~drain time)
        import threading as _threading
        succ = ServeServer(store2, batch_size=args.serve_batch,
                           max_delay_ms=args.serve_delay_ms,
                           host=server.host, port=server.port,
                           takeover=True).start()
        succ_id = succ.health_snapshot()["server_id"]
        gap_box: dict = {}
        t0 = _time.monotonic()

        def _probe():
            while _time.monotonic() - t0 < 15.0:
                try:
                    with ServeClient(server.host, server.port,
                                     timeout=2.0) as pc:
                        h = pc.health()
                    if h.get("server_id") == succ_id \
                            and h.get("status") == "ready":
                        gap_box["ms"] = (_time.monotonic() - t0) * 1e3
                        return
                except (OSError, ConnectionError, ValueError):
                    pass
                _time.sleep(0.005)

        probe = _threading.Thread(target=_probe)
        probe.start()
        # graceful-drain time with the queue already empty (the floor an
        # orchestrator pays per rotation) doubles as the handoff
        drain_s = server.drain()
        probe.join()
        takeover_gap_ms = gap_box.get("ms", 0.0)
        succ.close()
    finally:
        server.close()

    # elastic-autoscaling leg (ISSUE 18): one deliberately under-
    # provisioned replica takes the diurnal peak behind a router while
    # the autoscaler watches its #health — the numbers tracked are how
    # many spawns/drains the cycle produced and how long the fleet took
    # to settle (scale-up decision -> queue/shed back under threshold)
    auto_spawns = auto_drains = 0
    auto_settle_s = 0.0
    from difacto_tpu.serve import Autoscaler, RouterServer
    from loadgen import run_loadgen_failover
    # slow flush cadence + small queue: the diurnal 1.6x peak visibly
    # queues on the base (frac > up_queue_frac) while the 0.3x trough
    # does not — the scale-up is deterministic, not a scheduler race
    base = ServeServer(store, batch_size=args.serve_batch,
                       max_delay_ms=50.0, queue_cap=64)
    base.start()
    extra: list = []

    def _spawn(_idx):
        s = ServeServer(store, batch_size=args.serve_batch,
                        max_delay_ms=args.serve_delay_ms,
                        queue_cap=args.serve_queue_cap)
        s.start()
        extra.append(s)
        return (s.host, s.port)

    router = RouterServer([(base.host, base.port)])
    router.start()
    scaler_t0 = _time.monotonic()
    scaler = Autoscaler([(base.host, base.port)], _spawn,
                        router=(router.host, router.port),
                        min_replicas=1, max_replicas=3, poll_s=0.1,
                        ewma=1.0, up_queue_frac=0.4, up_shed_rate=0.01,
                        down_queue_frac=0.2, up_ticks=1, down_ticks=10,
                        cooldown_s=0.5)
    scaler.start()
    try:
        run_loadgen_failover([(router.host, router.port)], rows,
                             qps=args.serve_qps, duration_s=4.0,
                             profile="diurnal")
        t_up = next((e["t"] for e in scaler.events
                     if e["action"] == "up"), None)
        if t_up is not None:
            # settle: from the scale-up decision until the aggregated
            # queue/shed signals are back under the scale-up threshold
            deadline = _time.monotonic() + 10.0
            while _time.monotonic() < deadline:
                m = scaler.poll()
                if m["queue_frac"] < 0.5 and m["shed_rate"] <= 0.01:
                    auto_settle_s = (_time.monotonic() - scaler_t0) - t_up
                    break
                _time.sleep(0.05)
        scaler.close()
        # idle fleet: the scale-down path must walk back to min_replicas
        end = _time.monotonic() + 3.0
        while _time.monotonic() < end and len(scaler.endpoints()) > 1:
            scaler.step()
            _time.sleep(0.05)
        auto_spawns = sum(1 for e in scaler.events
                          if e["action"] == "up")
        auto_drains = sum(1 for e in scaler.events
                          if e["action"] == "down")
    finally:
        scaler.close()
        router.close()
        for s in extra:
            s.close()
        base.close()
    return {
        "reload_p99_ms": round(float(np.percentile(reload_ms, 99)), 3)
        if reload_ms else 0.0,
        "drain_s": round(drain_s, 3),
        "bluegreen_swap_ms": round(bluegreen_ms, 3),
        "warm_parallel_ms": round(warm_parallel_ms, 3),
        "takeover_gap_ms": round(takeover_gap_ms, 3),
        "autoscale_spawns": auto_spawns,
        "autoscale_drains": auto_drains,
        "autoscale_settle_s": round(auto_settle_s, 3),
        "p50_ms": rep.get("p50_ms", 0.0),
        "p95_ms": rep.get("p95_ms", 0.0),
        "p99_ms": rep.get("p99_ms", 0.0),
        "qps": rep["achieved_qps"],
        "shed_rate": rep["shed_rate"],
        "target_qps": args.serve_qps,
        "offered_qps": rep["offered_qps"],
        "batch_occupancy": snap["batch_occupancy"],
        "steady_state_compiles": after - before,
        "buckets_compiled": after,
        "config": {"batch": args.serve_batch,
                   "max_delay_ms": args.serve_delay_ms,
                   "queue_cap": args.serve_queue_cap,
                   "V_dim": args.serve_vdim,
                   "nnz_per_row": args.nnz_per_row,
                   "seconds": args.serve_seconds},
    }


def run_online_bench(args) -> dict:
    """online.* section: steady state of the serve→log→train→reload
    loop (docs/serving.md "Continuous learning"). One in-process server
    logs served rows into an OnlineLog while the feedback loadgen
    scores + labels them (#score/#label) and a REAL ``task=online``
    trainer subprocess tails the log, committing generations back over
    ``#reload``. Freshness is read from the trainer's own metrics JSONL
    (every flush carries the train_behind_serve_s gauge), so the p99 is
    measured across the run, not a final-state snapshot."""
    import os
    import subprocess
    import sys
    import tempfile

    from difacto_tpu.__main__ import main as difacto_main
    from difacto_tpu.online.log import OnlineLog
    from difacto_tpu.serve import ModelReloader, ServeServer, \
        open_serving_store
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    from loadgen import run_loadgen_feedback

    # the parent trains and serves in-process, i.e. it holds the chip
    # for as long as the trainer child below would need it
    from difacto_tpu.utils.device import refuse_chip_child
    refuse_chip_child("bench.py --online's task=online trainer")

    repo = os.path.dirname(os.path.abspath(__file__))
    rng = np.random.RandomState(0)
    with tempfile.TemporaryDirectory() as td:
        # a small labeled synthetic set: seed model + request stream
        data = os.path.join(td, "train.libsvm")
        with open(data, "w") as f:
            for i in range(256):
                ids = np.sort(rng.choice(1 << 14, args.nnz_per_row,
                                         replace=False))
                f.write(f"{i % 2} "
                        + " ".join(f"{j}:1" for j in ids) + "\n")
        with open(data, "rb") as f:
            rows = [l for l in f.read().splitlines() if l.strip()]
        model = os.path.join(td, "model")
        difacto_main([f"data_in={data}", "lr=0.1", "batch_size=100",
                      "max_num_epochs=1", "shuffle=0",
                      "num_jobs_per_epoch=1", "report_interval=0",
                      f"model_out={model}"])
        log_dir = os.path.join(td, "log")
        online_log = OnlineLog(log_dir,
                               segment_rows=args.online_segment_rows,
                               label_delay_s=args.online_label_delay_s,
                               label_default="negative")
        store, _meta, _rem = open_serving_store(model, [])
        server = ServeServer(store, batch_size=args.serve_batch,
                             max_delay_ms=args.serve_delay_ms,
                             queue_cap=args.serve_queue_cap,
                             online_log=online_log)
        server.reloader = ModelReloader(server.executor, model,
                                        server=server)
        server.start()
        metrics = os.path.join(td, "trainer.metrics.jsonl")
        trainer = subprocess.Popen(
            [sys.executable, "-m", "difacto_tpu", "task=online",
             f"online_log_dir={log_dir}", f"model_out={model}",
             "lr=0.1", "batch_size=100", "report_interval=0",
             f"online_ckpt_interval_s={args.online_ckpt_s}",
             f"online_endpoints={server.host}:{server.port}",
             f"metrics_path={metrics}", "metrics_interval_s=0.5"],
            cwd=repo,
            env=dict(os.environ, PYTHONPATH=repo))
        try:
            rep = run_loadgen_feedback(
                server.host, server.port, rows,
                qps=args.online_qps, duration_s=args.online_seconds,
                label_delay_s=args.online_label_delay_s,
                label_rate=args.online_label_rate)
            # terminate the log; the trainer drains the sealed tail,
            # commits the final generation, and exits 0
            online_log.end()
            trainer_rc = trainer.wait(timeout=180)
            reloads = server.reloader.stats()["reloads"]
            generation = server.executor.stats()["model_generation"]
        finally:
            if trainer.poll() is None:
                trainer.kill()
                trainer.wait()
            server.close()
        behind = []
        for p in (metrics + ".1", metrics):
            if not os.path.exists(p):
                continue
            with open(p) as f:
                for line in f:
                    try:
                        snap = json.loads(line)["metrics"]
                    except (ValueError, KeyError):
                        continue
                    series = snap.get("gauges", {}).get(
                        "train_behind_serve_s", {})
                    behind.extend(series.values())
        log_stats = online_log.stats()
    return {
        "rows_per_s": rep["achieved_qps"],
        "train_behind_serve_s_p99":
            round(float(np.percentile(behind, 99)), 3) if behind else 0.0,
        "reload_count": reloads,
        "label_join_rate":
            round(rep["labels_acked"] / max(rep["sent"], 1), 4),
        "model_generation": generation,
        "trainer_rc": trainer_rc,
        "ok": rep["ok"],
        "err": rep["err"],
        "shed_rate": rep["shed_rate"],
        "labels_sent": rep["labels_sent"],
        "labels_acked": rep["labels_acked"],
        "rows_logged": log_stats["rows_logged"],
        "segments_sealed": log_stats["next_seg"],
        "config": {"qps": args.online_qps,
                   "seconds": args.online_seconds,
                   "segment_rows": args.online_segment_rows,
                   "label_rate": args.online_label_rate,
                   "label_delay_s": args.online_label_delay_s,
                   "ckpt_interval_s": args.online_ckpt_s},
    }


def run_durability_bench(args) -> dict:
    """durability.* section (ISSUE 20): what the write-ahead delta log
    costs and what it buys. Three numbers over one synthetic labeled
    set: ``wal_overhead_pct`` — wall-clock cost of logging touched rows
    every ``--durability-flush`` batches vs the identical WAL-off run
    (target <= 5%); ``recovery_s`` — time for a FRESH learner to climb
    the recovery ladder (checkpoint load + WAL replay) after the chain
    loses its newest delta segment, the simulated mid-window crash; and
    ``rpo_batches`` — batches of work that loss actually cost, which
    the WAL bounds at one flush window (the RPO the knob buys, asserted
    exactly in tests/test_durability.py's kill leg)."""
    import os
    import tempfile
    import time

    from difacto_tpu.__main__ import main as difacto_main
    from difacto_tpu.durability import wal as _wal
    from difacto_tpu.learners.sgd import SGDLearner

    rng = np.random.RandomState(0)
    flush = args.durability_flush
    with tempfile.TemporaryDirectory() as td:
        data = os.path.join(td, "train.libsvm")
        with open(data, "w") as f:
            for i in range(2000):
                ids = np.sort(rng.choice(1 << 14, args.nnz_per_row,
                                         replace=False))
                f.write(f"{i % 2} "
                        + " ".join(f"{j}:1" for j in ids) + "\n")
        common = [f"data_in={data}", "lr=0.1", "batch_size=100",
                  "max_num_epochs=2", "shuffle=0", "seed=7",
                  "num_jobs_per_epoch=2", "report_interval=0",
                  "hash_capacity=65536", "V_dim=8", "slot_dtype=fp32",
                  # WAL forces device_cache_mb=0; pin it off in the
                  # baseline too so overhead compares identical programs
                  "device_cache_mb=0"]
        # untimed warmup leg: the first run pays JIT compile for the
        # fused step; timing it would swamp the <=5% WAL overhead target
        difacto_main(common + [f"model_out={os.path.join(td, 'warm')}"])
        t0 = time.perf_counter()
        difacto_main(common + [f"model_out={os.path.join(td, 'base')}"])
        base_s = time.perf_counter() - t0
        model = os.path.join(td, "wal")
        t0 = time.perf_counter()
        difacto_main(common + [f"model_out={model}", "ckpt_interval=1",
                               "auto_resume=1",
                               f"wal_flush_batches={flush}"])
        wal_s = time.perf_counter() - t0

        # simulated mid-window crash inside the LAST epoch: the epoch's
        # checkpoint and the final model never landed (deleted), and the
        # newest delta window died with the process (newest real segment
        # dropped) — the fresh learner must climb checkpoint(epoch-1) +
        # WAL replay of the surviving verified prefix
        import glob as _glob
        import re as _re
        epochs = sorted({int(m.group(1))
                         for f in _glob.glob(model + "_iter-*")
                         for m in [_re.search(r"_iter-(\d+)_", f)] if m})
        for f in (_glob.glob(model + f"_iter-{epochs[-1]}_*")
                  + _glob.glob(model + "_part-*")
                  + _glob.glob(model + ".meta*")):
            os.remove(f)
        wdir = _wal.wal_dir(model)
        gen = _wal.chain_generations(wdir, 0)[0]
        chain = _wal.chain_segments(wdir, 0, gen)
        head_full, dropped = 0, 0
        for seq, seg in reversed(chain):
            meta, _ = _wal.read_segment(seg)
            head_full = max(head_full, int(meta["step_hi"]))
            os.remove(seg)
            dropped += 1
            if meta["step_hi"] > meta["step_lo"]:
                break
        ln = SGDLearner()
        ln.init([tuple(kv.split("=", 1)) for kv in common]
                + [("model_out", model), ("ckpt_interval", "1"),
                   ("auto_resume", "1"),
                   ("wal_flush_batches", str(flush))])
        t0 = time.perf_counter()
        ln._try_resume()
        recovery_s = time.perf_counter() - t0
        ln.stop()
        with open(model + ".recovery.json") as f:
            stamp = json.load(f)
        head_after = int(stamp["head"]["step"])
    return {
        "wal_overhead_pct": round(100.0 * (wal_s - base_s)
                                  / max(base_s, 1e-9), 2),
        "recovery_s": round(recovery_s, 3),
        "rpo_batches": head_full - head_after,
        "wal_flush_batches": flush,
        "segments_dropped": dropped,
        "recovery_rungs": stamp["rungs"],
        "baseline_s": round(base_s, 3),
        "wal_s": round(wal_s, 3),
    }


def run_multichip(args) -> dict:
    """multichip.* section: the capacity-scaling trajectory of the
    fs-sharded slot table (difacto_tpu/parallel/capacity.py) — for each
    fs rung the table is ``--capacity * fs`` rows over fs devices, so
    the legs show max trainable hash_capacity growing with the mesh at
    ~constant per-device bytes while ex/s reports the collective cost.
    __graft_entry__.dryrun_multichip prints the same metric at small
    shapes; this leg is the full-size version.

    The ``delay`` block rides along: bounded-delay (τ) pipelining legs
    at hosts x {1,2,4} simulated straggler timelines x τ (--delay-taus,
    default {0,1,4}) over the same fused fs-sharded step — {hosts, tau,
    ex/s} plus the delay-vs-AUC trajectory leg (auc_delta vs τ=0), each
    leg carrying its compiled hlo.{table_collectives, peak_temp_bytes}
    scan (difacto_tpu/parallel/capacity.bounded_delay_report)."""
    from difacto_tpu.parallel.capacity import (bounded_delay_report,
                                               capacity_scaling_report)

    rep = capacity_scaling_report(
        base_capacity=args.multichip_capacity,
        V_dim=args.vdim, batch=args.batch_size,
        nnz_per_row=args.nnz_per_row, steps=args.steps,
        v_dtype=args.vdtype)
    rep["delay"] = bounded_delay_report(
        hosts_values=(1, 2, 4),
        taus=tuple(int(t) for t in args.delay_taus.split(",")),
        # the widest rung the capacity sweep above just ran (and
        # printed) on the devices this process has
        fs=max(leg["fs"] for leg in rep["legs"]),
        base_capacity=args.multichip_capacity,
        V_dim=args.vdim, batch=args.batch_size,
        nnz_per_row=args.nnz_per_row, steps=max(args.steps, 6),
        v_dtype=args.vdtype)
    return rep


def _gen_capacity_libsvm(path: str, nrows: int, nfeat: int, alpha: float,
                         seed: int, w: np.ndarray) -> None:
    """Synthetic planted-model libsvm rows: zipf(alpha)-ranked feature
    ids, labels drawn from the logistic of the planted weights — so a
    config's validation AUC measures how much signal its table kept."""
    rng = np.random.RandomState(seed)
    nnz = 8
    ranks = (rng.zipf(alpha, (nrows, nnz)) - 1) % nfeat
    with open(path, "w") as f:
        for r in ranks:
            ids = np.unique(r)
            p = 1.0 / (1.0 + np.exp(-w[ids].sum()))
            y = 1 if rng.random_sample() < p else 0
            f.write(f"{y} " + " ".join(f"{i}:1" for i in ids) + "\n")


def run_capacity_bench(args) -> dict:
    """``--capacity`` (bare) mode — the quality-vs-capacity story of the
    three table-capacity levers (ISSUE 19):

      quality : train the same planted-model data at equal-ish per-device
                byte budgets: fp32 at the base capacity vs int8/fp8 legs
                at 2x/4x/8x the rows (the 8x leg stacks the cold tier on
                int8), each leg reporting validation AUC, its delta vs
                the fp32 baseline, and the store's own capacity_stats
                accounting (bytes/device, effective rows, multiplier);
      tier    : cold-tier hit rate across >= 2 zipf skews — the number
                that says whether a half-resident table serves the hot
                set from device rows.
    """
    import tempfile

    from difacto_tpu.learners import Learner
    from difacto_tpu.store.local import SlotStore
    from difacto_tpu.updaters.sgd_updater import SGDUpdaterParam

    base_cap = args.capacity_base
    vdim = 4
    nfeat = base_cap * 16
    rng = np.random.RandomState(7)
    w_true = rng.randn(nfeat) * 0.7

    def cap_stats(slot_dtype: str, cap: int, cold: int) -> dict:
        p, _ = SGDUpdaterParam.init_allow_unknown([
            ("V_dim", str(vdim)), ("hash_capacity", str(cap)),
            ("slot_dtype", slot_dtype), ("cold_tier_rows", str(cold))])
        return SlotStore(p).capacity_stats()

    with tempfile.TemporaryDirectory() as d:
        train_p, val_p = f"{d}/train.libsvm", f"{d}/val.libsvm"
        _gen_capacity_libsvm(train_p, 3000, nfeat, 1.3, 1, w_true)
        _gen_capacity_libsvm(val_p, 1500, nfeat, 1.3, 2, w_true)

        def train_auc(slot_dtype: str, cap: int, cold: int = 0) -> float:
            aucs = []
            learner = Learner.create("sgd")
            learner.init([
                ("data_in", train_p), ("data_val", val_p),
                ("data_format", "libsvm"), ("loss", "fm"),
                ("V_dim", str(vdim)), ("V_threshold", "0"),
                ("lr", "0.1"), ("l1", "1e-5"),
                ("batch_size", "256"), ("shuffle", "0"),
                ("max_num_epochs", "3"), ("num_jobs_per_epoch", "1"),
                ("report_interval", "0"), ("stop_rel_objv", "0"),
                ("stop_val_auc", "0"), ("device_cache_mb", "0"),
                ("hash_capacity", str(cap)),
                ("slot_dtype", slot_dtype),
                ("cold_tier_rows", str(cold))])
            learner.add_epoch_end_callback(
                lambda e, t, v: aucs.append(v.auc / max(v.nrows, 1.0)))
            learner.run()
            return aucs[-1]

        base_auc = train_auc("fp32", base_cap)
        base_stats = cap_stats("fp32", base_cap, 0)
        base_bytes = max(base_stats["table_bytes_per_device"], 1)
        legs = []
        for slot_dtype, mult, cold_frac in (("int8", 2, 0.0),
                                            ("int8", 4, 0.0),
                                            ("fp8", 4, 0.0),
                                            ("int8", 8, 0.5)):
            cap = base_cap * mult
            cold = int(cap * cold_frac)
            auc = train_auc(slot_dtype, cap, cold)
            stats = cap_stats(slot_dtype, cap, cold)
            legs.append({
                "slot_dtype": slot_dtype,
                "capacity_mult": mult,
                "cold_tier_rows": cold,
                "auc": round(auc, 5),
                "auc_delta_vs_fp32": round(auc - base_auc, 5),
                "bytes_ratio_vs_fp32": round(
                    stats["table_bytes_per_device"] / base_bytes, 3),
                "capacity_stats": stats,
            })

    # tier hit-rate across skews: stream zipf keys through a
    # half-resident table and read the tier's own counters
    def tier_hit_rate(alpha: float, cap: int = 4096,
                      steps: int = 50, batch: int = 512) -> dict:
        p, _ = SGDUpdaterParam.init_allow_unknown([
            ("V_dim", "4"), ("hash_capacity", str(cap)),
            ("cold_tier_rows", str(cap // 2))])
        store = SlotStore(p)
        krng = np.random.RandomState(int(alpha * 100))
        h0 = store.tier._hits.value()
        m0 = store.tier._misses.value()
        for _ in range(steps):
            keys = np.unique(
                ((krng.zipf(alpha, batch) - 1) % (cap * 4)).astype(np.int64))
            store.pull(keys)
        h = store.tier._hits.value() - h0
        m = store.tier._misses.value() - m0
        return {"zipf_alpha": alpha,
                "hit_rate": round(h / max(h + m, 1), 4),
                "hits": int(h), "misses": int(m)}

    tier_legs = [tier_hit_rate(a) for a in args.capacity_alphas]
    x8 = legs[-1]["capacity_stats"]
    return {
        "baseline": {"slot_dtype": "fp32", "auc": round(base_auc, 5),
                     "capacity_stats": base_stats},
        "quality_vs_capacity": legs,
        "tier_hit_rate": tier_legs,
        # the acceptance number: logical rows per device of the stacked
        # int8+tier leg over the fp32/no-tier rows the same per-device
        # bytes would hold
        "effective_rows_per_device": x8["effective_rows_per_device"],
        "capacity_multiplier_x8_leg": x8["capacity_multiplier"],
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch-size", type=int, default=65536)
    ap.add_argument("--vdim", type=int, default=64)
    ap.add_argument("--nnz-per-row", type=int, default=39)  # criteo density
    ap.add_argument("--uniq", type=int, default=1 << 17,
                    help="feature-id space each batch draws from")
    ap.add_argument("--capacity", nargs="?", const="bench",
                    default=1 << 21,
                    help="table rows when given a value; passed BARE it "
                         "selects the table-capacity bench instead "
                         "(quantized-slot AUC legs at 2x/4x/8x effective "
                         "capacity + cold-tier hit-rate across zipf "
                         "skews)")
    ap.add_argument("--capacity-base", type=int, default=1024,
                    help="fp32 baseline hash_capacity of the --capacity "
                         "bench quality legs")
    ap.add_argument("--capacity-alphas", default="1.1,1.6",
                    help="comma-separated zipf skews for the --capacity "
                         "bench tier hit-rate legs")
    ap.add_argument("--zipf-alpha", type=float, default=0.0,
                    help="serve-bench request skew: forwarded to the "
                         "loadgen row picker (tools/loadgen.py "
                         "make_picker); 0 keeps the round-robin cycle")
    ap.add_argument("--dist", choices=("zipf", "uniform"), default="zipf",
                    help="feature frequency skew (criteo is heavy-tailed)")
    ap.add_argument("--vdtype", choices=("float32", "bfloat16"),
                    default="bfloat16")
    ap.add_argument("--fused-kernel", default="auto",
                    choices=("auto", "pallas", "jnp", "off"),
                    help="table-kernel backend of the fused step "
                         "(updaters/sgd_updater.py fused_kernel): the "
                         "headline rides this; the kernel block times "
                         "every available backend regardless")
    ap.add_argument("--steps", type=int, default=40)
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--e2e", action="store_true",
                      help="full text->train pipeline ONLY (skip device "
                           "step)")
    mode.add_argument("--device-only", action="store_true",
                      help="device step only (skip the e2e pipeline run)")
    mode.add_argument("--serve", action="store_true",
                      help="online-serving latency/throughput ONLY: "
                           "in-process server + open-loop Poisson loadgen")
    mode.add_argument("--online", action="store_true",
                      help="serve→log→train→reload loop steady state "
                           "ONLY: in-process server + feedback loadgen "
                           "+ a task=online trainer subprocess")
    mode.add_argument("--multichip", action="store_true",
                      help="fs-sharded table capacity-scaling ONLY: "
                           "table of --multichip-capacity * fs rows per "
                           "fs rung in {1,2,4,8}, ex/s + per-device "
                           "bytes per leg")
    mode.add_argument("--durability", action="store_true",
                      help="WAL overhead + recovery cost ONLY: WAL-off "
                           "vs WAL-on wall clock, then a simulated "
                           "mid-window crash recovered through the "
                           "ladder (durability.{wal_overhead_pct, "
                           "recovery_s, rpo_batches})")
    ap.add_argument("--durability-flush", type=int, default=8,
                    help="wal_flush_batches for the --durability legs "
                         "(the RPO bound under test)")
    ap.add_argument("--delay-taus", default="0,1,4",
                    help="comma-separated bounded-delay windows for the "
                         "--multichip delay legs (τ batches of permitted "
                         "staleness; 0 = synchronous)")
    ap.add_argument("--multichip-capacity", type=int, default=1 << 20,
                    help="per-fs-rung base hash_capacity of the "
                         "--multichip sweep (table = base * fs rows)")
    ap.add_argument("--serve-qps", type=float, default=500.0,
                    help="target offered rate for the serve bench")
    ap.add_argument("--serve-seconds", type=float, default=5.0)
    ap.add_argument("--serve-vdim", type=int, default=8)
    ap.add_argument("--serve-capacity", type=int, default=1 << 16)
    ap.add_argument("--serve-batch", type=int, default=256)
    ap.add_argument("--serve-delay-ms", type=float, default=2.0)
    ap.add_argument("--serve-queue-cap", type=int, default=1024)
    ap.add_argument("--online-qps", type=float, default=200.0,
                    help="offered rate for the --online loop bench")
    ap.add_argument("--online-seconds", type=float, default=6.0)
    ap.add_argument("--online-segment-rows", type=int, default=64,
                    help="rows per sealed training-log segment")
    ap.add_argument("--online-label-rate", type=float, default=0.5,
                    help="fraction of served rows the feedback loadgen "
                         "labels back")
    ap.add_argument("--online-label-delay-s", type=float, default=0.5,
                    help="feedback-join horizon (labels go out at half)")
    ap.add_argument("--online-ckpt-s", type=float, default=1.0,
                    help="trainer generation commit cadence (wall s)")
    ap.add_argument("--e2e-rows", type=int, default=1_800_000,
                    help="rows in the e2e window; large enough that the "
                         "fixed epoch-boundary cost (the final metric "
                         "fetch) amortizes")
    ap.add_argument("--e2e-batch", type=int, default=65536,
                    help="training batch size for the e2e pipeline run")
    ap.add_argument("--producer-mode", default="auto",
                    choices=("auto", "thread", "process"),
                    help="streamed-regime producer transport: in-process "
                         "threads or spawn worker processes + shared-"
                         "memory ring (auto = process when >= 4 cores)")
    ap.add_argument("--profile", metavar="DIR", default="",
                    help="capture a device trace of the timed step window "
                         "into DIR (view with xprof/TensorBoard)")
    ap.add_argument("--mesh", metavar="DPxFS", default="",
                    help="run the SAME panel/chunked step as a sharded "
                         "program over a (dp, fs) jax.sharding.Mesh "
                         "(e.g. 1x1 on one chip proves the sharded "
                         "lowering keeps the flat-path rate; 2x4 on the "
                         "virtual CPU mesh checks multi-device)")
    args = ap.parse_args()
    # bare --capacity is the capacity-bench mode; with a value it stays
    # the table-rows knob every other mode reads
    capacity_mode = args.capacity == "bench"
    args.capacity = (1 << 21) if capacity_mode else int(args.capacity)
    args.capacity_alphas = tuple(
        float(a) for a in str(args.capacity_alphas).split(",") if a)

    # before the first backend touch
    from difacto_tpu.utils.device import place_compile_cache
    place_compile_cache()

    if capacity_mode:
        emit({"capacity": run_capacity_bench(args)})
        return
    if args.e2e:
        emit(run_e2e(args))
        return
    if args.serve:
        emit({"serve": run_serve_bench(args)})
        return
    if args.online:
        emit({"online": run_online_bench(args)})
        return
    if args.multichip:
        emit({"multichip": run_multichip(args)})
        return
    if args.durability:
        emit({"durability": run_durability_bench(args)})
        return

    import jax
    import jax.numpy as jnp

    mesh = None
    if args.mesh:
        from difacto_tpu.parallel import make_mesh
        dp, fs = (int(v) for v in args.mesh.lower().split("x"))
        mesh = make_mesh(dp=dp, fs=fs)

    step_raw, state, _, _, _ = build_step(
        args.vdim, args.capacity, args.vdtype,
        chunks_sorted=mesh is None or mesh.shape["dp"] == 1,
        fused_kernel=args.fused_kernel if mesh is None else
        ("jnp" if args.fused_kernel == "pallas" else args.fused_kernel),
        mesh=mesh)
    host_batches = make_batches(4, args.batch_size, args.nnz_per_row,
                                args.uniq, args.capacity, args.dist,
                                chunk_multiple=(mesh.shape["dp"]
                                                if mesh else 1))

    # per-step dispatch with a DONATED state — the production replay
    # pattern (learners/sgd.py replays cached batches one jitted call per
    # step). A lax.scan harness measures the same body ~6% slower: XLA
    # inserts carry copies for the gather-then-scatter table inside a
    # while loop, a cost the product never pays. JAX async dispatch
    # pipelines the per-call host cost, so the chained wall time is
    # device execution; the final value fetch is the completion fence.
    step = jax.jit(step_raw, donate_argnums=0)
    if mesh is not None:
        from difacto_tpu.parallel import (batch_sharding, replicated,
                                          shard_pytree, state_sharding)
        state = shard_pytree(state, state_sharding(mesh))
        batches = [shard_pytree(b, batch_sharding(mesh))
                   for b, _ in host_batches]
        slots_l = [jax.device_put(np.asarray(s), replicated(mesh))
                   for _, s in host_batches]
    else:
        batches = [jax.device_put(b) for b, _ in host_batches]
        slots_l = [jnp.asarray(s) for _, s in host_batches]
    n_bk = len(host_batches)
    u_cap = slots_l[0].shape[0]

    # warmup / compile (fetch forces completion; jaxtrace declares the
    # sync so the jax-host-sync pass knows it is the harness fence)
    from difacto_tpu.utils import jaxtrace
    state, objv, _ = step(state, batches[0], slots_l[0])
    jaxtrace.fetch(objv, point="bench.fence")

    import contextlib

    # jax's own: a trace that was asked for and cannot start raises
    trace = (jax.profiler.trace(args.profile) if args.profile
             else contextlib.nullcontext())
    with trace:
        t0 = time.perf_counter()
        for i in range(args.steps):
            state, objv, _ = step(state, batches[i % n_bk], slots_l[i % n_bk])
        jaxtrace.fetch(objv, point="bench.fence")
        dt = time.perf_counter() - t0

    eps = args.steps * args.batch_size / dt
    v_bytes = 2 if args.vdtype == "bfloat16" else 4
    out = {
        "metric": ("fm_v64_train_examples_per_sec" if mesh is None else
                   f"fm_v64_mesh{args.mesh}_train_examples_per_sec"),
        "value": round(eps, 1),
        "unit": "examples/sec",
        "vs_baseline": round(eps / REF_PSLITE_32W_EPS, 3),
        "baseline": "estimated 5e5 ex/s (32-worker ps-lite CPU; the "
                    "reference publishes no numbers)",
        "config": {"batch": args.batch_size, "V_dim": args.vdim,
                   "dist": args.dist, "V_dtype": args.vdtype,
                   "uniq_rows_per_step": u_cap},
        "roofline": roofline(args.batch_size * args.nnz_per_row, u_cap,
                             args.vdim, v_bytes, dt / args.steps,
                             vvg_cols=int(state.VVg.shape[1])),
    }
    if mesh is None and args.vdim > 0:
        # per-backend roofline attribution of the fused step (ISSUE 13):
        # every available fused_kernel backend full-step timed, plus the
        # dedup/gather/interaction/scatter leg split
        out["kernel"] = run_kernel_bench(args, host_batches,
                                         args.nnz_per_row)
    if not args.device_only and mesh is None:
        # the product number rides the default output so a pipeline
        # regression is driver-visible (round-3 verdict #10)
        out["e2e"] = run_e2e(args)
    emit(out)


if __name__ == "__main__":
    main()
