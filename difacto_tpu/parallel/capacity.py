"""Capacity-scaling measurement for the fs-sharded slot table.

The point of key-range sharding the table (mesh.py fs axis; the
reference's KVStoreDist server sharding) is CAPACITY: an fs-way mesh
holds an fs-times-larger table at the same per-device HBM. This module
is the one measurement of that claim, driven by the driver's
``__graft_entry__.dryrun_multichip`` leg and ``tools/hlomap.py`` — for each
``fs`` rung it builds a table of ``base_capacity * fs`` rows sharded
over ``fs`` devices, runs the SAME fused train step the product
dispatches (panel + chunked backward at dp=1), and reports throughput
next to per-device table bytes — a real scaling trajectory instead of a
bare {rc, ok}.

``scaling``: per-device bytes should stay ~flat while max trainable
capacity grows linearly — ``capacity_scaling`` is exact by construction
(cap_fs / cap_1); ``throughput_retention`` (ex/s at fs vs fs=1) is the
honest cost figure, since the gather/scatter turns into cross-shard
collectives.
"""

from __future__ import annotations

import time
from typing import Optional, Sequence


def capacity_scaling_report(fs_values: Optional[Sequence[int]] = None,
                            base_capacity: int = 1 << 12,
                            V_dim: int = 8, batch: int = 1024,
                            nnz_per_row: int = 8, steps: int = 4,
                            v_dtype: str = "float32",
                            slot_dtype: str = "fp32") -> dict:
    """One leg per fs rung: {fs, hash_capacity, table_bytes_per_device,
    examples_per_sec} plus the cross-rung scaling summary. Rungs that
    exceed the visible device count are skipped (reported in
    ``skipped_fs``), so the same call works on an 8-chip host and a
    1-device CPU host."""
    import jax
    import numpy as np

    from ..updaters.sgd_updater import (SGDUpdaterParam, init_state,
                                        make_fns, set_all_live, state_bytes)
    from ..losses import create as create_loss
    from ..step import make_step_fns, state_constrainer
    from ..store.local import pad_slots_oob
    from ..utils import jaxtrace
    from . import (make_mesh, replicated, shard_pytree, sharding_tree,
                   state_sharding)

    n_dev = len(jax.devices())
    if fs_values is None:
        fs_values = [f for f in (1, 2, 4, 8) if f <= n_dev]
    legs = []
    skipped = [f for f in fs_values if f > n_dev]
    rng = np.random.RandomState(0)
    for fs in fs_values:
        if fs > n_dev:
            continue
        cap = base_capacity * fs
        param = SGDUpdaterParam(V_dim=V_dim, V_threshold=0, lr=0.1,
                                l1=1e-4, l2=1e-4, V_dtype=v_dtype,
                                hash_capacity=cap, slot_dtype=slot_dtype)
        mesh = make_mesh(dp=1, fs=fs)
        fns = make_fns(param, mesh)
        loss = create_loss("fm", V_dim)
        state = init_state(param, cap)
        if V_dim:
            state = set_all_live(param, state)
        shardings = sharding_tree(state, state_sharding(mesh))
        state = shard_pytree(state, state_sharding(mesh))
        _, train_step, _ = make_step_fns(fns, loss,
                                         state_shardings=shardings)
        # the per-leg compile is intentional: one program per fs rung
        # lint: ok(jax-recompile) one bounded compile per fs rung of the
        # capacity sweep — the loop IS the benchmark matrix
        step = jaxtrace.pjit(train_step, donate_argnums=0)

        # synthetic localized batch: uniform draws over the table
        u_cap = min(cap // 2, max(64, batch * nnz_per_row // 4))
        uniq = np.sort(rng.permutation(cap - 1)[:u_cap] + 1)
        slots = jax.device_put(
            pad_slots_oob(uniq.astype(np.int32), u_cap, cap),
            replicated(mesh))
        from ..data.rowblock import RowBlock
        from ..ops.batch import pad_batch
        idx = rng.randint(0, u_cap, batch * nnz_per_row).astype(np.uint32)
        blk = RowBlock(
            offset=np.arange(batch + 1, dtype=np.int64) * nnz_per_row,
            label=rng.choice([0.0, 1.0], batch).astype(np.float32),
            index=idx, value=None)
        dev = pad_batch(blk, num_uniq=u_cap, batch_cap=batch,
                        nnz_cap=batch * nnz_per_row)
        dev = shard_pytree(dev, lambda x: replicated(mesh))

        # layout-cleanliness proof for the MULTICHIP metric: scan the
        # leg's compiled HLO (utils/hloscan.py) BEFORE the donating
        # warm call — zero table-axis collectives is what makes the
        # throughput numbers mean "sharded", not "secretly gathered"
        from ..utils import hloscan
        leg_hlo = None
        try:
            compiled = step.lower(state, dev, slots).compile()
            one = hloscan.scan_compiled(compiled, rows=cap,
                                        label="train_step")
            hloscan.record(
                getattr(step, "site", "difacto_tpu/parallel/capacity.py"),
                compiled, label="train_step", rows=cap)
            leg_hlo = {
                "table_collectives": one["table_collectives"],
                "peak_temp_bytes": one["peak_temp_bytes"],
            }
        except Exception as e:   # the sweep must survive a scan failure
            import logging
            logging.getLogger("difacto_tpu").warning(
                "capacity: hlo scan of the fs=%d leg failed: %s", fs, e)
            leg_hlo = None

        state, objv, _ = step(state, dev, slots)           # compile
        jaxtrace.fetch(objv, point="capacity.fence")
        t0 = time.perf_counter()
        for _ in range(steps):
            state, objv, _ = step(state, dev, slots)
        jaxtrace.fetch(objv, point="capacity.fence")
        dt = time.perf_counter() - t0
        total = state_bytes(param, cap)
        leg = {
            "fs": fs,
            "hash_capacity": cap,
            "table_bytes_total": int(total),
            "table_bytes_per_device": int(total // fs),
            "examples_per_sec": round(steps * batch / dt, 1),
            "step_ms": round(dt / steps * 1e3, 3),
        }
        if leg_hlo is not None:
            leg["hlo"] = leg_hlo
        legs.append(leg)
        del state
    out = {
        "metric": "multichip_capacity_scaling",
        "n_devices": n_dev,
        "config": {"base_capacity": base_capacity, "V_dim": V_dim,
                   "batch": batch, "nnz_per_row": nnz_per_row,
                   "steps": steps, "V_dtype": v_dtype},
        "legs": legs,
        "skipped_fs": skipped,
    }
    if legs:
        base = legs[0]
        peak = legs[-1]
        out["max_hash_capacity"] = peak["hash_capacity"]
        out["capacity_scaling"] = round(
            peak["hash_capacity"] / base["hash_capacity"], 3)
        out["throughput_retention"] = round(
            peak["examples_per_sec"] / max(base["examples_per_sec"], 1e-9),
            3)
        # near-linear capacity scaling at bounded per-device bytes is
        # the acceptance claim: efficiency 1.0 = fs x capacity at
        # constant per-device residency
        out["scaling_efficiency"] = round(
            (peak["hash_capacity"] / base["hash_capacity"])
            / max(peak["fs"] / base["fs"], 1e-9), 3)
    return out


def bounded_delay_report(hosts_values: Sequence[int] = (1, 2, 4),
                         taus: Sequence[int] = (0, 1, 4),
                         fs: int = 4, base_capacity: int = 1 << 12,
                         V_dim: int = 8, batch: int = 1024,
                         nnz_per_row: int = 8, steps: int = 8,
                         v_dtype: str = "float32",
                         straggle_factor: float = 1.5,
                         auc_legs: bool = True,
                         seed: int = 0) -> dict:
    """Bounded-delay (τ) pipelining legs.

    One REAL fs-sharded fused train step (the same compiled program as
    the capacity sweep) is driven through the real windowed pipeline
    (data/prefetch.prefetch at depth 2+τ) against SIMULATED peer
    clocks: for each ``hosts`` rung a deterministic straggler timeline
    ``peer_done[t]`` (slowest of hosts-1 jittered peers, cumulative) is
    drawn once per rung — the SAME timeline for every τ — and the
    exchange stage sleeps until ``peer_done[s-τ-1]`` before staging
    step ``s``, exactly the wait_clock contract of the live schedule
    (learners/sgd.py _iterate_data_spmd). Because a larger τ waits on a
    strictly earlier (hence never later) peer clock against one fixed
    timeline, ex/s is monotonically non-decreasing in τ by
    construction, and the measured gap IS the synchronization time the
    window hides.

    ``auc_legs`` adds the delay-vs-AUC trajectory leg: short REAL
    trainings on synthetic data through the windowed schedule at each
    τ, reporting ``auc_delta`` vs the τ=0 run — honest support for the
    τ-invariance claim (device steps stay collective-synchronous, so
    the trajectory does not move with τ).
    """
    import jax
    import numpy as np

    from ..updaters.sgd_updater import (SGDUpdaterParam, init_state,
                                        make_fns, set_all_live)
    from ..losses import create as create_loss
    from ..step import make_step_fns
    from ..store.local import pad_slots_oob
    from ..utils import hloscan, jaxtrace
    from ..data.prefetch import prefetch
    from . import (make_mesh, replicated, shard_pytree, sharding_tree,
                   state_sharding)

    n_dev = len(jax.devices())
    if fs > n_dev:
        raise ValueError(
            f"bounded_delay_report: fs={fs} needs {fs} devices, this "
            f"process sees {n_dev} — a smaller mesh would be a different "
            "measurement under the same name")
    cap = base_capacity * fs
    rng = np.random.RandomState(seed)
    param = SGDUpdaterParam(V_dim=V_dim, V_threshold=0, lr=0.1,
                            l1=1e-4, l2=1e-4, V_dtype=v_dtype,
                            hash_capacity=cap)
    mesh = make_mesh(dp=1, fs=fs)
    fns = make_fns(param, mesh)
    loss = create_loss("fm", V_dim)
    state = init_state(param, cap)
    if V_dim:
        state = set_all_live(param, state)
    shardings = sharding_tree(state, state_sharding(mesh))
    state = shard_pytree(state, state_sharding(mesh))
    _, train_step, _ = make_step_fns(fns, loss, state_shardings=shardings)
    # lint: ok(jax-recompile) one bounded compile for the whole delay
    # sweep — every (hosts, τ) leg drives the SAME program
    step = jaxtrace.pjit(train_step, donate_argnums=0)

    u_cap = min(cap // 2, max(64, batch * nnz_per_row // 4))
    uniq = np.sort(rng.permutation(cap - 1)[:u_cap] + 1)
    slots = jax.device_put(
        pad_slots_oob(uniq.astype(np.int32), u_cap, cap),
        replicated(mesh))
    from ..data.rowblock import RowBlock
    from ..ops.batch import pad_batch
    idx = rng.randint(0, u_cap, batch * nnz_per_row).astype(np.uint32)
    blk = RowBlock(
        offset=np.arange(batch + 1, dtype=np.int64) * nnz_per_row,
        label=rng.choice([0.0, 1.0], batch).astype(np.float32),
        index=idx, value=None)
    dev = pad_batch(blk, num_uniq=u_cap, batch_cap=batch,
                    nnz_cap=batch * nnz_per_row)
    dev = shard_pytree(dev, lambda x: replicated(mesh))

    hlo = None
    try:
        compiled = step.lower(state, dev, slots).compile()
        one = hloscan.scan_compiled(compiled, rows=cap,
                                    label="train_step_delay")
        hlo = {"table_collectives": one["table_collectives"],
               "peak_temp_bytes": one["peak_temp_bytes"]}
        for tau in taus:
            # per-τ record under a colon-free site: hlomap.build treats
            # it as a non-pjit measurement label, not an unknown site
            hloscan.record(f"capacity.delay/tau{tau}", compiled,
                           label=f"train_step_tau{tau}", rows=cap)
    except Exception as e:   # the sweep must survive a scan failure
        import logging
        logging.getLogger("difacto_tpu").warning(
            "bounded_delay: hlo scan failed: %s", e)

    # warm + base step time (feeds the simulated peer timelines)
    state, objv, _ = step(state, dev, slots)
    jaxtrace.fetch(objv, point="capacity.fence")
    t0 = time.perf_counter()
    for _ in range(max(2, steps // 2)):
        state, objv, _ = step(state, dev, slots)
    jaxtrace.fetch(objv, point="capacity.fence")
    step_s = (time.perf_counter() - t0) / max(2, steps // 2)

    legs = []
    for hosts in hosts_values:
        # one straggler timeline per hosts rung, REUSED across every τ
        # (fresh deterministic seed => identical peer clocks), so the
        # τ column of the matrix measures only the window, never luck
        lrng = np.random.RandomState(seed * 1000 + hosts)
        if hosts > 1:
            jit = lrng.uniform(0.0, straggle_factor * step_s,
                               size=(steps, hosts - 1)).max(axis=1)
        else:
            jit = np.zeros(steps)
        peer_done = np.cumsum(step_s + jit)
        for tau in taus:
            def exchange_sim(peer_done=peer_done, tau=tau, hosts=hosts):
                start = time.perf_counter()
                for s in range(steps):
                    need = s - tau - 1
                    if hosts > 1 and need >= 0:
                        # the wait_clock contract: block until the
                        # slowest peer has dispatched step s-τ-1
                        rem = peer_done[need] - (time.perf_counter()
                                                 - start)
                        if rem > 0:
                            time.sleep(rem)
                    yield s
                # epoch-end barrier: the part drain always joins the
                # slowest peer's LAST step, window or not
                if hosts > 1:
                    rem = peer_done[steps - 1] - (time.perf_counter()
                                                  - start)
                    if rem > 0:
                        time.sleep(rem)

            t0 = time.perf_counter()
            for _ in prefetch(exchange_sim(), depth=2 + tau):
                state, objv, _ = step(state, dev, slots)
            jaxtrace.fetch(objv, point="capacity.fence")
            dt = time.perf_counter() - t0
            leg = {
                "hosts": hosts,
                "tau": tau,
                "examples_per_sec": round(steps * batch / dt, 1),
                "step_ms": round(dt / steps * 1e3, 3),
            }
            if hlo is not None:
                leg["hlo"] = hlo
            legs.append(leg)
    del state

    out = {
        "metric": "bounded_delay_pipelining",
        "n_devices": n_dev,
        "config": {"fs": fs, "base_capacity": base_capacity,
                   "V_dim": V_dim, "batch": batch,
                   "nnz_per_row": nnz_per_row, "steps": steps,
                   "V_dtype": v_dtype, "straggle_factor": straggle_factor,
                   "seed": seed},
        "base_step_ms": round(step_s * 1e3, 3),
        "legs": legs,
    }
    # scaling retention per τ: the slowest rung's ex/s over the mean
    # single-host ex/s (one common denominator, so the τ column inherits
    # the sleep-until monotonicity instead of hosts=1 timing noise) —
    # this is the acceptance figure: retention improves with τ because
    # the window hides the stragglers' sync time
    h1 = [leg for leg in legs if leg["hosts"] == 1]
    hm = [leg for leg in legs if leg["hosts"] == max(hosts_values)]
    if h1 and hm and max(hosts_values) > 1:
        base1 = sum(leg["examples_per_sec"] for leg in h1) / len(h1)
        out["retention_by_tau"] = {
            str(leg["tau"]): round(leg["examples_per_sec"]
                                   / max(base1, 1e-9), 4)
            for leg in hm}
    if auc_legs:
        out["auc"] = _delay_auc_legs(taus, fs, n_dev)
    return out


def _delay_auc_legs(taus: Sequence[int], fs: int, n_dev: int) -> list:
    """Delay-vs-AUC trajectory: short REAL trainings on synthetic data
    at each τ through the windowed schedule; ``auc_delta`` vs τ=0 backs
    the trajectory-invariance claim with measurement (expected ~0 —
    bounded delay moves wait time, not gradients)."""
    import tempfile

    import numpy as np

    dp = 2 if 2 * fs <= n_dev else 1
    if dp * fs > n_dev:
        return [{"skipped": f"needs {dp * fs} devices, have {n_dev}"}]
    rng = np.random.RandomState(7)
    rows, feats = 400, 1 << 12
    with tempfile.NamedTemporaryFile("w", suffix=".libsvm",
                                     delete=False) as f:
        w = rng.randn(64)
        for _ in range(rows):
            ks = np.sort(rng.choice(feats, rng.randint(4, 16),
                                    replace=False))
            y = 1 if w[ks % 64].sum() > 0 else 0
            f.write(str(y) + " "
                    + " ".join(f"{k}:1" for k in ks) + "\n")
        path = f.name

    def train(tau: int) -> float:
        from ..learners import Learner
        conf = {"data_in": path, "V_dim": "2", "V_threshold": "1",
                "lr": "0.1", "l1": "1e-4", "l2": "1e-4",
                "batch_size": "100", "max_num_epochs": "2",
                "shuffle": "0", "report_interval": "0",
                "stop_rel_objv": "0", "stop_val_auc": "-2",
                "num_jobs_per_epoch": "1", "hash_capacity": str(1 << 16),
                "mesh_dp": str(dp), "mesh_fs": str(fs),
                "bounded_delay": str(tau)}
        ln = Learner.create("sgd")
        ln.init(list(conf.items()))
        aucs: list = []
        ln.add_epoch_end_callback(
            lambda e, t, v: aucs.append(t.auc / max(t.nrows, 1.0)))
        ln.run()
        return float(aucs[-1])

    base = None
    legs = []
    try:
        for tau in sorted(set([0, *taus])):
            auc = train(tau)
            if tau == 0:
                base = auc
            legs.append({"tau": tau, "auc": round(auc, 6),
                         "auc_delta": round(auc - base, 6)})
    finally:
        import os as _os
        _os.unlink(path)
    return legs
