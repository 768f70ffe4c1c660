"""Device-resident batch replay cache (learners/sgd.py _DeviceBatchCache).

The cache stages each packed batch once and replays it from device
memory, so later epochs pay no host pack and no transfer. These tests pin
its contract: exact replay equivalence with shuffle off, correct gating
(neg_sampling, dictionary store), budget fallback, and permutation-only
shuffle on replay.
"""

import numpy as np
import pytest

from difacto_tpu.learners import Learner
from difacto_tpu.learners.sgd import (K_TRAINING, K_VALIDATION,
                                      _DeviceBatchCache)


def run_hashed(rcv1_path, epochs=6, setup=None, **over):
    """``setup(learner)`` runs between init and run — e.g. to pre-seed a
    byte-budget cache."""
    args = [("data_in", rcv1_path), ("data_format", "libsvm"),
            ("loss", "fm"), ("V_dim", "2"), ("V_threshold", "0"),
            ("lr", "0.1"), ("l1", "0.1"), ("l2", "0"),
            ("batch_size", "25"), ("shuffle", "0"),
            ("max_num_epochs", str(epochs)), ("num_jobs_per_epoch", "1"),
            ("report_interval", "0"), ("stop_rel_objv", "0"),
            ("hash_capacity", str(1 << 14))]
    args += [(k, str(v)) for k, v in over.items()]
    learner = Learner.create("sgd")
    remain = learner.init(args)
    assert remain == []
    if setup is not None:
        setup(learner)
    seen = []
    learner.add_epoch_end_callback(lambda e, t, v: seen.append(t.loss))
    learner.run()
    return np.array(seen), learner


def test_replay_identical_no_shuffle(rcv1_path):
    """Replayed epochs reproduce the streamed trajectory exactly (shuffle
    off => identical batches in identical order), and the cache actually
    engaged (ready after epoch 0, entries staged)."""
    ref, _ = run_hashed(rcv1_path, device_cache_mb=0)
    got, learner = run_hashed(rcv1_path, device_cache_mb=256)
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-6)
    cache = learner._dev_caches[K_TRAINING]
    assert cache.ready and cache.alive
    assert sum(len(v) for v in cache.entries.values()) == 4  # 100 rows / 25


def test_replay_counts_pushed_once(rcv1_path):
    """The epoch-0 feature-count push must not repeat on replay: final
    cnt equals one epoch's occurrence counts either way."""
    _, base = run_hashed(rcv1_path, device_cache_mb=0, epochs=3)
    _, cached = run_hashed(rcv1_path, device_cache_mb=256, epochs=3)
    from difacto_tpu.updaters.sgd_updater import scal_cols
    np.testing.assert_allclose(
        np.asarray(scal_cols(cached.store.param, cached.store.state)[3]),
        np.asarray(scal_cols(base.store.param, base.store.state)[3]))


def test_validation_replay(rcv1_path):
    """data_val epochs ride the cache too and stay correct (loss is a pure
    function of the model, so cached vs streamed val loss is identical)."""
    ref, _ = run_hashed(rcv1_path, device_cache_mb=0, data_val=rcv1_path)
    got, learner = run_hashed(rcv1_path, device_cache_mb=256,
                              data_val=rcv1_path)
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-6)
    assert learner._dev_caches[K_VALIDATION].ready


def test_neg_sampling_disables_cache(rcv1_path):
    """neg_sampling < 1 must resample every epoch — no train cache."""
    _, learner = run_hashed(rcv1_path, neg_sampling=0.9, epochs=2)
    assert learner._get_cache(K_TRAINING) is None


def run_dict(rcv1_path, epochs=6, extra_callback=None, **over):
    """Dictionary-store (no hash_capacity) run."""
    args = [("data_in", rcv1_path), ("data_format", "libsvm"),
            ("loss", "logit"), ("lr", "1"), ("l1", "1"), ("l2", "1"),
            ("batch_size", "25"), ("shuffle", "0"),
            ("max_num_epochs", str(epochs)), ("num_jobs_per_epoch", "1"),
            ("report_interval", "0"), ("stop_rel_objv", "0")]
    args += [(k, str(v)) for k, v in over.items()]
    learner = Learner.create("sgd")
    learner.init(args)
    seen = []
    learner.add_epoch_end_callback(lambda e, t, v: seen.append(t.loss))
    if extra_callback is not None:
        learner.add_epoch_end_callback(
            lambda e, t, v: extra_callback(learner, e))
    learner.run()
    return np.array(seen), learner


def test_dictionary_store_caches_first_pass_with_repad(rcv1_path):
    """The single-host dictionary store stages on its FIRST pass even
    though the table grows mid-epoch (slot assignment is
    insertion-stable; the replay entry repads the staged OOB slot tails
    to the final capacity — round-5, replacing the second-pass staging
    that paid a whole extra streamed epoch). Replayed epochs 1+
    reproduce the streamed trajectory exactly."""
    ref, _ = run_dict(rcv1_path, device_cache_mb=0, init_capacity=64)
    got, learner = run_dict(rcv1_path, device_cache_mb=256,
                            init_capacity=64)
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-6)
    cache = learner._dev_caches[K_TRAINING]
    assert cache.ready and cache.stage_after_pass == 0 and cache.repadable
    # init_capacity=64 forces growth DURING the staging pass, so the
    # repad path really ran (stale flag set then cleared at replay)
    assert cache.capacity == learner.store.state.capacity
    assert not cache.stale_pads
    assert sum(len(v) for v in cache.entries.values()) == 4  # 100/25


def test_dictionary_cache_repads_on_capacity_growth(rcv1_path):
    """A capacity change after staging (impossible for fixed data, but
    the guard covers it) repads the staged OOB slot tails instead of
    throwing the cache away — stale pads would fall back in bounds and
    alias real rows; the trajectory must be unchanged either way."""
    ref, _ = run_dict(rcv1_path, device_cache_mb=0, epochs=5)

    def grow_after_epoch(learner, e):
        if e == 3:
            # simulate post-staging growth
            from difacto_tpu.updaters.sgd_updater import grow_state
            learner.store.state = grow_state(
                learner.store.param, learner.store.state,
                learner.store.state.capacity * 2)

    seen, learner = run_dict(rcv1_path, device_cache_mb=256, epochs=5,
                             extra_callback=grow_after_epoch)
    cache = learner._dev_caches[K_TRAINING]
    assert cache.alive and cache.ready  # repadded, NOT invalidated
    assert cache.capacity == learner.store.state.capacity
    np.testing.assert_allclose(seen, ref, rtol=1e-6, atol=1e-6)


def test_shuffle_replay_permutes_batches(rcv1_path):
    """With shuffle on, replayed epochs permute the cached batches — the
    trajectory differs from the unshuffled one but uses the same rows, so
    both converge on the same data (epoch-0 loss identical: the first
    epoch streams through the same shuffle-buffer reader either way)."""
    ref, _ = run_hashed(rcv1_path, device_cache_mb=0, shuffle=10)
    got, learner = run_hashed(rcv1_path, device_cache_mb=256, shuffle=10)
    assert learner._dev_caches[K_TRAINING].ready
    np.testing.assert_allclose(got[0], ref[0], rtol=1e-6)


def test_cache_budget_overflow_keeps_prefix():
    """Budget overflow keeps the fully-staged part prefix and freezes
    staging (round-4 verdict weak #3: all-or-nothing made a dataset 1.1x
    the budget train ~6x slower than one 0.9x it); the half-staged part
    is dropped and its bytes refunded."""
    c = _DeviceBatchCache(1)  # 1 MB
    c.add(0, "a", 300 << 10)
    c.add(0, "b", 300 << 10)
    c.add(1, "c", 300 << 10)
    assert c.alive and not c.frozen
    c.add(1, "d", 300 << 10)  # would exceed 1 MB: freeze, drop part 1
    assert c.frozen and c.partial
    assert c.parts() == {0} and len(c.entries[0]) == 2
    assert c.used == 600 << 10 and c.shared["used"] == 600 << 10
    c.add(2, "e", 8)          # frozen: no further staging
    assert c.parts() == {0}
    c.finish_pass()
    assert c.ready and c.alive  # the prefix replays; the rest streams
    assert list(c.iter_parts(False, seed=0)) == [(0, "a"), (0, "b")]


def test_cache_budget_overflow_nothing_fits():
    """When not even the first part fits, the cache dies outright and
    every epoch streams."""
    c = _DeviceBatchCache(1)
    c.add(0, "a", 2 << 20)
    assert c.frozen and not c.partial and not c.entries
    c.finish_pass()
    assert not c.ready and not c.alive


def test_partial_cache_mixed_regime_trajectory(rcv1_path):
    """A dataset ~2x the budget: the staged prefix replays, the rest
    streams, and the trajectory equals pure streaming exactly (shuffle
    off). Budget is tuned from a full-cache probe run so the test tracks
    payload-size changes."""
    probe, learner = run_hashed(rcv1_path, device_cache_mb=256, epochs=2,
                                num_jobs_per_epoch=4)
    full = learner._dev_caches[K_TRAINING]
    assert full.ready and not full.frozen and len(full.parts()) == 4
    total = sum(full.part_bytes.values())

    ref, _ = run_hashed(rcv1_path, device_cache_mb=0,
                        num_jobs_per_epoch=4)

    # budget that fits ~half the parts: pre-seed the cache with a byte
    # budget (the MB-granular param can't express sub-MB datasets)
    def seed_cache(learner):
        pool = {"used": 0}
        cache = _DeviceBatchCache(0, shared=pool)
        cache.budget = int(total * 0.55)
        learner._dev_caches = {K_TRAINING: cache}
        learner._dev_cache_pool = pool

    seen, learner2 = run_hashed(rcv1_path, device_cache_mb=256,
                                num_jobs_per_epoch=4, setup=seed_cache)
    cache = learner2._dev_caches[K_TRAINING]
    assert cache.ready and cache.partial
    assert 1 <= len(cache.parts()) <= 3
    # the cached set is a part prefix
    assert cache.parts() == set(range(len(cache.parts())))
    np.testing.assert_allclose(seen, ref, rtol=1e-6, atol=1e-6)


def test_cache_iter_parts_order_and_permutation():
    c = _DeviceBatchCache(64)
    for part in (1, 0):
        for i in range(6):
            c.add(part, (part, i), 8)
    c.finish_pass()
    plain = list(c.iter_parts(False, seed=0))
    assert plain == [(p, (p, i)) for p in (0, 1) for i in range(6)]
    shuf = list(c.iter_parts(True, seed=3))
    assert shuf != plain
    # parts stay in order; within-part items are a permutation
    assert [p for p, _ in shuf] == [p for p, _ in plain]
    assert sorted(shuf) == sorted(plain)
    assert list(c.iter_parts(True, seed=3)) == shuf  # deterministic


def test_panel_replay_chunked_backward(tmp_path):
    """Criteo-format (uniform-width panel) cached replay: epochs 1+ take
    the chunked-run backward (panel_chunk_tokens staged at cache time) and
    reproduce the streamed trajectory; only summation order differs."""
    rng = np.random.RandomState(5)
    path = tmp_path / "criteo.txt"
    with open(path, "w") as f:
        for _ in range(200):
            ints = [str(rng.randint(0, 50)) for _ in range(13)]
            cats = [f"c{rng.randint(0, 400)}" for _ in range(26)]
            f.write("\t".join([str(rng.randint(0, 2))] + ints + cats) + "\n")

    def run(cache_mb):
        args = [("data_in", str(path)), ("data_format", "criteo"),
                ("loss", "fm"), ("V_dim", "4"), ("V_threshold", "0"),
                ("lr", "0.1"), ("l1", "0.01"), ("l2", "0"),
                ("batch_size", "50"), ("shuffle", "0"),
                ("max_num_epochs", "5"), ("num_jobs_per_epoch", "1"),
                ("report_interval", "0"), ("stop_rel_objv", "0"),
                ("hash_capacity", str(1 << 14)),
                ("device_cache_mb", str(cache_mb))]
        learner = Learner.create("sgd")
        learner.init(args)
        seen = []
        learner.add_epoch_end_callback(lambda e, t, v: seen.append(t.loss))
        learner.run()
        return np.array(seen), learner

    ref, _ = run(0)
    got, learner = run(256)
    cache = learner._dev_caches[K_TRAINING]
    assert cache.ready
    # the cached payloads really carry the chunked layout (panel path)
    payloads = [pl for items in cache.entries.values() for pl in items]
    assert payloads and all(pl[0] == "panel_chunked" for pl in payloads)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)


def test_mesh_replay_matches_streaming(rcv1_path):
    """Single-controller mesh path: staged global (DeviceBatch, slots)
    pairs replay epochs 1+ with no re-staging, reproducing the streamed
    trajectory (shuffle off)."""
    def run(cache_mb):
        args = [("data_in", rcv1_path), ("data_format", "libsvm"),
                ("loss", "fm"), ("V_dim", "2"), ("V_threshold", "0"),
                ("lr", "0.1"), ("l1", "0.1"), ("l2", "0"),
                ("batch_size", "25"), ("shuffle", "0"),
                ("max_num_epochs", "5"), ("num_jobs_per_epoch", "1"),
                ("report_interval", "0"), ("stop_rel_objv", "0"),
                ("hash_capacity", str(1 << 14)),
                ("mesh_dp", "2"), ("mesh_fs", "4"),
                ("device_cache_mb", str(cache_mb))]
        learner = Learner.create("sgd")
        learner.init(args)
        seen = []
        learner.add_epoch_end_callback(lambda e, t, v: seen.append(t.loss))
        learner.run()
        return np.array(seen), learner

    ref, _ = run(0)
    got, learner = run(256)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
    cache = learner._dev_caches[K_TRAINING]
    assert cache.ready
    payloads = [pl for items in cache.entries.values() for pl in items]
    assert payloads and all(pl[0] == "devbatch" for pl in payloads)


def test_stream_chunks_matches_unsorted(tmp_path):
    """Producer-side chunked-run layout for STREAMED panel training
    (stream_chunks=1, device_cache_mb=0): same trajectory as the
    unsorted-scatter streamed step — the layout changes the backward's
    schedule, not its math."""
    from conftest import write_uniform_libsvm
    data = write_uniform_libsvm(str(tmp_path / "u.libsvm"), rows=300,
                                width=8, id_space=500)
    base, ln0 = run_hashed(data, epochs=4, device_cache_mb=0,
                           stream_chunks=0)
    chunked, ln1 = run_hashed(data, epochs=4, device_cache_mb=0,
                              stream_chunks=1)
    np.testing.assert_allclose(chunked, base, rtol=2e-5)


def test_stream_chunks_staging_replay(tmp_path):
    """With the cache ON, stream_chunks defers to the staging-time
    DEVICE chunker (host-built chunks would double the staged bytes on
    the slow link); the trajectory matches the host-chunked streamed run
    and the staged payloads still carry the chunked layout."""
    from conftest import write_uniform_libsvm
    data = write_uniform_libsvm(str(tmp_path / "u.libsvm"), rows=300,
                                width=8, id_space=500)
    streamed, _ = run_hashed(data, epochs=5, device_cache_mb=0,
                             stream_chunks=1)
    cached, ln = run_hashed(data, epochs=5, device_cache_mb=256,
                            stream_chunks=1)
    np.testing.assert_allclose(cached, streamed, rtol=2e-5)
    cache = ln._get_cache(K_TRAINING)
    assert cache is not None and cache.ready
    # the staged payloads carry the chunked layout
    for payloads in cache.entries.values():
        for pl in payloads:
            assert pl[0] == "panel_chunked"


def test_stream_chunks_binary_panel(tmp_path):
    """Binary (value-elided) uniform panels ride the cv=None chunk path:
    BatchReader drops all-1.0 value arrays, _panel_arrays keeps uniform
    FULL batches valueless (rows must be a multiple of the bucketed
    batch cap — bucket(128)=128 — or the ragged pad path materializes
    values), and _chunk_host must hand chunk_vals=None through dispatch
    and staging."""
    rng = np.random.RandomState(11)
    path = str(tmp_path / "bin.libsvm")
    with open(path, "w") as f:
        for _ in range(384):  # 3 full batches of 128
            ids = np.sort(rng.choice(500, 8, replace=False))
            f.write(str(rng.randint(0, 2)) + " "
                    + " ".join(f"{j}:1" for j in ids) + "\n")
    base, _ = run_hashed(path, epochs=4, device_cache_mb=0,
                         stream_chunks=0, batch_size=128)
    chunked, ln = run_hashed(path, epochs=4, device_cache_mb=0,
                             stream_chunks=1, batch_size=128)
    np.testing.assert_allclose(chunked, base, rtol=2e-5)
    # prove the cv=None branch actually engaged: a full uniform binary
    # batch prepares as a valueless chunked panel
    from difacto_tpu.data import BatchReader
    blk = next(iter(BatchReader(path, "libsvm", batch_size=128)))
    payload = ln._prepare_hashed(blk, want_counts=True, fill_counts=False,
                                 dim_min=8, job="train", b_cap=128,
                                 stream_chunk=True)
    assert payload[0] == "panel_chunked"
    ci, cl, cv, hr, hv = payload[3]
    assert cv is None and hv is None and payload[4] is True  # binary
    assert hr.shape == (payload[7],)


def test_non_repadable_cache_invalidates_on_growth_mid_staging():
    """The invalidate arm still guards non-repadable caches (the mesh
    dictionary path): a capacity change between adds kills the cache."""
    c = _DeviceBatchCache(64)
    c.add(0, "a", 10, capacity=100)
    c.add(0, "b", 10, capacity=200)
    assert not c.alive and not c.entries and c.shared["used"] == 0


def test_stale_non_repadable_cache_invalidates_at_replay(rcv1_path):
    """A staged-vs-live capacity mismatch at the replay entry invalidates
    a NON-repadable cache (hashed here; the mesh dictionary in
    production) and training falls back to streaming with the
    trajectory unchanged."""
    ref, _ = run_hashed(rcv1_path, device_cache_mb=0, epochs=5)

    def setup(learner):
        def corrupt(e, t, v):
            if e == 2:
                learner._dev_caches[K_TRAINING].capacity += 1

        learner.add_epoch_end_callback(corrupt)

    seen, learner = run_hashed(rcv1_path, device_cache_mb=256, epochs=5,
                               setup=setup)
    assert not learner._dev_caches[K_TRAINING].alive
    np.testing.assert_allclose(seen, ref, rtol=1e-6, atol=1e-6)


# ------------------------------------------- ISSUE 30: the sticky <job>.c
def _write_two_cap_rows(path: str, batch: int = 256, width: int = 40,
                        hot_batches: int = 4) -> int:
    """Binary uniform rows whose FIRST batch touches every lane once or
    twice (next to no chunk needed: the sticky chunk cap starts low) and
    whose later batches draw from ``width`` ids only (each lane's run is
    ``batch`` tokens long: the cap must grow). The shapes are large
    enough (static chunk bound > ShapeSchedule.STATIC_CHUNKS) for the
    sticky cap to be in use. Returns the row count."""
    with open(path, "w") as f:
        for r in range(batch):
            ids = range(1000 + r * width, 1000 + (r + 1) * width)
            f.write(f"{r % 2} " + " ".join(f"{j}:1" for j in ids) + "\n")
        for r in range(hot_batches * batch):
            f.write(f"{r % 2} " + " ".join(f"{j}:1" for j in range(
                500_000, 500_000 + width)) + "\n")
    return (1 + hot_batches) * batch


def _run_two_caps(path, cache_mb, epochs=4):
    args = dict(data_in=path, data_format="libsvm", loss="fm", V_dim=4,
                V_threshold=0, lr=0.1, l1=0.01, l2=0, batch_size=256,
                shuffle=0, max_num_epochs=epochs, num_jobs_per_epoch=1,
                report_interval=0, stop_rel_objv=0,
                hash_capacity=1 << 18, producer_mode="thread",
                device_cache_mb=cache_mb)
    ln = Learner.create("sgd")
    assert ln.init([(k, str(v)) for k, v in args.items()]) == []
    ends = []

    def on_end(epoch, train, _val):
        import threading
        for t in threading.enumerate():
            if t.name == "pair-exec-compile":
                t.join()
        ends.append({
            "rows": train.nrows, "loss": train.loss,
            "paired": getattr(ln, "_paired_dispatches", 0),
            "ccap": ln.obs.value("step_chunk_cap_total", job="train"),
            "chunks": ln.obs.value("step_chunks_total", job="train")})

    ln.add_epoch_end_callback(on_end)
    ln.run()
    return ends, ln


def test_replay_with_two_chunk_caps_dispatches_the_odd_batch_alone(
        tmp_path):
    """A batch staged before the sticky chunk cap grew keeps its smaller
    layout; the pair-replay executable is keyed by the chunk cap too, so
    that batch is never handed to a program compiled for its neighbours'
    shape: it runs alone (as a ragged tail does), the rest pair, every
    epoch reports all its rows, and the trajectory is the streamed one."""
    from difacto_tpu.data.pack_stream import ShapeSchedule
    from difacto_tpu.ops.batch import chunk_cap
    path = str(tmp_path / "two_caps.libsvm")
    rows = _write_two_cap_rows(path)
    ref, _ = _run_two_caps(path, 0)
    got, ln = _run_two_caps(path, 64)
    cache = ln._dev_caches[K_TRAINING]
    assert cache.ready
    items = [pl for part in cache.entries.values() for pl in part]
    assert [pl[0] for pl in items] == ["panel_chunked"] * 5
    b_cap, width, u_cap = items[0][4:7]
    assert chunk_cap(u_cap, b_cap * width) > ShapeSchedule.STATIC_CHUNKS
    caps = [pl[3][1].shape[0] for pl in items]
    need = [pl[10] for pl in items]
    # the first batch needs a chunk only where two ids share a table
    # row; the others sixteen a hot lane
    assert need[0] < 0.1 * b_cap * width / 16
    assert len(set(need[1:])) == 1 and need[1] >= 16 * (width - 1)
    assert caps[0] < caps[1] and len(set(caps[1:])) == 1
    assert caps[1] == ln._shapes.snapshot()["train.c"] < chunk_cap(
        u_cap, b_cap * width)
    for pl, c, n in zip(items, caps, need):
        assert n <= c and pl[3][0].shape == (c, 16)
        assert int((np.asarray(pl[3][1]) < u_cap).sum()) == n
    # all other statics agree: the chunk cap alone keeps the odd one out
    assert len({pl[4:9] for pl in items}) == 1
    assert len({ln._pair_statics(pl) for pl in items}) == 2
    # two executables, one a chunk cap, each fed only its own shape
    keys = [k for k, v in ln._pair_execs.items() if v is not None]
    assert sorted(k[-2] for k in keys) == sorted(set(caps))
    for before, after in zip(got[1:], got[2:]):
        # a replayed epoch: batches 2-5 in two pairs, batch 1 alone
        assert after["paired"] - before["paired"] == 2
        assert after["ccap"] - before["ccap"] == sum(caps)
        assert after["chunks"] - before["chunks"] == sum(need)
    assert [e["rows"] for e in got] == [rows] * len(got)
    np.testing.assert_allclose([e["loss"] for e in got],
                               [e["loss"] for e in ref], rtol=2e-5)


def test_stream_chunks_payloads_carry_heads(tmp_path):
    """stream_chunks=1 (no cache): the producer-side builder ships the
    two-tier layout (at the static bound: the shapes are small), and the
    chunk counters see every streamed step."""
    from conftest import write_uniform_libsvm
    data = write_uniform_libsvm(str(tmp_path / "u.libsvm"), rows=300,
                                width=8, id_space=500)
    _, ln = run_hashed(data, epochs=2, device_cache_mb=0, stream_chunks=1)
    from difacto_tpu.data import BatchReader
    from difacto_tpu.ops.batch import chunk_cap, chunks_needed
    blk = next(iter(BatchReader(data, "libsvm", batch_size=25)))
    payload = ln._prepare_hashed(blk, want_counts=True, fill_counts=False,
                                 dim_min=8, job="train", b_cap=32,
                                 stream_chunk=True)
    assert payload[0] == "panel_chunked" and len(payload[3]) == 5
    ci, cl, cv, hr, hv = payload[3]
    b_cap, width, u_cap = payload[5:8]
    assert hr.shape == (u_cap,) and (hv is None) == (cv is None)
    c_cap = chunk_cap(u_cap, b_cap * width)
    assert cl.shape == (c_cap,)
    need = chunks_needed(payload[1][:b_cap * width], u_cap)
    assert int((cl < u_cap).sum()) == need <= c_cap
    # every lane the batch touches has its head, and no other
    lanes = np.unique(payload[1][:b_cap * width])
    assert (np.flatnonzero(hr < b_cap) == lanes).all()
    ccap = ln.obs.value("step_chunk_cap_total", job="train")
    chunks = ln.obs.value("step_chunks_total", job="train")
    steps = 2 * 12                       # 300 rows in batches of 25
    assert ccap == steps * c_cap and 0 < chunks <= ccap


def test_stream_chunks_worker_caps_reach_the_consumer():
    """A producer-chunked payload packed at a grown chunk cap (a worker
    process's own schedule) leaves that cap in the consumer's."""
    from difacto_tpu.data.pack_stream import (ShapeSchedule, chunk_host,
                                              payload_chunks)
    from difacto_tpu.learners.sgd import SGDLearner
    rng = np.random.RandomState(3)
    b_cap, width, u_cap = 256, 40, 9216
    cells = b_cap * width
    lanes = rng.randint(0, 600, cells).astype(np.int32)
    i32 = np.concatenate([lanes, np.zeros(u_cap + 2, np.int32)])
    worker = ShapeSchedule()
    chunks = chunk_host(worker, "train", i32, np.zeros(1, np.float32),
                        b_cap, width, u_cap, True)
    c_cap = worker.snapshot()["train.c"]
    assert chunks[1].shape == (c_cap,) and chunks[3].shape == (u_cap,)
    payload = ("panel_chunked", i32, None, chunks, True, b_cap, width,
               u_cap)
    assert payload_chunks(payload) == int((chunks[1] < u_cap).sum()) > 0
    ln = SGDLearner.__new__(SGDLearner)
    ln._shapes = ShapeSchedule()
    ln._absorb_payload_caps("train", ("ready", None, payload))
    assert ln._shapes.snapshot() == {"train.b": b_cap, "train.w": width,
                                     "train.u": u_cap, "train.c": c_cap}
    # a plain panel's chunks are counted only when somebody will stage
    # a layout from it
    plain = ("panel", i32, None, True, b_cap, width, u_cap)
    assert payload_chunks(plain) is None
    assert payload_chunks(plain, count=True) == payload_chunks(payload)
