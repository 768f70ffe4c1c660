"""Process-based producer pipeline: ProcessProducerPool determinism vs the
thread pool, worker-death/exception retry (exactly-once), straggler
re-issue, and shared-memory ring hygiene — no leaked /dev/shm segments on
any exit path (clean, consumer break, worker raise; ISSUE 1).

Every test runs under an explicit SIGALRM deadline: a deadlocked
multiprocess pipeline must fail the suite loudly, not hang the tier-1
command. Workers are ``spawn``-ed and inherit JAX_PLATFORMS=cpu (the pool
sets it for its workers regardless; conftest.py sets it for this parent).
All make_iter callables live at module level so spawn can pickle them by
reference.
"""

import contextlib
import os
import signal
import time

import numpy as np
import pytest

from difacto_tpu.data.producer_pool import (OrderedProducerPool,
                                            ProcessProducerPool)


@contextlib.contextmanager
def deadline(seconds: int):
    """Hard per-test timeout: multiprocess bugs hang, and a hang must be
    a failure, not an 870 s tier-1 timeout."""
    def on_alarm(signum, frame):
        raise TimeoutError(f"test exceeded {seconds}s deadline")

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


def ring_segments() -> set:
    try:
        return {n for n in os.listdir("/dev/shm")
                if n.startswith("difacto_ring")}
    except FileNotFoundError:  # pragma: no cover - non-Linux
        return set()


# ---------------------------------------------------------- make_iters
# (module-level: spawn pickles them by reference)

def seeded_items(part):
    """Deterministic per-part item stream: mixed structure (tuple + dict +
    arrays + scalars) to exercise the ring's encode/decode walk."""
    rng = np.random.RandomState(1000 + part)
    for j in range(5):
        yield {"part": part, "j": j,
               "a": rng.randint(0, 1 << 30, 64).astype(np.int32),
               "b": rng.rand(33).astype(np.float32),
               "meta": ("x", j)}


def slow_items(part):
    for j in range(12):
        time.sleep(0.03)
        yield (part, j, np.full(8, part * 100 + j, dtype=np.int64))


def failing_part1(part):
    if part == 1:
        raise RuntimeError("persistent boom")
    for j in range(3):
        yield (part, j)


def hang_once_items(marker_dir, part):
    """Attempt 1 of the last part hangs (after dropping a marker file);
    the re-issued attempt sees the marker and proceeds — the process
    twin of test_cached.test_producer_pool_straggler_reissue."""
    if part == 11:
        marker = os.path.join(marker_dir, f"attempt_{part}")
        if not os.path.exists(marker):
            open(marker, "w").close()
            time.sleep(120)  # hung IO; terminated at pool shutdown
    for j in range(3):
        yield (part, j)


def _snap(items):
    """Copy-out + normalize a pool's yields for comparison (process-pool
    arrays are ring views valid for one iteration)."""
    out = []
    for part, item in items:
        arrays = []
        from difacto_tpu.data.shm_ring import decode_item, encode_item
        spec = encode_item(item, arrays)
        out.append((part, decode_item(spec, [np.array(a) for a in arrays])))
    return out


# -------------------------------------------------------------- tests

def test_process_pool_matches_thread_pool_bytes():
    """Determinism contract: the process pool yields the byte-identical
    (part, item) sequence the thread pool yields for the same seeded
    parts."""
    with deadline(120):
        before = ring_segments()
        expect = _snap(OrderedProducerPool(4, seeded_items, n_workers=2))
        got = _snap(ProcessProducerPool(4, seeded_items, n_workers=2,
                                        slot_bytes=1 << 20))
    assert len(got) == len(expect) == 20
    for (pe, ie), (pg, ig) in zip(expect, got):
        assert pe == pg
        assert ie["part"] == ig["part"] and ie["j"] == ig["j"]
        assert ie["meta"] == ig["meta"]
        np.testing.assert_array_equal(ie["a"], ig["a"])
        np.testing.assert_array_equal(ie["b"], ig["b"])
    assert ring_segments() == before  # no leaked segments, clean path


def test_process_pool_survives_worker_kill():
    """A worker SIGKILLed mid-part is detected, its part re-queued
    (pool.reset) and resumed by a live worker exactly after the items
    already delivered — no duplicates, no gaps (the generation guard
    across the process boundary)."""
    with deadline(120):
        before = ring_segments()
        pool = ProcessProducerPool(2, slow_items, n_workers=2, depth=4,
                                   slot_bytes=1 << 20)
        got = []
        killed = False
        for part, item in pool:
            got.append((part, item[1], int(item[2][0])))
            if not killed and len(got) == 3:
                # part 0 is assigned to worker 0 (lowest part to the
                # first-fed worker); kill it mid-part
                os.kill(pool._procs[0].pid, signal.SIGKILL)
                killed = True
        assert killed
        expect = [(p, j, p * 100 + j) for p in range(2) for j in range(12)]
        assert got == expect
        assert ring_segments() == before


def test_process_pool_escalates_after_max_retries():
    """A persistently raising part escalates to the consumer after
    max_retries, after delivering the preceding parts — and the ring is
    still unlinked on the error path."""
    with deadline(120):
        before = ring_segments()
        pool = ProcessProducerPool(2, failing_part1, n_workers=2,
                                   max_retries=1, slot_bytes=1 << 20)
        got = []
        with pytest.raises(RuntimeError, match="persistent boom"):
            for part, item in pool:
                got.append((part, item))
        assert got == [(0, (0, j)) for j in range(3)]
        assert ring_segments() == before


def test_process_pool_straggler_reissue(tmp_path):
    """A part stuck on a hung worker process is re-issued through
    WorkloadPool.remove_stragglers; delivery stays exactly-once."""
    import functools

    from difacto_tpu.tracker.workload_pool import (WorkloadPool,
                                                   WorkloadPoolParam)
    with deadline(120):
        before = ring_segments()
        wp = WorkloadPool(WorkloadPoolParam(straggler_timeout=0.5))
        pool = ProcessProducerPool(
            12, functools.partial(hang_once_items, str(tmp_path)),
            n_workers=3, pool=wp, slot_bytes=1 << 20, join_timeout=2.0)
        items = list(pool)
        assert items == [(p, (p, j)) for p in range(12) for j in range(3)]
        assert os.path.exists(tmp_path / "attempt_11")  # it DID hang
        assert ring_segments() == before


def test_ring_no_leak_on_consumer_break():
    """Consumer early-exit (break mid-epoch) tears the ring down."""
    with deadline(120):
        before = ring_segments()
        pool = ProcessProducerPool(3, seeded_items, n_workers=2,
                                   slot_bytes=1 << 20)
        for i, (part, item) in enumerate(pool):
            if i == 2:
                break
        assert ring_segments() == before


def test_ring_oversize_item_falls_back_to_pickle():
    """An item larger than a ring slot travels the pickled channel —
    slower, never wrong — and is counted for observability."""
    with deadline(120):
        pool = ProcessProducerPool(2, seeded_items, n_workers=1,
                                   slot_bytes=256)  # < one item's arrays
        got = _snap(pool)
        assert [g[1]["j"] for g in got] == list(range(5)) * 2
        assert pool.overflow_items == 10


def test_ring_encode_decode_roundtrip_and_header():
    """ShmRing slot round-trip: structure, dtypes, zero-copy reads, and
    the tail header's (part, seq, gen) identity."""
    from difacto_tpu.data.rowblock import RowBlock
    from difacto_tpu.data.shm_ring import ShmRing
    blk = RowBlock(offset=np.array([0, 2, 5], np.int64),
                   label=np.array([1.0, 0.0], np.float32),
                   index=np.arange(5, dtype=np.uint32),
                   value=None)
    item = ("ready", blk, ("panel", np.arange(12, dtype=np.int32),
                           np.zeros(3, np.float32), True, 2, 6, 8))
    ring = ShmRing(n_slots=2, slot_bytes=1 << 16)
    try:
        ring.write(0, item, part=3, seq=7, gen=2)
        out, part, seq, gen = ring.read(0)
        assert (part, seq, gen) == (3, 7, 2)
        kind, oblk, payload = out
        assert kind == "ready" and payload[0] == "panel"
        assert isinstance(oblk, RowBlock) and oblk.value is None
        np.testing.assert_array_equal(oblk.offset, blk.offset)
        np.testing.assert_array_equal(payload[1],
                                      np.arange(12, dtype=np.int32))
        assert payload[3:] == (True, 2, 6, 8)
        del out, oblk, payload  # drop the zero-copy views before close
    finally:
        ring.unlink()
    assert ring.name not in ring_segments()


def test_learner_process_mode_matches_thread_trajectory(rcv1_path):
    """End-to-end: the SGD learner's streamed hashed path produces the
    same training trajectory with producer_mode=process as with threads
    (same batches, same canonical order), and reports the transport +
    stage decomposition it ran."""
    from difacto_tpu.learners import Learner
    base = [("data_in", rcv1_path), ("V_dim", "0"), ("l2", "1"),
            ("l1", "1"), ("lr", "1"), ("num_jobs_per_epoch", "2"),
            ("batch_size", "50"), ("max_num_epochs", "2"),
            ("shuffle", "0"), ("report_interval", "0"),
            ("stop_rel_objv", "0"), ("device_cache_mb", "0"),
            ("hash_capacity", "4096"), ("num_producers", "1")]

    def run(mode):
        ln = Learner.create("sgd")
        ln.init(base + [("producer_mode", mode)])
        seen = []
        ln.add_epoch_end_callback(
            lambda e, t, v: seen.append((t.nrows, t.loss)))
        ln.run()
        stages = ln.obs.snapshot()["counters"]["stage_seconds_total"]
        return seen, dict({dict(k)["stage"]: v for k, v in stages.items()},
                          producer_mode=ln._last_producer_mode)

    with deadline(300):
        before = ring_segments()
        t_seen, t_stats = run("thread")
        p_seen, p_stats = run("process")
    assert t_stats["producer_mode"] == "thread"
    assert p_stats["producer_mode"] == "process"
    assert p_stats["pack"] > 0  # worker-side pack time was collected
    assert [n for n, _ in t_seen] == [n for n, _ in p_seen]
    np.testing.assert_allclose([ls for _, ls in t_seen],
                               [ls for _, ls in p_seen], rtol=1e-6)
    assert ring_segments() == before


def test_no_leaked_segments_overall():
    """The ISSUE 1 acceptance check: whatever ran before this test, no
    difacto ring segment may be live in /dev/shm between tests (every
    pool unlinks on its own exit paths; atexit is only the crash net)."""
    assert ring_segments() == set()
