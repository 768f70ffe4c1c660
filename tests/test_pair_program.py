"""ISSUE 38: the pair-replay program holds one table where two do not fit.

``packed_panel_train_chunked2`` (two cached batches a dispatch, what a
replay window times) has two forms of one arithmetic. Composed in a
straight line, the table between the two steps is a value of its own in
the compiler's count of live bytes beside the donated one; at 2^24 fused
bf16 rows twice the table passes the chip and XLA's rematerialisation
pass clones the first step's scatter (``%fusion.51.remat``,
``%fusion.51.remat2``, ``%fusion.55``: three table-sized scatters for two
steps, 3.9 of the Avazu cell's 27.07 ms a step on the chip, ledger PR
37). As a two-trip loop over ONE carried table nothing is cloned; where
two tables fit, the straight line is the faster form on the chip
(PERF.md 6, PR 38), so ``_warm_pair_exec`` builds the loop only where the
straight line came out rematerialised.

(a) the learner's own pair program at two cells' real shapes, compiled for
    a described v5e (nothing runs; counts, not times). The loop: nothing
    rematerialised, no copy of the table, two executions of a table-sized
    scatter a pair, the table aliased in place, room for the batch cache.
    The straight line: ``_rematerialised`` finds the clones at 2^24 rows
    and nothing at 2^23;
(b) on the CPU at a tiny size, fused bf16 and flat tables with the gates
    on: one call of the pair program, in either form, equals two calls of
    the one-batch program on the same two payloads bit for bit, in both
    orders of two differing batches (a loop that picks the wrong payload
    on a trip, or runs one twice, fails here);
(c) the learner builds the straight line, and the loop in its place where
    the straight line is rematerialised; the ``compile.pair_exec`` spans
    say which.
"""

import json
import os
import re
import subprocess
import sys

import pytest
from conftest import write_uniform_libsvm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HBM = 16e9
B = 65536
# configuration -> (row cap, chunk cap, panel width) its traffic reaches
# (PERF.md 4: counted with the generator)
CELLS = {"fm_v64_avazu": (98_304, 114_688, 22),
         "fm_v64_criteo": (294_912, 212_992, 39)}
MODEL_KEYS = ("loss", "V_dim", "V_dtype", "V_threshold", "l1_shrk", "l1",
              "l2", "V_l2", "V_lr", "V_init_scale", "lr")


def _learner(data, **args):
    from difacto_tpu.learners import Learner
    args = dict(dict(num_jobs_per_epoch=1, batch_size=32, shuffle=0,
                     report_interval=0, stop_rel_objv=0,
                     producer_mode="thread", device_cache_mb=16),
                data_in=data, **args)
    ln = Learner.create("sgd")
    assert ln.init([(k, str(v)) for k, v in args.items()]) == []
    return ln


def _program(ln, loop):
    return (ln._packed_panel_train_chunked2_loop if loop
            else ln._packed_panel_train_chunked2)


# ------------------- (a) the real sizes, compiled for a described v5e chip
@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _compile_pair(name, one_chip, data, loop):
    """The learner's own ``_packed_panel_train_chunked2`` (a tiny learner
    of the configuration's model: the table's rows are a shape of the
    state argument, nothing of that size is allocated) lowered at the
    cell's shapes as ``_warm_pair_exec`` lowers it -> (compiled, table
    rows)."""
    import jax
    import jax.numpy as jnp
    from difacto_tpu.updaters.sgd_updater import init_state
    with open(os.path.join(ROOT, "perfbench", "configs",
                           name + ".json")) as f:
        cfg = json.load(f)
    u, c, w = CELLS[name]
    ln = _learner(data, hash_capacity=4096,
                  **{k: cfg[k] for k in MODEL_KEYS if k in cfg})

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    rows = cfg["hash_capacity"]
    state = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda: init_state(ln.store.param, rows)))
    i32 = jax.ShapeDtypeStruct((B * w + u + 2,), jnp.int32,
                               sharding=one_chip)
    f32 = jax.ShapeDtypeStruct((3 * B + u,), jnp.float32, sharding=one_chip)
    chunks = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda a, b: ln._panel_chunk_packed(a, b, B, w, u, True, c),
        i32, f32))
    pa = (i32, f32, chunks)
    return _program(ln, loop).lower(
        state, pa, pa, B, w, u, False, True).compile(), rows


def _computations(text):
    """HLO text -> {computation name: its instruction lines}."""
    out, name = {}, None
    for line in text.splitlines():
        m = re.match(r"(?:ENTRY )?(%[\w.\-]+) \(.*\{\s*$", line)
        if m:
            name = "ENTRY" if line.startswith("ENTRY") else m.group(1)
            out[name] = []
        elif name is not None:
            out[name].append(line)
    return out


@pytest.mark.parametrize("name", sorted(CELLS))
def test_compiled_loop_holds_one_table(topo, tmp_path, name):
    """The parent has the straight line alone (no
    ``_packed_panel_train_chunked2_loop``: this test fails there), and its ``fm_v64_avazu`` pair counts three
    table-sized scatters and two rematerialised instructions;
    ``fm_v64_criteo``'s counts two and none in both forms (2 x 4.29 GB
    has room)."""
    from jax.sharding import SingleDeviceSharding
    from difacto_tpu.learners.sgd import _rematerialised
    data = write_uniform_libsvm(str(tmp_path / "u.libsvm"), rows=64)
    compiled, rows = _compile_pair(
        name, SingleDeviceSharding(topo.devices[0]), data, True)
    assert not _rematerialised(compiled, rows)
    text = compiled.as_text()
    table = rows * 512
    shape = rf"= bf16\[{rows},256\]\S* "

    remat = re.findall(r"^\s*(?:ROOT )?(%\S*remat\S*) = ", text, re.M)
    assert remat == []
    assert re.findall(shape + r"copy(?:-start)?\(.*", text) == []

    # executions of a table-sized scatter a call: an instruction of the
    # entry runs once, one of the pair loop's body twice
    comps = _computations(text)
    loops = [ln for ln in comps["ENTRY"] if re.search(
        r' while\(.*op_name="jit\(packed_panel_train_chunked2\)/while"',
        ln)]
    trips = {"ENTRY": 1}
    for ln in loops:
        trips[re.search(r"body=(%[\w.\-]+)", ln).group(1)] = 2

    def scatters(line):
        if not re.search(shape, line):
            return False
        if re.search(r" scatter\(", line):
            return True
        called = re.search(r" fusion\(.*calls=(%[\w.\-]+)", line)
        return called is not None and any(
            re.search(r" scatter\(", x) for x in comps[called.group(1)])

    runs = {c: sum(map(scatters, comps[c])) for c in trips}
    assert sum(trips[c] * n for c, n in runs.items()) == 2, runs
    others = {c: n for c in comps if c not in trips
              and not c.startswith("%fused_computation")
              and (n := sum(map(scatters, comps[c])))}
    assert others == {}

    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes >= table
    assert m.alias_size_in_bytes >= table       # updated in place
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert total + 4096 * 2 ** 20 < HBM, (total, m)


@pytest.mark.parametrize("name, cloned", [("fm_v64_avazu", True),
                                          ("fm_v64_criteo", False)])
def test_straight_line_is_rematerialised_where_two_tables_do_not_fit(
        topo, tmp_path, name, cloned):
    """What ``_warm_pair_exec`` reads off the straight line: at 2^24 rows
    the first step's scatter under XLA's ``.remat`` names, the chip's own
    (ledger PR 37, ``breakdown.device_ops``); at 2^23 nothing."""
    from jax.sharding import SingleDeviceSharding
    from difacto_tpu.learners.sgd import _rematerialised
    data = write_uniform_libsvm(str(tmp_path / "u.libsvm"), rows=64)
    compiled, rows = _compile_pair(
        name, SingleDeviceSharding(topo.devices[0]), data, False)
    assert _rematerialised(compiled, rows) is cloned
    names = re.findall(rf"(%\S+) = bf16\[{rows},256\]\S* fusion\(",
                       compiled.as_text())
    if cloned:
        assert names == ["%fusion.51.remat", "%fusion.51.remat2",
                         "%fusion.55"]
    else:
        assert len(names) == 2 and not any("remat" in n for n in names)
    # a table of other rows is not this program's
    assert not _rematerialised(compiled, rows + 1)


# ------------------- (b) the pair against two one-batch calls, on the CPU
_PAIR = """
import json, sys, threading
import jax, jax.numpy as jnp
import numpy as np
sys.path.insert(0, %(tests)r)
import test_pair_program as T
from difacto_tpu.learners.sgd import K_TRAINING

def bits(x):
    a = np.ascontiguousarray(np.asarray(x))
    return a.reshape(a.shape or (1,)).view(np.uint8).tobytes()

def case(**model):
    ln = T._learner(%(data)r, hash_capacity=2048, max_num_epochs=2,
                    V_threshold=10, l1_shrk=1, lr=0.1, l1=0.5, l2=0,
                    **model)
    ln.run()                    # epoch 0 pushes the counts, epoch 1 replays
    for t in threading.enumerate():
        if t.name == "pair-exec-compile":
            t.join(300)
    staged = [p for part in ln._dev_caches[K_TRAINING].entries.values()
              for p in part if p[0] == "panel_chunked"]
    pa, pb = staged[0], staged[1]
    assert ln._pair_statics(pa) == ln._pair_statics(pb)
    assert bits(pa[1]) != bits(pb[1])           # two differing batches
    b_cap, width, u_cap, _, binary = pa[4:9]
    start = ln.store.state

    def copy():
        return jax.tree_util.tree_map(jnp.copy, start)

    out = {}
    for order, (x, y) in {"ab": (pa, pb), "ba": (pb, pa)}.items():
        s, o1, a1 = ln._packed_panel_train_chunked(
            copy(), *x[1:4], b_cap, width, u_cap, False, binary)
        s, o2, a2 = ln._packed_panel_train_chunked(
            s, *y[1:4], b_cap, width, u_cap, False, binary)
        single = [bits(v) for v in
                  jax.tree_util.tree_leaves(s) + [o1, a1, o2, a2]]
        equal = {}
        for form, loop in (("line", False), ("loop", True)):
            p = T._program(ln, loop)(
                copy(), x[1:4], y[1:4], b_cap, width, u_cap, False, binary)
            equal[form] = single == [bits(v) for v in
                jax.tree_util.tree_leaves(p[0]) + list(p[1:])]
        moved = [bits(v) for v in jax.tree_util.tree_leaves(start)]
        out[order] = dict(equal=equal,
                          moved=moved != single[:len(moved)],
                          losses=[float(o1), float(o2)],
                          table=hash(tuple(single[:len(moved)])))
    from difacto_tpu.updaters.sgd_updater import scal_cols
    w, _, sqrt_g, cnt, live = (np.asarray(v)
                               for v in scal_cols(ln.store.param, start))
    return dict(out, ordered=out["ab"]["table"] != out["ba"]["table"],
                touched=int((sqrt_g != 0).sum()),
                counted=int((cnt > 10).sum()),
                nnz_w=int((w != 0).sum()), live=int(live.sum()))

print("RESULT " + json.dumps({
    "fused_bf16": case(V_dim=4, V_dtype="bfloat16"),
    "flat": case(V_dim=0)}))
"""


@pytest.fixture(scope="module")
def pairs(tmp_path_factory):
    """Both tables, both orders, in one process of its own with the CPU
    held to SSE4.2 (with FMA on, XLA's CPU codegen may round two programs
    of the same arithmetic differently in last bits:
    tests/test_owned_run.py)."""
    data = write_uniform_libsvm(
        str(tmp_path_factory.mktemp("pair") / "u.libsvm"), rows=128,
        id_space=80)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_cpu_max_isa=SSE4_2")
    out = subprocess.run(
        [sys.executable, "-c", _PAIR % {
            "tests": os.path.join(ROOT, "tests"), "data": data}],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [x for x in out.stdout.splitlines() if x.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


@pytest.mark.parametrize("form", ["line", "loop"])
@pytest.mark.parametrize("order", ["ab", "ba"])
@pytest.mark.parametrize("table", ["fused_bf16", "flat"])
def test_pair_equals_two_single_calls_bit_for_bit(pairs, table, order,
                                                  form):
    got = pairs[table]
    # l1 has left some of the touched weights at 0 and some not; in the
    # fused table some rows have passed the count and some of those are
    # live (a flat table keeps no counts: it has no embedding to gate)
    assert 0 < got["nnz_w"] < got["touched"]
    if table == "fused_bf16":
        assert 0 < got["live"] <= got["counted"] < got["touched"]
    # the two orders end in different tables: a pair that ran its trips
    # in the other order, or one batch twice, is not this one
    assert got["ordered"]
    assert got[order]["moved"]
    assert got[order]["losses"][0] != got[order]["losses"][1]
    assert got[order]["equal"][form]


# ------------------------------ (c) which form the learner builds, and when
@pytest.mark.parametrize("cloned", [False, True], ids=["room", "no_room"])
def test_learner_builds_the_loop_where_the_line_is_rematerialised(
        tmp_path, monkeypatch, cloned):
    import threading
    from difacto_tpu.learners import sgd
    from difacto_tpu.obs import names, trace
    seen = []

    def reads(compiled, rows):
        seen.append(rows)
        return cloned

    monkeypatch.setattr(sgd, "_rematerialised", reads)
    data = write_uniform_libsvm(str(tmp_path / "u.libsvm"), rows=128)
    ln = _learner(data, V_dim=4, V_dtype="bfloat16", lr=0.1, l1=1e-4,
                  hash_capacity=2048, max_num_epochs=4)

    def on_end(*_):
        for t in threading.enumerate():
            if t.name == "pair-exec-compile":
                t.join(300)

    ln.add_epoch_end_callback(on_end)
    trace.drain_events()
    trace.start()
    try:
        ln.run()
    finally:
        trace.stop()
    forms = [e["args"]["form"] for e in trace.drain_events()
             if e["name"] == names.COMPILE_PAIR]
    assert forms == (["line", "loop"] if cloned else ["line"])
    assert seen == [2048]               # the straight line alone is read
    assert ln._paired_dispatches > 0
    (exec_,) = ln._pair_execs.values()
    assert (" while(" in exec_.as_text()) is cloned


def test_reading_a_flat_pair_prints_no_literals(tmp_path, monkeypatch):
    """The flat table's state holds an empty ``f32[rows, 0]`` leaf, and
    XLA's default text prints such a constant as ``rows`` pairs of braces
    (2 GB and a minute at the flat cell's 2^29 rows, in every run's epoch
    0): what ``_rematerialised`` reads has names and shapes alone."""
    import jax
    import jax.numpy as jnp
    from difacto_tpu.learners import sgd
    from difacto_tpu.updaters.sgd_updater import init_state
    data = write_uniform_libsvm(str(tmp_path / "u.libsvm"), rows=64)
    ln = _learner(data, V_dim=0, lr=0.1, l1=1, hash_capacity=4096)
    rows, (b, w, u) = 2 ** 22, (32, 8, 128)

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)

    state = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda: init_state(ln.store.param, rows)))
    i32 = jax.ShapeDtypeStruct((b * w + u + 2,), jnp.int32)
    f32 = jax.ShapeDtypeStruct((b * w + 3 * b + u,), jnp.float32)
    chunks = jax.eval_shape(
        lambda a, c: ln._panel_chunk_packed(a, c, b, w, u, False), i32, f32)
    pa = (i32, f32, chunks)
    compiled = ln._packed_panel_train_chunked2.lower(
        state, pa, pa, b, w, u, False, False).compile()
    read = []
    real = sgd.re.search
    monkeypatch.setattr(
        sgd.re, "search",
        lambda pat, text, *a: read.append(len(text)) or real(pat, text, *a))
    assert not sgd._rematerialised(compiled, rows)
    monkeypatch.undo()
    assert read and read[0] < 2 ** 20 < rows, read
    assert f"[{rows}]" in compiled.as_text()    # and it is this program
