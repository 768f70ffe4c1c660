"""The FM forward's 16-bit gather sources.

``losses/fm.fm_predict_panel_xv`` gathers one combined ``[w | V]`` row a
token. Stored as float32 at ``V_dim = 16`` that source is
``f32[294912,17]``: 17 lanes pad to 128, 151 MB, and the v5e's compiler
leaves it in HBM (~10 ns a gathered row), where V64's bf16 source of the
same padded bytes as V16's packed one sits in fast memory (``S(1)``).
Where ``packs_forward`` holds the source is one ``uint16[U, 2(k+1)]``
array, 256 B a padded row, and each gathered row is reassembled. 8-bit
rows (``slot_dtype`` int8 or fp8) dequantised to float32 made the same
``f32[294912,65]`` source at ``V_dim = 64``; where ``packs_codes`` holds
the forward gathers their codes, two to a 16-bit lane, with the halves of
``w`` and of the masked V scale (``code_rows``: ``uint16[U, 36]``), and
dequantises each gathered row.

(a) on the CPU: ``pred`` and ``XV`` are the plain float32 gather's bit
    for bit, with -0.0, NaN payloads, infinities and subnormals in ``w``
    and ``V``, masked rows, binary and valued panels, the column loop and
    the wide branch; the code source's gathered rows are the dequantised
    rows' bit for bit, and so are ``pred`` and ``XV`` where the CPU does
    not contract a multiply and an add into one rounding;
(b) the rules engage by storage dtype and width alone, and the gauge
    ``step_forward_packed{job=train}`` a learner sets says the same;
(c) the V16 and 8-bit cells' pair programs compiled for a described v5e
    at their real shapes: every forward gather reads a source in fast
    memory and none reads a float32 ``[294912,17]`` or ``[294912,65]``;
    V64's forward holds no ``u16[``;
(d) what ``learner.init`` compiles for 8-bit rows, which no persistent
    cache keeps (the table's seed is a constant of the program), is the
    table's init alone, and its text is pinned.
"""

import json
import os
import re

import numpy as np
import pytest
from conftest import write_uniform_libsvm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# float32 bit patterns every lane kind must carry through the halves
SPECIAL = np.array([0x80000000,            # -0.0
                    0x7FC00000, 0xFFA00001,  # NaN, signalling NaN payload
                    0x7F800000, 0xFF800000,  # +Inf, -Inf
                    0x00000001, 0x807FFFFF,  # subnormals
                    0x0000FFFF, 0xFFFF0000], np.uint32).view(np.float32)


def _bits(x):
    return np.asarray(x).view(np.uint32)


def _panel(rng, U, B, F, valued):
    import jax.numpy as jnp
    from difacto_tpu.ops.batch import PanelBatch
    ones = jnp.ones((B,), jnp.float32)
    return PanelBatch(
        idx=jnp.asarray(rng.integers(0, U, (B, F)), jnp.int32),
        vals=(jnp.asarray(rng.random((B, F)), jnp.float32) if valued
              else None),
        labels=ones, rweight=ones, row_mask=ones,
        num_rows=jnp.int32(B), num_uniq=jnp.int32(U))


# (a) ------------------------------------------------- bit for bit, CPU
@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("valued", [False, True])
@pytest.mark.parametrize("F", [39, 70])
def test_forward_is_the_float32_gather_bit_for_bit(monkeypatch, F, valued,
                                                   masked):
    import jax
    import jax.numpy as jnp
    from difacto_tpu.losses import fm
    assert (F > fm._COLLOOP_MAX_WIDTH) == (F == 70)   # both branches
    rng = np.random.default_rng(40 + F)
    U, B, k = 4096, 256, 16
    w = rng.standard_normal(U).astype(np.float32)
    V = rng.standard_normal((U, k)).astype(np.float32)
    n = len(SPECIAL)
    w[:n] = SPECIAL
    V[n:2 * n, 3] = SPECIAL
    V[2 * n, :n] = SPECIAL
    mask = None
    if masked:
        mask = jnp.asarray(rng.random(U) < 0.7, jnp.float32)
    params = fm.FMParams(jnp.asarray(w), jnp.asarray(V), mask)
    pb = _panel(rng, U, B, F, valued)
    # every special row is read
    pb = pb._replace(idx=pb.idx.at[:2 * n + 1, 0].set(
        jnp.arange(2 * n + 1)))

    fwd = jax.jit(fm.fm_predict_panel_xv)
    assert "uint16" in str(jax.make_jaxpr(fm.fm_predict_panel_xv)(params,
                                                                  pb))
    pred, XV = fwd(params, pb)
    monkeypatch.setattr(fm, "_row_taker", lambda wv: lambda idx: wv[idx])
    jax.clear_caches()                  # trace the plain gather anew
    assert "uint16" not in str(jax.make_jaxpr(fm.fm_predict_panel_xv)(
        params, pb))
    pred0, XV0 = jax.jit(fm.fm_predict_panel_xv)(params, pb)
    assert np.isnan(np.asarray(pred)).any()        # the specials arrived
    assert np.array_equal(_bits(pred), _bits(pred0))
    assert np.array_equal(_bits(XV), _bits(XV0))


def _code_params(kind, U=4096, k=64, seed=43):
    """``rows_to_params`` of 8-bit fused rows built as the store keeps
    them: codes of both signs, all-zero rows (scale 1.0), rows whose
    codes are all negative, rows that are not live and rows whose ``w``
    is 0.0 or -0.0 (``l1_shrk`` masks their V)."""
    import jax.numpy as jnp
    from difacto_tpu.ops import fused
    from difacto_tpu.updaters import sgd_updater as su
    param = su.SGDUpdaterParam(V_dim=k, slot_dtype=kind, hash_capacity=U)
    _, h, _, off = su.row_layout(param, U)
    rng = np.random.default_rng(seed)
    V = rng.standard_normal((U, k)).astype(np.float32)
    V[:64] = 0.0
    V[64:96] = -np.abs(V[64:96])
    w = rng.standard_normal(U).astype(np.float32)
    w[96:128] = 0.0
    w[128:160] = -0.0
    live = rng.random(U) < 0.8
    Vc, sV = fused.quant_half(jnp.asarray(V), kind)
    Vgc, sVg = fused.quant_half(jnp.asarray(np.abs(V)), kind)
    zero = np.zeros(U, np.float32)
    rows = jnp.concatenate(
        [su.fuse_vvg(Vc, Vgc, h), jnp.zeros((U, off - 2 * h), jnp.int8),
         su.pack_scal(w, zero, zero, zero, live, jnp.int8,
                      scale_V=sV, scale_Vg=sVg)], axis=1)
    empty = jnp.zeros((0,), jnp.float32)
    state = su.SGDState(empty, empty, empty, empty, rows,
                        jnp.zeros((0,), bool))
    return su.make_fns(param).rows_to_params(state, rows)


_CODES = """
import json, sys
sys.path.insert(0, %(tests)r)
import jax
import numpy as np
import test_forward_pack as t
from difacto_tpu.losses import fm
got = {}
for kind in ("int8", "fp8"):
    p = t._code_params(kind)
    fwd = jax.jit(fm.fm_predict_panel_xv)
    for F in (39, 70):
        for valued in (False, True):
            pb = t._panel(np.random.default_rng(F), 4096, 256, F, valued)
            a = fwd(p, pb)
            b = fwd(p._replace(codes=None), pb)
            got[f"{kind}-{F}-{valued}"] = [
                bool(np.array_equal(t._bits(x), t._bits(y)))
                for x, y in zip(a, b)]
print("RESULT " + json.dumps(got))
"""


@pytest.fixture(scope="module")
def code_forwards():
    """``pred`` and ``XV`` from the code source against the dequantised
    float32 source, in a process held to ``--xla_cpu_max_isa=SSE4_2``:
    with FMA on, XLA's CPU codegen contracts the binary panel's
    ``XV + c * s`` into one rounding where the float32 source adds a
    gathered ``c * s`` (tests/test_owned_run.py)."""
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_cpu_max_isa=SSE4_2")
    out = subprocess.run(
        [sys.executable, "-c",
         _CODES % {"tests": os.path.join(ROOT, "tests")}],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [x for x in out.stdout.splitlines() if x.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


@pytest.mark.parametrize("valued", [False, True])
@pytest.mark.parametrize("F", [39, 70])
@pytest.mark.parametrize("kind", ["int8", "fp8"])
def test_code_source_is_the_dequantised_gather_bit_for_bit(
        code_forwards, kind, F, valued):
    import jax.numpy as jnp
    from difacto_tpu.losses import fm
    k = 64
    p = _code_params(kind)
    assert p.codes.kind == kind
    assert p.codes.src.dtype == jnp.uint16
    assert p.codes.src.shape == (4096, k // 2 + 4)
    vm = np.asarray(p.v_mask)
    assert 0 < vm.sum() < len(vm) and not vm[96:160].any()
    # the gathered token rows: [w | V * v_mask], signed zeros included
    idx = jnp.asarray(np.random.default_rng(F).integers(0, 4096, (256, F)),
                      jnp.int32)
    plain = jnp.concatenate([p.w[:, None], p.V * p.v_mask[:, None]], axis=1)
    tok = fm._code_taker(p.codes, k)(idx)
    a = np.asarray(plain)
    assert ((a == 0) & np.signbit(a)).any()         # -0.0 where masked
    assert np.array_equal(_bits(tok), _bits(plain[idx]))
    assert code_forwards[f"{kind}-{F}-{valued}"] == [True, True]


# (b) ------------------------------------- the rule, and the gauge's say
@pytest.mark.parametrize("model,packs", [
    (dict(V_dim=16, V_dtype="float32"), True),      # fm_v16_kaggle
    (dict(V_dim=63, V_dtype="float32"), True),      # 128 halves: one row
    (dict(V_dim=64, V_dtype="float32"), False),     # 130 pad to 256
    (dict(V_dim=16, V_dtype="bfloat16"), False),    # 2 B a lane already
    (dict(V_dim=16, slot_dtype="int8"), True),      # its codes, u16[U, 12]
    (dict(V_dim=0), False),                         # no V: flat table
    (dict(V_dim=64, slot_dtype="int8"), True),      # fm_v64_criteo_int8
    (dict(V_dim=64, slot_dtype="fp8"), True),       # the cell's control
    (dict(V_dim=64, slot_dtype="bf16"), False),     # bf16 rows: no codes
])
def test_packed_source_engages_by_dtype_and_width(tmp_path, model, packs):
    import jax
    import jax.numpy as jnp
    from difacto_tpu.learners import Learner
    from difacto_tpu.losses import fm
    from difacto_tpu.obs import names
    from difacto_tpu.step import make_step_fns
    data = write_uniform_libsvm(str(tmp_path / "u.libsvm"), rows=64)
    args = dict(data_in=data, batch_size=32, hash_capacity=4096, **model)
    ln = Learner.create("sgd")
    assert ln.init([(k, str(v)) for k, v in args.items()]) == []
    assert ln.obs.value(names.STEP_FORWARD_PACKED, job="train") == packs

    # the learner's own train step takes a 16-bit source where the rule
    # of its storage dtype holds, and nowhere else
    k = model["V_dim"]
    rng = np.random.default_rng(k)
    _, train_step, _ = make_step_fns(ln.store.fns, ln.loss)
    step = str(jax.make_jaxpr(train_step)(
        ln.store.state, _panel(rng, 64, 8, 39, False),
        jnp.arange(64, dtype=jnp.int32)))
    assert ("uint16" in step) == packs
    if model.get("slot_dtype") in ("int8", "fp8"):
        assert fm.packs_codes(k) == packs
        return
    dt = jnp.bfloat16 if model.get("V_dtype") == "bfloat16" else jnp.float32
    assert fm.packs_forward(dt, k) == packs
    params = fm.FMParams(jnp.zeros((64,), jnp.float32),
                         jnp.zeros((64, k), dt))
    jaxpr = str(jax.make_jaxpr(fm.fm_predict_panel_xv)(
        params, _panel(rng, 64, 8, 39, False)))
    assert ("uint16" in jaxpr) == packs


def test_code_source_width_rule():
    from difacto_tpu.losses import fm
    # k codes two to a lane and four halves in 128 lanes
    assert [fm.packs_codes(k) for k in (0, 1, 64, 247, 248, 249, 256)] == [
        False, True, True, True, True, False, False]


# (c) ------------------------- the real shapes, compiled for a described v5e
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


# the cells' step (PERF.md 4): rows, panel width, row cap, chunk cap
B, F, U, C = 65536, 39, 294_912, 212_992


def _pair_text(name, device, data, form="line"):
    """The learner's own pair program (``_packed_panel_train_chunked2``,
    what a replay window times; ``form`` "loop" its two-trip loop) of
    configuration ``name``, lowered at the cells' shapes for ``device``
    -> its compiled text. Shapes only."""
    import jax
    import jax.numpy as jnp
    from difacto_tpu.learners import Learner
    from difacto_tpu.updaters.sgd_updater import init_state
    with open(os.path.join(ROOT, "perfbench", "configs",
                           name + ".json")) as f:
        cfg = json.load(f)
    args = dict(data_in=data, batch_size=32, hash_capacity=4096,
                **{k: cfg[k] for k in ("loss", "V_dim", "V_dtype", "lr",
                                       "l1", "V_threshold", "slot_dtype")
                   if k in cfg})
    ln = Learner.create("sgd")
    assert ln.init([(k, str(v)) for k, v in args.items()]) == []

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=device)

    state = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda: init_state(ln.store.param, cfg["hash_capacity"])))
    i32 = sds(jax.ShapeDtypeStruct((B * F + U + 2,), jnp.int32))
    f32 = sds(jax.ShapeDtypeStruct((3 * B + U,), jnp.float32))
    chunks = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda a, b: ln._panel_chunk_packed(a, b, B, F, U, True, C),
        i32, f32))
    pa = (i32, f32, chunks)
    program = (ln._packed_panel_train_chunked2_loop if form == "loop"
               else ln._packed_panel_train_chunked2)
    return program.lower(state, pa, pa, B, F, U, False,
                         True).compile().as_text()


def _forward_gather_sources(text):
    """The shape and layout of every forward gather's source operand
    (inside a fusion: the fused computation's parameter, whose layout
    says where the caller's operand lives)."""
    shape = {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = (\S+) ", line)
        if m:
            shape[m.group(1)] = m.group(2)
    return [shape[re.search(r" gather\((%[\w.\-]+)", line).group(1)]
            for line in text.splitlines()
            if " gather(" in line and 'leg="forward"' in line]


def test_v16_forward_gathers_from_fast_memory(topo, tmp_path):
    from jax.sharding import SingleDeviceSharding
    data = write_uniform_libsvm(str(tmp_path / "u.libsvm"), rows=64)
    one_chip = SingleDeviceSharding(topo.devices[0])
    src = _forward_gather_sources(
        _pair_text("fm_v16_kaggle", one_chip, data))
    assert len(src) == 2 * F                     # two steps of 39 columns
    assert all("S(1)" in s for s in src), sorted(set(src))
    assert not any(s.startswith(f"f32[{U},17]") for s in src)
    assert all(s.startswith(f"u16[{U},34]") for s in src)

    v64 = _pair_text("fm_v64_criteo", one_chip, data)
    assert not [line for line in v64.splitlines()
                if 'leg="forward"' in line and "u16[" in line]
    assert all(s.startswith(f"bf16[{U},65]")
               for s in _forward_gather_sources(v64))


@pytest.mark.parametrize("form", ["line", "loop"])
def test_int8_forward_gathers_codes_from_fast_memory(topo, tmp_path, form):
    """8-bit rows at V_dim = 64: the dequantised float32 source was
    ``f32[294912,65]``, built in fast memory and copied to HBM (151 MB
    padded) for the 39 gathers; the codes' source is ``u16[294912,36]``
    and stays in ``S(1)``. Its columns run as a loop of a few trips, a
    trip's gathers in the text once. The cell runs the pair's loop (its
    straight line is rematerialised, learners/sgd._rematerialised); both
    forms gather so."""
    from jax.sharding import SingleDeviceSharding
    from difacto_tpu.losses import fm
    data = write_uniform_libsvm(str(tmp_path / "u.libsvm"), rows=64)
    src = _forward_gather_sources(_pair_text(
        "fm_v64_criteo_int8", SingleDeviceSharding(topo.devices[0]), data,
        form))
    steps = 1 if form == "loop" else 2
    assert len(src) == steps * -(-F // fm._CODE_TRIPS)    # a trip's columns
    assert all("S(1)" in s for s in src), sorted(set(src))
    assert not any(s.startswith(f"f32[{U},65]") for s in src)
    assert all(s.startswith(f"u16[{U},36]") for s in src)


# (d) --------------------------- what set-up compiles in every run, CPU
# sha256 of ``_jitted_init``'s lowered text for the configuration below
# (seed 0, 4,096 rows of 8-bit codes): the program every run of an 8-bit
# cell compiles, since its seed is a constant of it (perfbench/sut.drive
# keeps it out of the persistent cache). A change here costs compile time
# in every run's set-up; a JAX upgrade that alters the text re-pins it
INIT_INT8_SHA256 = (
    "dc4cd957b4136b5a7c19eb695ee1b855a90a5f43e0f722818913a00498732713")


def test_int8_init_compiles_the_table_init_alone(tmp_path):
    import dataclasses
    import hashlib
    import jax
    import jax.monitoring as mon
    from difacto_tpu.learners import Learner
    from difacto_tpu.store import local
    data = write_uniform_libsvm(str(tmp_path / "u.libsvm"), rows=64)
    args = dict(data_in=data, batch_size=32, hash_capacity=4096, V_dim=64,
                slot_dtype="int8")
    seen = []

    def on_compile(event, secs, fun_name="", **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(fun_name)

    # nothing this process compiled before may stand in for init's compile
    local._jitted_init.cache_clear()
    jax.clear_caches()
    mon.register_event_duration_secs_listener(on_compile)
    try:
        ln = Learner.create("sgd")
        assert ln.init([(k, str(v)) for k, v in args.items()]) == []
    finally:
        mon.unregister_event_duration_listener(on_compile)
    assert seen == ["jit(build)"]
    text = local._jitted_init(dataclasses.astuple(ln.store.param),
                              ln.store.state.capacity, None).lower().as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == INIT_INT8_SHA256
