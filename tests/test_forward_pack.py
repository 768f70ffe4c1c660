"""ISSUE 40: the float32 FM forward gathers its ``[w | V]`` rows as two
16-bit halves.

``losses/fm.fm_predict_panel_xv`` gathers one combined ``[w | V]`` row a
token. Stored as float32 at ``V_dim = 16`` that source is
``f32[294912,17]``: 17 lanes pad to 128, 151 MB, and the v5e's compiler
leaves it in HBM (~10 ns a gathered row), where V64's bf16 source of the
same padded bytes as V16's packed one sits in fast memory (``S(1)``).
Where ``packs_forward`` holds the source is one ``uint16[U, 2(k+1)]``
array, 256 B a padded row, and each gathered row is reassembled.

(a) on the CPU: ``pred`` and ``XV`` are the plain float32 gather's bit
    for bit, with -0.0, NaN payloads, infinities and subnormals in ``w``
    and ``V``, masked rows, binary and valued panels, the column loop and
    the wide branch;
(b) the rule engages by storage dtype and width alone, and the gauge
    ``step_forward_packed{job=train}`` a learner sets says the same;
(c) the V16 cell's pair program compiled for a described v5e at its real
    shapes: every forward gather reads a source in fast memory and none
    reads the float32 ``[294912,17]``; V64's forward holds no ``u16[``.
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


# (b) ------------------------------------- the rule, and the gauge's say
@pytest.mark.parametrize("model,packs", [
    (dict(V_dim=16, V_dtype="float32"), True),      # fm_v16_kaggle
    (dict(V_dim=63, V_dtype="float32"), True),      # 128 halves: one row
    (dict(V_dim=64, V_dtype="float32"), False),     # 130 pad to 256
    (dict(V_dim=16, V_dtype="bfloat16"), False),    # 2 B a lane already
    (dict(V_dim=16, slot_dtype="int8"), True),      # dequantised to f32
    (dict(V_dim=0), False),                         # no V: flat table
])
def test_packed_source_engages_by_dtype_and_width(tmp_path, model, packs):
    import jax
    import jax.numpy as jnp
    from difacto_tpu.learners import Learner
    from difacto_tpu.losses import fm
    from difacto_tpu.obs import names
    data = write_uniform_libsvm(str(tmp_path / "u.libsvm"), rows=64)
    args = dict(data_in=data, batch_size=32, hash_capacity=4096, **model)
    ln = Learner.create("sgd")
    assert ln.init([(k, str(v)) for k, v in args.items()]) == []
    assert ln.obs.value(names.STEP_FORWARD_PACKED, job="train") == packs

    k = model["V_dim"]
    dt = jnp.bfloat16 if model.get("V_dtype") == "bfloat16" else jnp.float32
    assert fm.packs_forward(dt, k) == packs
    rng = np.random.default_rng(k)
    params = fm.FMParams(jnp.zeros((64,), jnp.float32),
                         jnp.zeros((64, k), dt))
    jaxpr = str(jax.make_jaxpr(fm.fm_predict_panel_xv)(
        params, _panel(rng, 64, 8, 39, False)))
    assert ("uint16" in jaxpr) == packs


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


def _pair_text(name, device, data):
    """The learner's own pair program (``_packed_panel_train_chunked2``,
    what a replay window times) of configuration ``name``, lowered at the
    cells' shapes for ``device`` -> its compiled text. Shapes only."""
    import jax
    import jax.numpy as jnp
    from difacto_tpu.learners import Learner
    from difacto_tpu.updaters.sgd_updater import init_state
    with open(os.path.join(ROOT, "perfbench", "configs",
                           name + ".json")) as f:
        cfg = json.load(f)
    args = dict(data_in=data, batch_size=32, hash_capacity=4096,
                **{k: cfg[k] for k in ("loss", "V_dim", "V_dtype", "lr",
                                       "l1", "V_threshold")})
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
    return ln._packed_panel_train_chunked2.lower(
        state, pa, pa, B, F, U, False, True).compile().as_text()


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
