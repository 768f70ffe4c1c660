"""JAX flow analyzer + runtime tracer suite (difacto-lint v4).

Three layers, all tier-1:

- **tracer units** (utils/jaxtrace.py) — disabled pass-through, per-site
  compile/call counting with the jit cache as ground truth (weak-typed
  scalars never over-count), static-argnum keys by value, fetch
  counting, dump/load round-trip;
- **the static model** (analysis/jaxflow.py) — the serve jit site is
  known and warm-declared on this very repo, declared fetch points
  include the executor's scores sync, rule scoping (local vs cross)
  matches the --changed-only contract, pass timings land in the JSON
  report;
- **the gate** — drive the REAL serve path (MicroBatcher ->
  PredictExecutor) in a subprocess under DIFACTO_JAXTRACE=1 and assert
  dynamic ⊆ static: every observed jit site is statically known AND
  warm-declared, compiles STOP GROWING after warm-up (the "zero
  steady-state recompiles" claim, previously only bench-measured),
  and every observed device->host transfer is a declared fetch point.
  Same shape as the RACETRACE gate in tests/test_lint.py.

Rule fixture twins (TP exactly once / negative / suppressed) live in
tests/test_lint.py next to every other rule's.
"""

import importlib.util
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from difacto_tpu.analysis import core
from difacto_tpu.analysis.cli import DEFAULT_PATHS
from difacto_tpu.analysis.cli import main as lint_main
from difacto_tpu.analysis.jaxflow import get_jax_model
from difacto_tpu.utils import jaxtrace

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def repo_model():
    project = core.Project(
        REPO_ROOT, [p for p in DEFAULT_PATHS if (REPO_ROOT / p).exists()])
    return get_jax_model(project)


# ---------------------------------------------------------------------------
# tracer units


def test_tracer_disabled_is_passthrough(monkeypatch):
    monkeypatch.delenv("DIFACTO_JAXTRACE", raising=False)
    jaxtrace.reset()
    f = jaxtrace.jit(lambda x: x + 1)
    import jax.numpy as jnp
    out = f(jnp.ones(3))
    assert out.shape == (3,)
    got = jaxtrace.fetch(out, point="unit")
    assert isinstance(got, np.ndarray)
    assert jaxtrace.sites() == {} and jaxtrace.fetches() == {}


def test_tracer_counts_compiles_per_shape(monkeypatch):
    monkeypatch.setenv("DIFACTO_JAXTRACE", "1")
    jaxtrace.reset()
    import jax.numpy as jnp
    f = jaxtrace.jit(lambda x: x + 1)
    f(jnp.ones(3))
    f(jnp.ones(3))
    f(jnp.ones(4))          # new shape -> new compile
    (site, rec), = jaxtrace.sites().items()
    assert site.startswith("tests/test_jaxflow.py:")
    assert rec["calls"] == 3
    assert rec["compiles"] == 2
    jaxtrace.reset()


def test_tracer_weak_scalars_do_not_overcount(monkeypatch):
    monkeypatch.setenv("DIFACTO_JAXTRACE", "1")
    jaxtrace.reset()
    import jax.numpy as jnp
    g = jaxtrace.jit(lambda x, a: x * a)
    arr = jnp.ones(3)
    g(arr, 2.0)
    g(arr, 3.0)             # weak-typed float: same compiled program
    (_, rec), = jaxtrace.sites().items()
    assert rec["calls"] == 2
    assert rec["compiles"] == 1
    jaxtrace.reset()


def test_tracer_statics_key_by_value(monkeypatch):
    monkeypatch.setenv("DIFACTO_JAXTRACE", "1")
    jaxtrace.reset()
    import jax.numpy as jnp

    def pad(x, n):
        return jnp.zeros(n).at[: x.shape[0]].set(x)

    h = jaxtrace.jit(pad, static_argnums=(1,))
    arr = jnp.ones(3)
    h(arr, 8)
    h(arr, 8)
    h(arr, 16)              # new static value -> new compile
    (_, rec), = jaxtrace.sites().items()
    assert rec["calls"] == 3
    assert rec["compiles"] == 2
    assert len(rec["keys"]) == 2
    jaxtrace.reset()


def test_pjit_same_site_identity_and_counts(monkeypatch):
    """jaxtrace.pjit (sharded-jit creation, ISSUE 12) records the SAME
    relpath:lineno site identity as jaxtrace.jit — mesh-sharded
    programs stay inside the compile/transfer gates."""
    monkeypatch.setenv("DIFACTO_JAXTRACE", "1")
    jaxtrace.reset()
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("dp", "fs"))
    sh = NamedSharding(mesh, P("fs"))
    f = jaxtrace.pjit(lambda x: x * 2, out_shardings=sh)
    x = jax.device_put(jnp.ones(8), sh)
    f(x)
    f(x)
    (site, rec), = jaxtrace.sites().items()
    assert site.startswith("tests/test_jaxflow.py:")
    assert rec["calls"] == 2 and rec["compiles"] == 1
    jaxtrace.reset()


def test_pjit_site_in_static_model(repo_model):
    """The capacity bench's jaxtrace.pjit creation sites (the fs
    capacity sweep + the bounded-delay sweep, parallel/capacity.py)
    are discovered by the static model under the same identity scheme
    and are warm-declared (reasoned suppressions — one compile per fs
    rung / one per delay sweep)."""
    cap_sites = [s for s in repo_model.sites
                 if s.startswith("difacto_tpu/parallel/capacity.py:")]
    assert len(cap_sites) == 2, cap_sites
    for site in cap_sites:
        assert site in repo_model.known_warm(), site
    # its declared fetch point is known too
    assert any(s.startswith("difacto_tpu/parallel/capacity.py:")
               for s in repo_model.declared_fetches())


def test_fetch_counts_and_dump_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv("DIFACTO_JAXTRACE", "1")
    jaxtrace.reset()
    import jax.numpy as jnp
    f = jaxtrace.jit(lambda x: x * 2)
    y = f(jnp.ones(4))
    for _ in range(3):
        got = jaxtrace.fetch(y, point="unit.sync")
    assert isinstance(got, np.ndarray) and got.shape == (4,)
    (fsite, frec), = jaxtrace.fetches().items()
    assert frec == {"point": "unit.sync", "count": 3}
    out = tmp_path / "jax.json"
    jaxtrace.dump(out)
    loaded = jaxtrace.load(out)
    assert fsite in loaded["fetches"]
    assert loaded["fetches"][fsite]["count"] == 3
    (site, rec), = loaded["sites"].items()
    assert rec["compiles"] == 1 and rec["calls"] == 1
    jaxtrace.reset()
    assert jaxtrace.sites() == {}


# ---------------------------------------------------------------------------
# the static model on this repo


def test_serve_jit_site_known_and_warm(repo_model):
    exec_sites = [s for s in repo_model.sites
                  if s.startswith("difacto_tpu/serve/executor.py:")]
    assert len(exec_sites) == 1, exec_sites
    assert repo_model.sites[exec_sites[0]].target_name == "packed_predict"
    assert exec_sites[0] in repo_model.known_warm()


def test_every_repo_site_is_warm_declared(repo_model):
    # the zero-findings scrub contract: every jit site is either proven
    # bounded or carries a reasoned jax-recompile suppression
    not_warm = set(repo_model.sites) - repo_model.known_warm()
    assert not_warm == set(), sorted(not_warm)


def test_serve_scores_fetch_is_declared(repo_model):
    declared = repo_model.declared_fetches()
    assert any(s.startswith("difacto_tpu/serve/executor.py:")
               for s in declared), sorted(declared)


def test_hot_roots_include_serve_dispatch_loop(repo_model):
    assert "difacto_tpu/serve/batcher.py::MicroBatcher._loop" \
        in repo_model.hot_roots


def test_model_json_shape(repo_model):
    doc = repo_model.to_json()
    assert doc["sites"] and doc["fetch_sites"] and doc["hot_roots"]
    for rec in doc["sites"].values():
        assert {"target", "bound", "static_argnums", "donate_argnums",
                "call_sites", "warm_bounded", "unbounded"} <= set(rec)


def test_jaxflow_rule_scoping_matches_changed_only_contract():
    # --changed-only narrows LOCAL rules to changed files while cross
    # rules always see the whole tree (cli.run_project contract): the
    # dtype pass is local, the three flow passes are cross
    rules = core.all_rules()
    assert not rules["jax-dtype64"].cross
    for rid in ("jax-recompile", "jax-host-sync", "jax-donate-flow"):
        assert rules[rid].cross


def test_rule_seconds_cover_jaxflow_passes(tmp_path, capsys):
    (tmp_path / "mod.py").write_text("import jax\n")
    rc = lint_main(["--root", str(tmp_path), "mod.py", "--format", "json",
                    "--rules",
                    "jax-recompile,jax-host-sync,jax-donate-flow,"
                    "jax-dtype64"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert set(doc["rule_seconds"]) == {
        "jax-recompile", "jax-host-sync", "jax-donate-flow",
        "jax-dtype64"}


# ---------------------------------------------------------------------------
# jitmap


def _load_jitmap():
    spec = importlib.util.spec_from_file_location(
        "difacto_jitmap", REPO_ROOT / "tools" / "jitmap.py")
    jitmap = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(jitmap)
    return jitmap


def test_jitmap_static_build_and_text(repo_model):
    jitmap = _load_jitmap()
    graph = jitmap.build(REPO_ROOT)
    assert graph["sites"] and graph["fetch_sites"]
    txt = jitmap.to_text(graph)
    assert "packed_predict" in txt
    assert "declared fetch points" in txt


def test_jitmap_check_fails_on_unknown_dynamic_site(tmp_path, capsys,
                                                    repo_model):
    jitmap = _load_jitmap()
    good_site = sorted(repo_model.sites)[0]
    dump = tmp_path / "jax.json"
    dump.write_text(json.dumps({
        "version": 1,
        "sites": {
            good_site: {"label": "x", "calls": 3, "compiles": 1,
                        "keys": []},
            "nowhere.py:1": {"label": "ghost", "calls": 1,
                             "compiles": 1, "keys": []},
        },
        "fetches": {"nowhere.py:2": {"point": "ghost", "count": 1}},
    }))
    rc = jitmap.main(["--root", str(REPO_ROOT),
                      "--dynamic", str(dump), "--check"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "UNKNOWN-SITES: nowhere.py:1" in out
    assert "UNKNOWN-FETCHES: nowhere.py:2" in out

    graph = jitmap.build(REPO_ROOT, dump)
    assert graph["unknown_sites"] == ["nowhere.py:1"]
    assert graph["unknown_fetches"] == ["nowhere.py:2"]
    assert good_site not in graph["unknown_sites"]


def test_jitmap_check_passes_on_model_subset(tmp_path, repo_model):
    jitmap = _load_jitmap()
    good_site = sorted(repo_model.sites)[0]
    good_fetch = sorted(repo_model.declared_fetches())[0]
    dump = tmp_path / "jax.json"
    dump.write_text(json.dumps({
        "version": 1,
        "sites": {good_site: {"label": "x", "calls": 5, "compiles": 1,
                              "keys": []}},
        "fetches": {good_fetch: {"point": "p", "count": 5}},
    }))
    rc = jitmap.main(["--root", str(REPO_ROOT),
                      "--dynamic", str(dump), "--check"])
    assert rc == 0


# ---------------------------------------------------------------------------
# the tier-1 JAXTRACE gate: dynamic compiles ⊆ static warm set on the
# REAL serve path, compiles stop growing after warm-up, transfers only
# at declared fetch points


def test_jaxtrace_gate_serve_steady_state(tmp_path, repo_model):
    warm_dump = tmp_path / "warm.json"
    final_dump = tmp_path / "final.json"
    scenario = textwrap.dedent(f"""
        import numpy as np
        from difacto_tpu.updaters.sgd_updater import SGDUpdaterParam
        from difacto_tpu.store.local import SlotStore
        from difacto_tpu.serve.batcher import MicroBatcher
        from difacto_tpu.serve.executor import PredictExecutor
        from difacto_tpu.data.rowblock import RowBlock
        from difacto_tpu.utils import jaxtrace

        store = SlotStore(SGDUpdaterParam(V_dim=4, hash_capacity=1024))
        ex = PredictExecutor(store)
        # batch_size == rows per request: each submit flushes exactly
        # one deterministic 4-row batch through the dispatch loop
        bat = MicroBatcher(ex.predict_scores, batch_size=4, queue_cap=64)
        bat.start()

        def blk():
            idx = (np.arange(16, dtype=np.uint32) * 7) % 97
            off = np.arange(0, 17, 4, dtype=np.int64)
            return RowBlock(offset=off,
                            label=np.zeros(4, np.float32),
                            index=idx, value=None, weight=None)

        for _ in range(3):          # warm-up: first bucket compiles
            fut = bat.submit(blk())
            assert fut is not None
            fut.result(60)
        jaxtrace.dump({str(warm_dump)!r})
        for _ in range(10):         # steady state: hits only
            fut = bat.submit(blk())
            assert fut is not None
            fut.result(60)
        bat.close()
        assert ex.stats()["dispatches"] == 13
        jaxtrace.dump({str(final_dump)!r})
    """)
    env = dict(os.environ, DIFACTO_JAXTRACE="1", JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", scenario],
                       cwd=REPO_ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    warm = jaxtrace.load(warm_dump)
    final = jaxtrace.load(final_dump)
    assert warm["sites"], "warm-up must have exercised a jit site"

    serve_site = [s for s in final["sites"]
                  if s.startswith("difacto_tpu/serve/executor.py:")]
    assert serve_site, final["sites"]

    known_warm = repo_model.known_warm()
    declared = repo_model.declared_fetches()
    for site, rec in sorted(final["sites"].items()):
        # dynamic ⊆ static: the tracer and the model key sites the
        # same way, so an unknown site is a discovery blind spot
        assert site in repo_model.sites, \
            f"jit site {site} unknown to the static model"
        assert site in known_warm, \
            f"jit site {site} is not statically warm-declared"
        # steady state: compiles frozen at the warm-up count while
        # calls kept growing — zero steady-state recompiles, proven
        w = warm["sites"].get(site)
        assert w is not None, f"{site} first compiled AFTER warm-up"
        assert rec["compiles"] == w["compiles"], \
            f"{site} recompiled in steady state: " \
            f"{w['compiles']} -> {rec['compiles']}"
        # ... while the serve program kept being called (a set-up
        # program — the store's jitted init — runs once, before warm-up)
        if site in serve_site:
            assert rec["calls"] > w["calls"]
    for site, rec in sorted(final["fetches"].items()):
        assert site in declared, \
            f"device->host transfer at undeclared site {site} " \
            f"({rec['point']})"
    # the serve loop's one declared sync actually fired per dispatch
    scores = [rec for rec in final["fetches"].values()
              if rec["point"] == "serve.scores"]
    assert scores and scores[0]["count"] == 13


# ---------------------------------------------------------------------------
# device-trace annotation (the PR 4 leftover): spans wrap
# jax.profiler.TraceAnnotation / StepTraceAnnotation under
# DIFACTO_TRACE_DEVICE, profiler artifacts land in the logdir


def test_trace_device_spans_and_profile_artifacts(tmp_path):
    logdir = tmp_path / "device"
    span_file = tmp_path / "trace.json"
    scenario = textwrap.dedent("""
        import jax
        import jax.numpy as jnp
        from difacto_tpu.obs import trace

        assert trace.active(), "DIFACTO_TRACE must activate spans"
        with trace.span("gate.step", step_num=1):
            jax.jit(lambda x: x + 1)(jnp.ones(8)).block_until_ready()
        with trace.span("gate.host"):
            pass
    """)
    env = dict(os.environ,
               DIFACTO_TRACE=str(span_file),
               DIFACTO_TRACE_DEVICE=str(logdir),
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", scenario],
                       cwd=REPO_ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    doc = json.loads(span_file.read_text())
    names = {ev["name"] for ev in doc["traceEvents"]}
    assert {"gate.step", "gate.host"} <= names
    profile_files = [p for p in logdir.rglob("*") if p.is_file()]
    assert profile_files, \
        "jax profiler wrote nothing under DIFACTO_TRACE_DEVICE"


def test_trace_device_absent_knob_keeps_spans_plain(tmp_path,
                                                    monkeypatch):
    # the module never imports jax for a span: where jax is not loaded
    # (a producer worker) spans stay the cheap host-only path, and no
    # profiler session is started without the knob
    import sys

    from difacto_tpu.obs import trace
    monkeypatch.delenv("DIFACTO_TRACE_DEVICE", raising=False)
    assert trace._device_on is False
    monkeypatch.setitem(sys.modules, "jax.profiler", None)
    assert trace._annotation("gate.host", {}) is None
    with trace.span("gate.host") as sid:
        pass
    assert sid == 0 or trace.active()
