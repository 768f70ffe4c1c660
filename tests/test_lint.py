"""difacto-lint fixture suite (docs/static_analysis.md).

Three layers, all tier-1:

- **per-rule fixtures** — for every local rule one true-positive
  snippet that must be flagged EXACTLY once, plus negative and
  suppressed twins that must be clean;
- **cross-rule fixtures** — tiny synthetic packages exercising each
  registry-drift rule's drifted and in-sync shapes;
- **the machinery** — JSON output schema, baseline add/expire,
  suppression pragma placement, exit codes, parse errors — and the
  the-tree-is-clean gate: the analyzer over this very repo must report
  zero unsuppressed, non-baselined findings.

Everything runs the analyzer in-process (no subprocesses): the whole
suite is a few hundred milliseconds.
"""

import json
import pathlib
import textwrap

import pytest

from difacto_tpu.analysis import core
from difacto_tpu.analysis.cli import main as lint_main

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent


def lint_src(tmp_path, src, rules, filename="mod.py"):
    """Run selected rules over one source snippet; return active
    findings."""
    (tmp_path / filename).write_text(textwrap.dedent(src))
    project = core.Project(tmp_path, [filename])
    res = core.run_project(project, rules)
    return res.active


# ---------------------------------------------------------------------------
# local-rule fixtures: (rule, true-positive, negative). The suppressed
# twin is generated from the true positive by pragma-tagging every line.

LOCAL_FIXTURES = [
    ("thread-daemon", """
        import threading
        def f():
            t = threading.Thread(target=print)
            t.start()
     """, """
        import threading
        def f():
            t = threading.Thread(target=print, daemon=True)
            t.start()
        def g():
            t = threading.Thread(target=print)
            t.start()
            t.join()
     """),
    ("lock-release", """
        import threading
        lock = threading.Lock()
        def f():
            lock.acquire()
            print("critical")
            lock.release()  # an exception above leaks the lock
     """, """
        import threading
        lock = threading.Lock()
        def f():
            lock.acquire()
            try:
                print("critical")
            finally:
                lock.release()
        def g():
            with lock:
                print("critical")
        def h():
            if not lock.acquire(timeout=1):
                return
            try:
                print("critical")
            finally:
                lock.release()
     """),
    ("resource-close", """
        import socket
        def f():
            s = socket.socket()
            s.connect(("h", 1))
     """, """
        import socket
        def ok_with():
            with socket.socket() as s:
                s.connect(("h", 1))
        def ok_finally():
            s = socket.socket()
            try:
                s.connect(("h", 1))
            finally:
                s.close()
        def ok_escapes():
            s = socket.socket()
            return s
        def ok_handed_off(pool):
            s = socket.socket()
            pool.add(s)
     """),
    ("wall-clock", """
        import time
        def f():
            t0 = time.time()
            return time.monotonic() - t0
     """, """
        import time
        def f():
            t0 = time.monotonic()
            return time.monotonic() - t0
     """),
    ("broad-except", """
        def f():
            try:
                g()
            except Exception:
                pass
     """, """
        import logging
        log = logging.getLogger(__name__)
        def ok_logs():
            try:
                g()
            except Exception as e:
                log.warning("g failed: %s", e)
        def ok_reraises():
            try:
                g()
            except Exception:
                raise RuntimeError("context")
        def ok_captures():
            errs = []
            try:
                g()
            except BaseException as e:
                errs.append(e)
        def ok_narrow():
            try:
                g()
            except ValueError:
                pass
     """),
    ("jax-donate", """
        import jax
        def run(step, x):
            step2 = jax.jit(step, donate_argnums=(0,))
            y = step2(x)
            return x
     """, """
        import jax
        def run(step, x):
            step2 = jax.jit(step, donate_argnums=(0,))
            x = step2(x)
            return x
     """),
    ("jax-jit-capture", """
        import jax
        class Model:
            def make(self):
                @jax.jit
                def inner(a):
                    return a * self.scale
                return inner
     """, """
        import jax
        class Model:
            def make(self):
                scale = self.scale
                @jax.jit
                def inner(a, s):
                    return a * s
                return inner
     """),
    ("jax-host-call", """
        import jax
        import numpy as np
        @jax.jit
        def f(a):
            return np.sum(a)
     """, """
        import jax
        import jax.numpy as jnp
        import numpy as np
        @jax.jit
        def f(a):
            return jnp.sum(a.astype(np.float32))
        def host(a):
            return np.sum(a)
     """),
    ("cond-wait-while", """
        import threading
        cond = threading.Condition()
        def f(ready):
            with cond:
                if not ready:
                    cond.wait()
     """, """
        import threading
        cond = threading.Condition()
        def ok_while(ready):
            with cond:
                while not ready():
                    cond.wait()
        def ok_wait_for(ready):
            with cond:
                cond.wait_for(ready)
     """),
    ("jax-dtype64", """
        import jax
        import numpy as np
        @jax.jit
        def f(a):
            return a * np.float64(2.0)
     """, """
        import jax
        import jax.numpy as jnp
        import numpy as np
        @jax.jit
        def ok_f32(a):
            return a * jnp.float32(2.0)
        def host_exact(xs):
            # host-side float64 accumulation is deliberate (parsers,
            # DCN wires) — never flagged outside jit targets
            return np.float64(2.0) * np.sum(xs)
     """),
]


@pytest.mark.parametrize("rule,bad,good",
                         LOCAL_FIXTURES,
                         ids=[r for r, _, _ in LOCAL_FIXTURES])
def test_local_rule_true_positive_fires_exactly_once(tmp_path, rule, bad,
                                                     good):
    found = lint_src(tmp_path, bad, [rule])
    assert len(found) == 1, \
        f"{rule}: expected exactly 1 finding, got {found}"
    assert found[0].rule == rule
    assert found[0].line > 0 and found[0].message


@pytest.mark.parametrize("rule,bad,good",
                         LOCAL_FIXTURES,
                         ids=[r for r, _, _ in LOCAL_FIXTURES])
def test_local_rule_negative_fixture_is_clean(tmp_path, rule, bad, good):
    assert lint_src(tmp_path, good, [rule]) == []


@pytest.mark.parametrize("rule,bad,good",
                         LOCAL_FIXTURES,
                         ids=[r for r, _, _ in LOCAL_FIXTURES])
def test_local_rule_suppression_pragma_silences(tmp_path, rule, bad, good):
    tagged = "\n".join(
        line + f"  # lint: ok({rule})" if line.strip() else line
        for line in textwrap.dedent(bad).splitlines())
    (tmp_path / "mod.py").write_text(tagged)
    res = core.run_project(core.Project(tmp_path, ["mod.py"]), [rule])
    assert res.active == []
    assert sum(f.suppressed for f in res.findings) == 1


def test_standalone_pragma_covers_next_code_line(tmp_path):
    src = ("import time\n"
           "# lint: ok(wall-clock) timestamp-of-record\n"
           "STAMP = time.time()\n")
    (tmp_path / "mod.py").write_text(src)
    res = core.run_project(core.Project(tmp_path, ["mod.py"]),
                           ["wall-clock"])
    assert res.active == [] and len(res.findings) == 1


def test_jit_method_self_argument_flagged(tmp_path):
    found = lint_src(tmp_path, """
        import jax
        class Model:
            @jax.jit
            def step(self, x):
                return x
     """, ["jax-jit-capture"])
    assert len(found) == 1 and "traced" in found[0].message


def test_parse_error_is_a_finding(tmp_path):
    found = lint_src(tmp_path, "def broken(:\n", ["wall-clock"])
    assert [f.rule for f in found] == ["parse-error"]


# ---------------------------------------------------------------------------
# cross-rule fixtures: tiny synthetic projects


_PROJ_SEQ = [0]


def make_project(tmp_path, files, **kw):
    _PROJ_SEQ[0] += 1
    root = tmp_path / f"proj{_PROJ_SEQ[0]}"
    for rel, text in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(text))
    lint = sorted({rel.split("/")[0] for rel in files if rel.endswith(".py")
                   and not rel.startswith(("tests/", "docs/"))})
    return core.Project(root, lint, **kw)


def test_fault_registry_drift_and_sync(tmp_path):
    proj = make_project(tmp_path, {
        "pkg/mod.py": """
            from utils import faultinject
            def work():
                faultinject.fire("my.point")
        """,
    })
    rules = ["fault-registry"]
    found = core.run_project(proj, rules).active
    msgs = "\n".join(f.message for f in found)
    assert len(found) == 2
    assert "never armed" in msgs and "undocumented" in msgs

    proj = make_project(tmp_path, {
        "pkg/mod.py": """
            from utils import faultinject
            def work():
                faultinject.fire("my.point")
        """,
        "tests/test_mod.py": 'FAULTS = "my.point:err@1"\n',
        "docs/chaos.md": "fault points: `my.point` tears the thing\n",
    })
    assert core.run_project(proj, rules).active == []


def test_fault_registry_rejects_unknown_kind(tmp_path):
    proj = make_project(tmp_path, {
        "pkg/mod.py": """
            from utils import faultinject
            faultinject.fire("my.point")
        """,
        "tests/test_mod.py": 'FAULTS = "my.point:explode@1"\n',
        "docs/chaos.md": "`my.point`\n",
    })
    found = core.run_project(proj, ["fault-registry"]).active
    assert len(found) == 1 and "explode" in found[0].message


def test_metric_registry_type_conflict_and_missing_doc(tmp_path):
    proj = make_project(tmp_path, {
        "pkg/a.py": 'from obs import counter\n'
                    'c = counter("my_widgets_total", "desc")\n',
        "pkg/b.py": 'from obs import gauge\n'
                    'g = gauge("my_widgets_total", "desc")\n',
        "docs/observability.md": "catalog: `my_widgets_total`\n",
    })
    found = core.run_project(proj, ["metric-registry"]).active
    assert len(found) == 1
    assert "one name must keep one type" in found[0].message

    proj = make_project(tmp_path, {
        "pkg/a.py": 'from obs import counter\n'
                    'c = counter("my_widgets_total", "desc")\n',
        "docs/observability.md": "catalog has nothing\n",
    })
    found = core.run_project(proj, ["metric-registry"]).active
    assert len(found) == 1 and "missing from" in found[0].message


def test_control_registry_two_way_match(tmp_path):
    files = {
        "srv/server.py": 'HANDLED = ("#stats", "#orphan")\n',
        "cli/client.py": 'SENT = ("#stats", "#lost")\n',
        "docs/wire.md": "`#stats` `#orphan` `#lost`\n",
    }
    proj = make_project(
        tmp_path, files,
        handler_files=("srv/server.py",), sender_files=("cli/client.py",))
    found = core.run_project(proj, ["control-registry"]).active
    by_msg = {f.message.split('"')[1]: f.message for f in found}
    assert set(by_msg) == {"#orphan", "#lost"}
    assert "ever sends" in by_msg["#orphan"]
    assert "never handles" in by_msg["#lost"]

    files["srv/server.py"] = 'HANDLED = ("#stats",)\n'
    files["cli/client.py"] = 'SENT = ("#stats",)\n'
    proj = make_project(
        tmp_path, files,
        handler_files=("srv/server.py",), sender_files=("cli/client.py",))
    assert core.run_project(proj, ["control-registry"]).active == []


def test_control_registry_requires_docs_entry(tmp_path):
    proj = make_project(
        tmp_path,
        {"srv/server.py": 'H = "#stats"\n',
         "cli/client.py": 'S = "#stats"\n',
         "docs/wire.md": "nothing here\n"},
        handler_files=("srv/server.py",), sender_files=("cli/client.py",))
    found = core.run_project(proj, ["control-registry"]).active
    assert len(found) == 1 and "undocumented" in found[0].message


def test_config_registry_undeclared_knob_and_env(tmp_path):
    proj = make_project(tmp_path, {
        "pkg/mod.py": """
            import os
            from config import Param
            class FooParam(Param):
                declared_knob: int = 1
            def read(kwargs):
                a = next(v for k, v in kwargs if k == "declared_knob")
                b = next(v for k, v in kwargs if k == "mystery_knob")
                return a, b, os.environ.get("DIFACTO_SECRET")
        """,
        "docs/conf.md": "knobs: declared_knob\n",
    })
    found = core.run_project(proj, ["config-registry"]).active
    msgs = "\n".join(f.message for f in found)
    assert len(found) == 2
    assert "mystery_knob" in msgs and "DIFACTO_SECRET" in msgs

    proj = make_project(tmp_path, {
        "pkg/mod.py": """
            import os
            from config import Param
            class FooParam(Param):
                declared_knob: int = 1
            def read(kwargs):
                a = next(v for k, v in kwargs if k == "declared_knob")
                return a, os.environ.get("DIFACTO_SECRET")
        """,
        "docs/conf.md": "knobs: declared_knob, DIFACTO_SECRET\n",
    })
    assert core.run_project(proj, ["config-registry"]).active == []


# ---------------------------------------------------------------------------
# interprocedural concurrency rules (analysis/concurrency.py)


def test_lock_order_cycle_detected_single_file(tmp_path):
    found = lint_src(tmp_path, """
        import threading
        A = threading.Lock()
        B = threading.Lock()
        def fwd():
            with A:
                with B:
                    pass
        def rev():
            with B:
                with A:
                    pass
     """, ["lock-order"])
    assert len(found) == 1
    msg = found[0].message
    assert "lock-order cycle" in msg
    # BOTH witness paths ride the finding
    assert "fwd" in msg and "rev" in msg


def test_lock_order_consistent_order_is_clean(tmp_path):
    assert lint_src(tmp_path, """
        import threading
        A = threading.Lock()
        B = threading.Lock()
        def f():
            with A:
                with B:
                    pass
        def g():
            with A:
                with B:
                    pass
     """, ["lock-order"]) == []


def test_lock_order_suppression(tmp_path):
    src = textwrap.dedent("""
        import threading
        A = threading.Lock()
        B = threading.Lock()
        def fwd():
            with A:  # lint: ok(lock-order) fixture
                with B:
                    pass
        def rev():
            with B:
                with A:
                    pass
    """)
    (tmp_path / "mod.py").write_text(src)
    res = core.run_project(core.Project(tmp_path, ["mod.py"]),
                           ["lock-order"])
    assert res.active == []
    assert sum(f.suppressed for f in res.findings) == 1


def test_lock_order_interprocedural_deadlock_package(tmp_path):
    """The synthetic two-lock deadlock: m1 takes A then calls into m2
    which takes B; m2 also takes B then calls back into m1 for A. The
    cycle spans modules — only the call graph can see it."""
    proj = make_project(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/m1.py": """
            import threading
            from pkg import m2
            A = threading.Lock()
            def outer():
                with A:
                    m2.take_b()
            def take_a():
                with A:
                    pass
        """,
        "pkg/m2.py": """
            import threading
            from pkg import m1
            B = threading.Lock()
            def take_b():
                with B:
                    pass
            def rev():
                with B:
                    m1.take_a()
        """,
    })
    found = core.run_project(proj, ["lock-order"]).active
    assert len(found) == 1
    msg = found[0].message
    assert "m1.py::A" in msg and "m2.py::B" in msg
    assert "outer" in msg and "rev" in msg  # one witness per direction


def test_lock_order_thread_target_does_not_propagate(tmp_path):
    """Held locks stop at a Thread(target=...) hand-off: the target
    runs on another thread, so A-held-while-spawning does not order A
    before anything the spawned thread takes."""
    assert lint_src(tmp_path, """
        import threading
        A = threading.Lock()
        B = threading.Lock()
        def take_b():
            with B:
                pass
        def take_a():
            with A:
                pass
        def spawn():
            with A:
                t = threading.Thread(target=take_b, daemon=True)
                t.start()
        def rev():
            with B:
                take_a()
     """, ["lock-order"]) == []


def test_lock_blocking_direct_and_negative(tmp_path):
    found = lint_src(tmp_path, """
        import threading
        L = threading.Lock()
        def f(conn):
            with L:
                conn.sendall(b"x")
     """, ["lock-blocking"])
    assert len(found) == 1 and "sendall" in found[0].message

    assert lint_src(tmp_path, """
        import threading
        import queue
        L = threading.Lock()
        q = queue.Queue()
        def ok_outside(conn):
            with L:
                pass
            conn.sendall(b"x")
        def ok_timed():
            with L:
                return q.get(timeout=0.1)
        def ok_nowait():
            with L:
                q.put_nowait(1)
     """, ["lock-blocking"]) == []


def test_lock_blocking_queue_without_timeout(tmp_path):
    found = lint_src(tmp_path, """
        import threading
        import queue
        L = threading.Lock()
        q = queue.Queue()
        def f():
            with L:
                return q.get()
     """, ["lock-blocking"])
    assert len(found) == 1
    assert "queue.get() without timeout" in found[0].message


def test_lock_blocking_interprocedural(tmp_path):
    found = lint_src(tmp_path, """
        import threading
        import time
        L = threading.Lock()
        def helper():
            time.sleep(0.1)
        def f():
            with L:
                helper()
     """, ["lock-blocking"])
    assert len(found) == 1
    msg = found[0].message
    assert "time.sleep" in msg and "helper" in msg


def test_lock_blocking_suppression(tmp_path):
    src = textwrap.dedent("""
        import threading
        L = threading.Lock()
        def f(conn):
            with L:
                conn.sendall(b"x")  # lint: ok(lock-blocking) fixture
    """)
    (tmp_path / "mod.py").write_text(src)
    res = core.run_project(core.Project(tmp_path, ["mod.py"]),
                           ["lock-blocking"])
    assert res.active == []
    assert sum(f.suppressed for f in res.findings) == 1


# ---------------------------------------------------------------------------
# --changed-only incremental mode


def test_changed_only_limits_local_rules_not_cross(tmp_path, capsys):
    """Local rules narrow to changed files; the concurrency rules still
    see the whole tree (a cycle in an UNCHANGED file must still fail)."""
    import subprocess
    root = tmp_path / "repo"
    root.mkdir()

    def git(*args):
        subprocess.run(
            ["git", "-C", str(root), "-c", "user.email=t@t",
             "-c", "user.name=t", *args],
            check=True, capture_output=True)

    (root / "a.py").write_text(textwrap.dedent("""
        import threading
        import time
        T = time.time()
        A = threading.Lock()
        B = threading.Lock()
        def fwd():
            with A:
                with B:
                    pass
        def rev():
            with B:
                with A:
                    pass
    """))
    (root / "b.py").write_text("import time\nU = time.monotonic()\n")
    git("init", "-q")
    git("add", ".")
    git("commit", "-q", "-m", "seed")
    (root / "b.py").write_text("import time\nU = time.time()\n")

    args = ["--root", str(root), ".", "--rules", "wall-clock,lock-order",
            "--format", "json"]
    rc = lint_main(args)
    full = json.loads(capsys.readouterr().out)
    assert rc == 1 and full["counts"]["active"] == 3  # 2 wall + 1 cycle

    rc = lint_main(args + ["--changed-only"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1
    by_rule = {}
    for f in doc["findings"]:
        by_rule.setdefault(f["rule"], []).append(f["path"])
    # a.py's wall-clock finding is pre-existing -> skipped; b.py's is
    # new -> reported; the cycle lives in unchanged a.py -> reported
    assert by_rule == {"wall-clock": ["b.py"], "lock-order": ["a.py"]}


# ---------------------------------------------------------------------------
# locktrace: the runtime lock sentinel (utils/locktrace.py)


def test_locktrace_records_and_roundtrips_across_threads(tmp_path,
                                                         monkeypatch):
    import threading

    from difacto_tpu.utils import locktrace

    monkeypatch.setenv("DIFACTO_LOCKTRACE", "1")
    locktrace.reset()
    a = locktrace.mutex()
    b = locktrace.mutex()

    def nest():
        with a:
            with b:
                pass

    t = threading.Thread(target=nest, daemon=True)
    t.start()
    t.join()
    nest()  # the main thread takes the same order

    edges = locktrace.edges()
    assert len(edges) == 1
    ((src, dst), count), = edges.items()
    assert count == 2  # one edge per thread, same sites
    assert src != dst
    assert all(s.startswith("tests/test_lint.py:") for s in (src, dst))
    assert locktrace.sites()[src] == "Lock"

    out = tmp_path / "locks.json"
    locktrace.dump(out)
    data = locktrace.load(out)
    assert data["edges"] == edges
    assert data["sites"][dst] == "Lock"
    locktrace.reset()
    assert locktrace.edges() == {}


def test_locktrace_release_order_and_disabled(monkeypatch):
    import threading

    from difacto_tpu.utils import locktrace

    monkeypatch.delenv("DIFACTO_LOCKTRACE", raising=False)
    raw = locktrace.mutex()
    assert isinstance(raw, type(threading.Lock()))

    monkeypatch.setenv("DIFACTO_LOCKTRACE", "1")
    locktrace.reset()
    a = locktrace.mutex()
    b = locktrace.mutex()
    # hand-over-hand: a release between acquires drops the edge source
    a.acquire()
    a.release()
    b.acquire()
    b.release()
    assert locktrace.edges() == {}
    with a:
        with b:
            assert b.locked()
    assert len(locktrace.edges()) == 1


def test_locktrace_dynamic_edges_subset_of_static_graph(monkeypatch):
    """The tier-1 gate: every acquisition-order edge a real execution
    records must already exist in the static lock-order graph (the
    static model over-approximates; a dynamic edge it missed is a
    callgraph blind spot to fix), and the static graph of this tree is
    cycle-free with an empty baseline."""
    import numpy as np

    from difacto_tpu.analysis.cli import DEFAULT_PATHS
    from difacto_tpu.analysis.concurrency import get_model
    from difacto_tpu.data.rowblock import RowBlock
    from difacto_tpu.serve.batcher import MicroBatcher, ServeStats
    from difacto_tpu.utils import locktrace

    monkeypatch.setenv("DIFACTO_LOCKTRACE", "1")
    locktrace.reset()
    blk = RowBlock(offset=np.array([0, 1], dtype=np.int64),
                   label=np.zeros(1, dtype=np.float32),
                   index=np.zeros(1, dtype=np.uint32),
                   value=None, weight=None)
    stats = ServeStats()
    bat = MicroBatcher(lambda x: np.zeros(x.size, np.float32),
                       batch_size=2, queue_cap=1, stats=stats)
    try:
        assert bat.submit(blk) is not None
        # second row overflows queue_cap=1: the shed counters tick
        # UNDER the batcher admission lock — a real nested acquisition
        assert bat.submit(blk) is None
        stats.record_latency(0.001)
        stats.snapshot()
    finally:
        bat.close()

    edges = locktrace.edges()
    assert edges, "the scenario must actually nest traced locks"

    project = core.Project(
        REPO_ROOT, [p for p in DEFAULT_PATHS if (REPO_ROOT / p).exists()])
    model = get_model(project)
    assert model.cycles == [], \
        f"static lock-order graph has cycles: {model.cycles}"
    site2lock = {f"{li.path}:{li.line}": lid
                 for lid, li in model.locks.items()}
    for a, b in edges:
        assert a in site2lock, \
            f"dynamic lock site {a} unknown to the static model"
        assert b in site2lock, \
            f"dynamic lock site {b} unknown to the static model"
        edge = (site2lock[a], site2lock[b])
        assert edge in model.edges, \
            f"observed edge {edge} missing from the static graph — " \
            f"callgraph blind spot"


# ---------------------------------------------------------------------------
# lockmap: merged static + dynamic graph (tools/lockmap.py)


def test_lockmap_merges_static_and_dynamic(tmp_path, monkeypatch):
    import importlib.util

    from difacto_tpu.utils import locktrace

    # tools/ is not a package: load lockmap by path
    spec = importlib.util.spec_from_file_location(
        "difacto_lockmap", REPO_ROOT / "tools" / "lockmap.py")
    lockmap = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lockmap)

    monkeypatch.setenv("DIFACTO_LOCKTRACE", "1")
    locktrace.reset()
    import numpy as np

    from difacto_tpu.data.rowblock import RowBlock
    from difacto_tpu.serve.batcher import MicroBatcher, ServeStats
    blk = RowBlock(offset=np.array([0, 1], dtype=np.int64),
                   label=np.zeros(1, dtype=np.float32),
                   index=np.zeros(1, dtype=np.uint32),
                   value=None, weight=None)
    bat = MicroBatcher(lambda x: np.zeros(x.size, np.float32),
                       batch_size=2, queue_cap=1, stats=ServeStats())
    try:
        bat.submit(blk)
        bat.submit(blk)
    finally:
        bat.close()
    dump = tmp_path / "trace.json"
    locktrace.dump(dump)

    graph = lockmap.build(REPO_ROOT, dump)
    assert graph["cycles"] == []
    assert graph["dynamic_only"] == []
    assert graph["confirmed"], "dynamic edges must confirm static ones"
    dot = lockmap.to_dot(graph)
    assert "digraph lockmap" in dot and "confirmed" in dot
    doc = lockmap.to_json(graph)
    assert doc["dynamic_edges"] and doc["locks"]


# ---------------------------------------------------------------------------
# machinery: output formats, baseline, exit codes


def _bad_tree(tmp_path):
    (tmp_path / "mod.py").write_text(
        "import time\n\ndef f():\n    return time.time()\n")
    return tmp_path


def test_json_output_schema(tmp_path, capsys):
    _bad_tree(tmp_path)
    rc = lint_main(["--root", str(tmp_path), "mod.py", "--format", "json",
                    "--rules", "wall-clock"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert doc["version"] == core.JSON_VERSION
    assert set(doc["counts"]) == {"files", "total", "active", "suppressed",
                                  "baselined", "expired_baseline"}
    assert doc["counts"] == {"files": 1, "total": 1, "active": 1,
                             "suppressed": 0, "baselined": 0,
                             "expired_baseline": 0}
    (finding,) = doc["findings"]
    assert set(finding) >= {"rule", "path", "line", "message",
                            "fingerprint", "suppressed", "baselined"}
    assert finding["rule"] == "wall-clock" and finding["path"] == "mod.py"
    assert isinstance(doc["expired_baseline"], list)


def test_github_format_annotations(tmp_path, capsys):
    _bad_tree(tmp_path)
    rc = lint_main(["--root", str(tmp_path), "mod.py", "--format", "github",
                    "--rules", "wall-clock"])
    out = capsys.readouterr().out
    assert rc == 1
    assert out.startswith("::error file=mod.py,line=4,")
    assert "wall-clock" in out


def test_baseline_add_then_expire(tmp_path, capsys):
    _bad_tree(tmp_path)
    baseline = tmp_path / ".lint-baseline.json"
    args = ["--root", str(tmp_path), "mod.py", "--rules", "wall-clock"]

    # findings fail the run until intentionally baselined
    assert lint_main(args) == 1
    assert lint_main(args + ["--write-baseline"]) == 0
    data = json.loads(baseline.read_text())
    assert data["version"] == core.BASELINE_VERSION
    assert len(data["findings"]) == 1
    capsys.readouterr()

    # grandfathered: same finding no longer fails, reported as baselined
    rc = lint_main(args + ["--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert doc["counts"]["baselined"] == 1 and doc["counts"]["active"] == 0

    # a NEW finding is not masked by the old baseline entry
    (tmp_path / "mod.py").write_text(
        "import time\n\ndef f():\n    return time.time()\n"
        "\ndef g():\n    return time.time()\n")
    assert lint_main(args) == 1
    capsys.readouterr()

    # the flagged line was fixed: entry expires, run stays green and
    # says so (regenerate with make lint-baseline)
    (tmp_path / "mod.py").write_text(
        "import time\n\ndef f():\n    return time.monotonic()\n")
    rc = lint_main(args + ["--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert doc["counts"]["active"] == 0
    assert len(doc["expired_baseline"]) == 1
    assert lint_main(args + ["--write-baseline"]) == 0
    assert json.loads(baseline.read_text())["findings"] == {}


def test_fingerprints_survive_line_drift(tmp_path):
    (tmp_path / "mod.py").write_text("import time\nt = time.time()\n")
    res = core.run_project(core.Project(tmp_path, ["mod.py"]),
                           ["wall-clock"])
    fp0 = res.findings[0].fingerprint()
    (tmp_path / "mod.py").write_text(
        "import time\n\n# a new comment above\n\nt = time.time()\n")
    res = core.run_project(core.Project(tmp_path, ["mod.py"]),
                           ["wall-clock"])
    assert res.findings[0].fingerprint() == fp0


def test_unknown_rule_is_usage_error(tmp_path, capsys):
    _bad_tree(tmp_path)
    assert lint_main(["--root", str(tmp_path), "mod.py",
                      "--rules", "no-such-rule"]) == 2


def test_list_rules_names_every_registered_rule(capsys):
    assert lint_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in core.all_rules():
        assert rid in out


# ---------------------------------------------------------------------------
# the gate: this tree is clean


def test_the_tree_is_clean(capsys):
    """`make lint` on the repo: zero unsuppressed, non-baselined
    findings. If this fails, run `python tools/lint.py` and either fix
    the finding, annotate it with a reasoned `# lint: ok(rule)`, or —
    for intentional grandfathering only — `make lint-baseline`."""
    rc = lint_main(["--root", str(REPO_ROOT), "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0, f"tree has lint findings: {doc['findings']}"
    assert doc["counts"]["active"] == 0
    # the suite itself keeps the analyzer honest: suppressions in the
    # tree must stay EXACTLY this number — bump deliberately when
    # adding one, prune when a fix removes one. Inventory (the v4
    # sweep re-justified every entry): 17 data-race (stop flags,
    # monotonic #stats counters, atomic reference swaps, single-owner
    # instances, pre-spawn publication, the router group's write-once
    # accept-thread handle and the Reporter's write-once monitor — PR 25
    # took three away with obs/trace's ``_annotate`` global and the
    # generator ``span`` whose reads of ``_active``/``_trace_id`` the
    # call graph followed from the producer threads; ``span`` is a class
    # now and ``with span(...)`` resolves to its ``__init__`` alone, a
    # documented blind spot, so those two pragmas stay in the file
    # unmatched. Deleting utils/profiling.py left ``Reporter.report``
    # the only ``report`` method, so the serve batcher's call resolved
    # precisely and the monitor's pre-spawn write needed its reason.
    # PR 31 deleted the one script that drove a ``ServeClient`` from
    # threads the call graph could see, so five of serve/client.py's
    # single-owner pragmas match no finding now; they stay, the
    # contract they state is the class's),
    # 6 wall-clock (cross-process file
    # timestamps x3, JSONL record stamps, trace-id entropy, run-dir
    # stamp), 2 lock-release (locktrace forwarding wrapper),
    # 1 lock-blocking (native build serialization), 14 jax-recompile
    # (pack/staging-time sticky caps the provenance model cannot chase
    # through payload tuples / the device cache — incl. the ISSUE 13
    # panel_raw device-dedup dispatch; warm-replay keys; the
    # capacity-scaling sweep's one-compile-per-fs-rung loop in
    # parallel/capacity.py — that loop IS the sweep). The two shard
    # rules run clean on the tree with no suppression.
    assert doc["counts"]["suppressed"] == 40
    import collections
    per_rule = collections.Counter(
        f["rule"] for f in doc["findings"] if f["suppressed"])
    assert dict(per_rule) == {
        "data-race": 17,
        "jax-recompile": 14,
        "wall-clock": 6,
        "lock-release": 2,
        "lock-blocking": 1,
    }


# ---------------------------------------------------------------------------
# thread-edge reference forms (analysis/callgraph.py _resolve_ref):
# partial / lambda / local-alias targets must produce thread roots


THREAD_FORMS = [
    ("partial", """
        import threading
        import functools
        def work():
            pass
        def spawn():
            t = threading.Thread(target=functools.partial(work, 1),
                                 daemon=True)
            t.start()
     """),
    ("lambda", """
        import threading
        def work():
            pass
        def spawn():
            t = threading.Thread(target=lambda: work(), daemon=True)
            t.start()
     """),
    ("alias", """
        import threading
        class W:
            def _loop(self):
                pass
            def spawn(self):
                run = self._loop
                t = threading.Thread(target=run, daemon=True)
                t.start()
     """),
]


@pytest.mark.parametrize("form,src", THREAD_FORMS,
                         ids=[f for f, _ in THREAD_FORMS])
def test_thread_target_forms_become_roots(tmp_path, form, src):
    """Regression for the callgraph thread-edge blind spot: every
    hand-off form resolves to a thread ROOT the race pass can see."""
    import textwrap as _tw

    from difacto_tpu.analysis.races import get_race_model
    (tmp_path / "mod.py").write_text(_tw.dedent(src))
    project = core.Project(tmp_path, ["mod.py"])
    model = get_race_model(project)
    target = "mod.py::W._loop" if form == "alias" else "mod.py::work"
    assert target in model.roots, \
        f"{form}: {target} missing from roots {sorted(model.roots)}"


def test_thread_edge_partial_does_not_propagate_locks(tmp_path):
    """A partial-wrapped thread target still breaks held-set
    propagation: no lock-order cycle through the spawn."""
    assert lint_src(tmp_path, """
        import threading
        import functools
        A = threading.Lock()
        B = threading.Lock()
        def take_b():
            with B:
                pass
        def take_a():
            with A:
                pass
        def spawn():
            with A:
                t = threading.Thread(target=functools.partial(take_b),
                                     daemon=True)
                t.start()
        def rev():
            with B:
                take_a()
     """, ["lock-order"]) == []


# ---------------------------------------------------------------------------
# data-race rule (analysis/races.py)


RACE_TP = """
    import threading
    class Worker:
        def __init__(self):
            self.n = 0
        def start(self):
            t = threading.Thread(target=self._loop, daemon=True)
            t.start()
        def _loop(self):
            self.n += 1
        def read(self):
            return self.n
"""


def test_data_race_two_root_true_positive_with_both_witnesses(tmp_path):
    found = lint_src(tmp_path, RACE_TP, ["data-race"])
    assert len(found) == 1
    msg = found[0].message
    assert "Worker.n" in msg
    # the two-site witness: the conflicting write and read, with roots
    # and held locks for each side
    assert "write at" in msg and "read at" in msg
    assert "_loop" in msg and "read" in msg
    assert "locks: none" in msg


def test_data_race_guarded_negative_infers_guardedby(tmp_path):
    src = """
        import threading
        class Worker:
            def __init__(self):
                self.n = 0
                self.mu = threading.Lock()
            def start(self):
                t = threading.Thread(target=self._loop, daemon=True)
                t.start()
            def _loop(self):
                with self.mu:
                    self.n += 1
            def read(self):
                with self.mu:
                    return self.n
    """
    assert lint_src(tmp_path, src, ["data-race"]) == []
    from difacto_tpu.analysis.races import get_race_model
    import textwrap as _tw
    (tmp_path / "g.py").write_text(_tw.dedent(src))
    model = get_race_model(core.Project(tmp_path, ["g.py"]))
    assert model.guarded_by.get("g.py::Worker.n") == \
        ("g.py::Worker.mu",)


def test_data_race_init_before_publish_negative(tmp_path):
    # cfg is written only in __init__ (and the spawn happens later):
    # published-then-immutable state is not a race however many
    # threads read it
    assert lint_src(tmp_path, """
        import threading
        class Worker:
            def __init__(self):
                self.cfg = {"rate": 1.0}
            def start(self):
                for _ in range(4):
                    t = threading.Thread(target=self._loop, daemon=True)
                    t.start()
            def _loop(self):
                return self.cfg
     """, ["data-race"]) == []


def test_data_race_suppressed_twin(tmp_path):
    src = RACE_TP.replace(
        "self.n += 1",
        "self.n += 1  # lint: ok(data-race) fixture: benign counter")
    (tmp_path / "mod.py").write_text(textwrap.dedent(src))
    res = core.run_project(core.Project(tmp_path, ["mod.py"]),
                           ["data-race"])
    assert res.active == []
    assert sum(f.suppressed for f in res.findings) == 1


def test_data_race_multi_instance_root_races_with_itself(tmp_path):
    # one spawn site in a loop -> the root can run as two instances:
    # its unguarded writes race even with no second root
    found = lint_src(tmp_path, """
        import threading
        class Worker:
            def __init__(self):
                self.n = 0
            def start(self):
                while True:
                    t = threading.Thread(target=self._loop, daemon=True)
                    t.start()
            def _loop(self):
                self.n += 1
     """, ["data-race"])
    assert len(found) == 1 and "Worker.n" in found[0].message


def test_data_race_join_hatch_clears_loadgen_pattern(tmp_path):
    # worker threads write closure counters; the binder reads them only
    # AFTER joining every worker — sequenced, not racing
    assert lint_src(tmp_path, """
        import threading
        def run():
            n_ok = 0
            def recv():
                nonlocal n_ok
                n_ok += 1
            t = threading.Thread(target=recv)
            t.start()
            t.join()
            return n_ok
     """, ["data-race"]) == []


def test_data_race_unspawned_closure_cell_is_confined(tmp_path):
    # a closure cell is per call frame: without a thread hand-off of
    # the nested function it cannot be shared, however many roots
    # reach the binder
    assert lint_src(tmp_path, """
        import threading
        def outer():
            k = 0
            def bump():
                nonlocal k
                k += 1
            bump()
            return k
        def root_a():
            outer()
        def root_b():
            outer()
        def spawn():
            threading.Thread(target=root_a, daemon=True).start()
            threading.Thread(target=root_b, daemon=True).start()
     """, ["data-race"]) == []


def test_data_race_global_written_from_thread(tmp_path):
    found = lint_src(tmp_path, """
        import threading
        COUNT = 0
        def work():
            global COUNT
            COUNT += 1
        def main():
            threading.Thread(target=work, daemon=True).start()
            return COUNT
     """, ["data-race"])
    assert len(found) == 1 and "COUNT" in found[0].message


# ---------------------------------------------------------------------------
# racetrace: the runtime shared-state sentinel (utils/shared.py)


def test_shared_attr_disabled_is_inert(monkeypatch):
    from difacto_tpu.utils import shared
    monkeypatch.delenv("DIFACTO_RACETRACE", raising=False)
    assert shared.attr() is None


def test_shared_tracer_eraser_state_machine(tmp_path, monkeypatch):
    import threading

    from difacto_tpu.utils import locktrace, shared

    monkeypatch.setenv("DIFACTO_RACETRACE", "1")
    shared.reset()
    locktrace.reset()

    class Box:
        val = shared.attr()
        ro = shared.attr()

        def __init__(self):
            self.mu = locktrace.mutex()
            self.val = 0          # exclusive phase (construction)
            self.ro = "config"

    b = Box()
    fid = "tests/test_lint.py::" \
          "test_shared_tracer_eraser_state_machine.<locals>.Box.val"
    b.val = 1                     # still exclusive: same thread
    st = shared.fields()[fid]
    assert st["state"] == "exclusive" and st["lockset"] is None

    def other():
        with b.mu:
            b.val += 1            # second thread: shared -> modified

    t = threading.Thread(target=other, daemon=True)
    t.start()
    t.join()
    st = shared.fields()[fid]
    assert st["state"] == "shared-modified"
    assert st["threads"] == 2
    # the candidate lockset is what the second thread held
    assert len(st["lockset"]) == 1

    with b.mu:
        _ = b.val                 # intersects to the same lock
    assert shared.fields()[fid]["lockset"] == st["lockset"]
    _ = b.val                     # unlocked read empties the lockset
    st = shared.fields()[fid]
    assert st["lockset"] == []
    assert fid in shared.alarms()

    # the read-only field never left exclusive (one thread)
    rid = fid.replace(".val", ".ro")
    assert shared.fields()[rid]["state"] == "exclusive"

    out = tmp_path / "races.json"
    shared.dump(out)
    loaded = shared.load(out)
    assert loaded[fid]["state"] == "shared-modified"
    assert loaded[fid]["lockset"] == []
    shared.reset()
    assert shared.fields() == {}


def test_racetrace_gate_dynamic_fields_statically_known_safe(tmp_path):
    """The tier-1 RACETRACE gate: drive the serve admission path in a
    subprocess with DIFACTO_RACETRACE=1 and assert every field observed
    in a shared state is statically KNOWN-SAFE (consistently locked,
    read-only after publish, or suppressed with a rationale), and every
    dynamic Eraser ALARM is a suppressed field. Anything else is a
    thread-root or shared-state-index blind spot — fix the model, never
    ignore the observation."""
    import os
    import subprocess
    import sys

    from difacto_tpu.analysis.cli import DEFAULT_PATHS
    from difacto_tpu.analysis.races import get_race_model
    from difacto_tpu.utils import shared

    dump = tmp_path / "racetrace.json"
    scenario = textwrap.dedent("""
        import time
        import numpy as np
        from difacto_tpu.serve.batcher import MicroBatcher, ServeStats
        from difacto_tpu.data.rowblock import RowBlock
        blk = RowBlock(offset=np.array([0, 1], dtype=np.int64),
                       label=np.zeros(1, dtype=np.float32),
                       index=np.zeros(1, dtype=np.uint32),
                       value=None, weight=None)
        stats = ServeStats()
        bat = MicroBatcher(lambda x: np.zeros(x.size, np.float32),
                           batch_size=2, queue_cap=1, stats=stats)
        bat.start()
        fut = bat.submit(blk)
        assert fut is not None
        fut.result(10)
        bat.submit(blk)
        stats.record_latency(0.001)
        stats.snapshot()
        time.sleep(0.3)
        bat.close()
    """)
    env = dict(os.environ,
               DIFACTO_RACETRACE="1",
               DIFACTO_RACETRACE_OUT=str(dump),
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", scenario],
                       cwd=REPO_ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    observed = shared.load(dump)
    multi = {f: rec for f, rec in observed.items()
             if rec["state"] != "exclusive"}
    assert multi, "the scenario must actually share traced fields"

    project = core.Project(
        REPO_ROOT, [p for p in DEFAULT_PATHS if (REPO_ROOT / p).exists()])
    model = get_race_model(project)
    safe = model.known_safe()
    for fid, rec in sorted(multi.items()):
        assert fid in model.fields, \
            f"dynamically shared field {fid} unknown to the static index"
        assert fid in safe, \
            f"dynamically shared field {fid} is not statically " \
            f"guarded/read-only/suppressed — blind spot"
        if rec["state"] == "shared-modified" and rec["lockset"] == []:
            assert fid in model.suppressed_fields, \
                f"dynamic race ALARM on {fid} without a reasoned " \
                f"suppression"


# ---------------------------------------------------------------------------
# satellite machinery: timing report, sarif, lockmap GuardedBy


def test_json_report_carries_pass_timings(tmp_path, capsys):
    _bad_tree(tmp_path)
    rc = lint_main(["--root", str(tmp_path), "mod.py", "--format", "json",
                    "--rules", "wall-clock,data-race"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert doc["lint_seconds"] >= 0
    assert set(doc["rule_seconds"]) == {"wall-clock", "data-race"}
    assert all(v >= 0 for v in doc["rule_seconds"].values())


def test_sarif_output_schema(tmp_path, capsys):
    _bad_tree(tmp_path)
    rc = lint_main(["--root", str(tmp_path), "mod.py",
                    "--format", "sarif", "--rules", "wall-clock"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert doc["version"] == "2.1.0"
    (run,) = doc["runs"]
    assert run["tool"]["driver"]["name"] == "difacto-lint"
    (result,) = run["results"]
    assert result["ruleId"] == "wall-clock"
    assert result["level"] == "error"
    loc = result["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"] == "mod.py"
    assert loc["region"]["startLine"] == 4
    assert result["partialFingerprints"]["difactoLint/v1"]
    rules = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert rules == {"wall-clock"}

    # suppressions do not reach code scanning
    (tmp_path / "mod.py").write_text(
        "import time\n\ndef f():\n    return time.monotonic()\n")
    rc = lint_main(["--root", str(tmp_path), "mod.py",
                    "--format", "sarif", "--rules", "wall-clock"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0 and doc["runs"][0]["results"] == []


def _load_lockmap():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "difacto_lockmap", REPO_ROOT / "tools" / "lockmap.py")
    lockmap = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lockmap)
    return lockmap


def test_lockmap_check_fails_on_dynamic_only_edge(tmp_path, capsys):
    """--check must exit 1 when a real run recorded an edge the static
    model cannot reproduce (a callgraph blind spot)."""
    lockmap = _load_lockmap()
    graph = lockmap.build(REPO_ROOT)
    # fabricate a dump with a REVERSED static edge: its sites are known
    # locks, but the static graph is acyclic so the reverse direction
    # cannot be a static edge
    (src, dst), _e = sorted(graph["static_edges"].items())[0]
    lock2site = {lid: f"{li.path}:{li.line}"
                 for lid, li in graph["locks"].items()}
    dump = tmp_path / "trace.json"
    dump.write_text(json.dumps({
        "version": 1,
        "sites": {lock2site[src]: "Lock", lock2site[dst]: "Lock"},
        "edges": [{"src": lock2site[dst], "dst": lock2site[src],
                   "count": 1}],
    }))
    rc = lockmap.main(["--root", str(REPO_ROOT),
                       "--dynamic", str(dump), "--check"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "DYNAMIC-ONLY" in out

    graph2 = lockmap.build(REPO_ROOT, dump)
    assert graph2["dynamic_only"] == [(dst, src)]


def test_lockmap_outputs_carry_guardedby(tmp_path):
    lockmap = _load_lockmap()
    graph = lockmap.build(REPO_ROOT)
    assert graph["guarded_by"], "the tree has inferred GuardedBy facts"
    # every guard names a known lock, inverted into the guards index
    for fid, locks in graph["guarded_by"].items():
        for lk in locks:
            assert lk in graph["locks"]
            assert fid in graph["guards"][lk]
    dot = lockmap.to_dot(graph)
    assert "guards: " in dot
    doc = lockmap.to_json(graph)
    assert doc["guarded_by"] == graph["guarded_by"]
    assert "difacto_tpu/serve/batcher.py::MicroBatcher._rows_queued" \
        in doc["guarded_by"]


def test_standalone_pragma_skips_comment_run(tmp_path):
    src = ("import time\n"
           "# lint: ok(wall-clock) timestamp-of-record\n"
           "# rationale continues on a second comment line\n"
           "STAMP = time.time()\n")
    (tmp_path / "mod.py").write_text(src)
    res = core.run_project(core.Project(tmp_path, ["mod.py"]),
                           ["wall-clock"])
    assert res.active == [] and len(res.findings) == 1


# ---------------------------------------------------------------------------
# jaxflow cross rules (analysis/jaxflow.py, difacto-lint v4): fixture
# twins — true positive exactly once, negative, suppressed — for each
# of jax-recompile / jax-host-sync / jax-donate-flow. The jax-dtype64
# local rule rides the LOCAL_FIXTURES table above. Deeper model tests
# (bounded provenance, hot-set closure, the JAXTRACE runtime gate)
# live in tests/test_jaxflow.py.


RECOMPILE_TP = """
    import jax
    def f(x, n):
        return x
    g = jax.jit(f, static_argnums=(1,))
    def hot(xs):
        for x in xs:
            g(x, len(x))
"""


def test_jax_recompile_unbounded_static_true_positive(tmp_path):
    found = lint_src(tmp_path, RECOMPILE_TP, ["jax-recompile"])
    assert len(found) == 1, found
    assert "len(...)" in found[0].message
    assert "bounded" in found[0].message


def test_jax_recompile_capped_static_is_clean(tmp_path):
    assert lint_src(tmp_path, """
        import jax
        from difacto_tpu.data.pack_stream import ShapeSchedule
        def f(x, n):
            return x
        g = jax.jit(f, static_argnums=(1,))
        CAP = 64
        def hot(xs, shapes):
            for x in xs:
                g(x, shapes.cap("b", len(x)))
                g(x, CAP)
    """, ["jax-recompile"]) == []


def test_jax_recompile_suppressed_twin(tmp_path):
    src = RECOMPILE_TP.replace(
        "g(x, len(x))",
        "g(x, len(x))  # lint: ok(jax-recompile) probe harness")
    res = lint_src(tmp_path, src, ["jax-recompile"])
    assert res == []


def test_jax_recompile_pjit_site_true_positive(tmp_path):
    """pjit-named creation sites (jax pjit / jaxtrace.pjit with
    shardings) are jit sites with the same identity — an unbounded
    static through a sharded program is still a finding (ISSUE 12:
    sharded train/serve programs must not dodge the gates)."""
    found = lint_src(tmp_path, """
        from difacto_tpu.utils import jaxtrace
        def f(x, n):
            return x
        g = jaxtrace.pjit(f, static_argnums=(1,), in_shardings=None,
                          out_shardings=None)
        def hot(xs):
            for x in xs:
                g(x, len(x))
    """, ["jax-recompile"])
    assert len(found) == 1, found
    assert "len(...)" in found[0].message


def test_jax_recompile_pjit_bounded_is_clean(tmp_path):
    assert lint_src(tmp_path, """
        from difacto_tpu.utils import jaxtrace
        def f(x, n):
            return x
        g = jaxtrace.pjit(f, static_argnums=(1,), donate_argnums=(0,))
        CAP = 128
        def hot(xs):
            for x in xs:
                g(x, CAP)
    """, ["jax-recompile"]) == []


def test_jax_recompile_jit_in_loop_and_immediate_invoke(tmp_path):
    found = lint_src(tmp_path, """
        import jax
        def f(x):
            return x
        def worst(xs):
            for x in xs:
                step = jax.jit(f)
                step(x)
        def also_bad(x):
            return jax.jit(f)(x)
    """, ["jax-recompile"])
    msgs = " | ".join(f.message for f in found)
    assert len(found) == 2, found
    assert "inside a loop" in msgs
    assert "invoked in one expression" in msgs


HOST_SYNC_TP = """
    import jax
    import numpy as np
    def f(x):
        return x
    step = jax.jit(f)
    def run(xs):
        out = 0.0
        for x in xs:
            y = step(x)
            out += float(y)
        return out
"""


def test_jax_host_sync_true_positive(tmp_path):
    found = lint_src(tmp_path, HOST_SYNC_TP, ["jax-host-sync"])
    assert len(found) == 1, found
    assert "float" in found[0].message
    assert "sync" in found[0].message


def test_jax_host_sync_declared_fetch_is_clean(tmp_path):
    assert lint_src(tmp_path, """
        import jax
        from difacto_tpu.utils import jaxtrace
        def f(x):
            return x
        step = jax.jit(f)
        def run(xs):
            out = 0.0
            for x in xs:
                y = step(x)
                out += float(jaxtrace.fetch(y, point="harness"))
            return out
    """, ["jax-host-sync"]) == []


def test_jax_host_sync_cold_path_is_clean(tmp_path):
    # the same coercion OUTSIDE the hot set (no loop, no _loop) is not
    # a finding: a one-off fetch at epoch end is normal
    assert lint_src(tmp_path, """
        import jax
        def f(x):
            return x
        step = jax.jit(f)
        def once(x):
            return float(step(x))
    """, ["jax-host-sync"]) == []


def test_jax_host_sync_interprocedural_through_helper(tmp_path):
    # the coercion lives in a helper the hot loop calls with a device
    # value — reachability + param taint must cross the edge
    found = lint_src(tmp_path, """
        import jax
        def f(x):
            return x
        step = jax.jit(f)
        def report(y):
            return float(y)
        def run(xs):
            out = 0.0
            for x in xs:
                y = step(x)
                out += report(y)
            return out
    """, ["jax-host-sync"])
    assert len(found) == 1, found
    assert "report" in found[0].message


def test_jax_host_sync_suppressed_twin(tmp_path):
    src = HOST_SYNC_TP.replace(
        "out += float(y)",
        "out += float(y)  # lint: ok(jax-host-sync) harness fence")
    assert lint_src(tmp_path, src, ["jax-host-sync"]) == []


DONATE_FLOW_TP = """
    import jax
    def g(x):
        return x + 1
    f = jax.jit(g, donate_argnums=(0,))
    def inner(buf):
        return f(buf)
    def outer(b):
        r = inner(b)
        return b
"""


def test_jax_donate_flow_cross_edge_read_true_positive(tmp_path):
    found = lint_src(tmp_path, DONATE_FLOW_TP, ["jax-donate-flow"])
    assert len(found) == 1, found
    assert "donated" in found[0].message or "donates" in found[0].message
    assert "inner" in found[0].message


def test_jax_donate_flow_rebind_is_clean(tmp_path):
    assert lint_src(tmp_path, """
        import jax
        def g(x):
            return x + 1
        f = jax.jit(g, donate_argnums=(0,))
        def inner(buf):
            return f(buf)
        def outer(b):
            b = inner(b)
            return b
    """, ["jax-donate-flow"]) == []


def test_jax_donate_flow_suppressed_twin(tmp_path):
    src = DONATE_FLOW_TP.replace(
        "        return b\n",
        "        # lint: ok(jax-donate-flow) fixture rationale\n"
        "        return b\n")
    assert lint_src(tmp_path, src, ["jax-donate-flow"]) == []


def test_jax_donate_flow_static_and_range_conflicts(tmp_path):
    found = lint_src(tmp_path, """
        import jax
        def g(x, n):
            return x
        f1 = jax.jit(g, donate_argnums=(1,), static_argnums=(1,))
        f2 = jax.jit(g, donate_argnums=(5,))
    """, ["jax-donate-flow"])
    msgs = " | ".join(f.message for f in found)
    assert len(found) == 2, found
    assert "also static_argnums" in msgs
    assert "point past" in msgs


def test_jax_donate_flow_aliased_positions(tmp_path):
    found = lint_src(tmp_path, """
        import jax
        def g(x, y):
            return x + y
        f = jax.jit(g, donate_argnums=(0,))
        def run(a):
            return f(a, a)
    """, ["jax-donate-flow"])
    assert len(found) == 1, found
    assert "non-donated" in found[0].message


# ---------------------------------------------------------------------------
# shardflow cross rules (analysis/shardflow.py, difacto-lint v5):
# fixture twins — true positive exactly once, negative, suppressed —
# for each of jax-shard-break / jax-shard-replicate.
# The model-level views (pin verdicts, hlomap merge, the HLOSCAN
# tier-1 gate) live in tests/test_hloscan.py.


SHARD_PIN_TP = """
    import jax
    from difacto_tpu.parallel import sharding_tree, state_sharding

    def train(state, batch):
        return state

    def build(mesh, state):
        shardings = sharding_tree(state, state_sharding(mesh))
        step = jax.jit(train, donate_argnums=0)
        return step, shardings
"""


def test_jax_shard_break_unpinned_donating_program(tmp_path):
    found = lint_src(tmp_path, SHARD_PIN_TP, ["jax-shard-break"])
    assert len(found) == 1, found
    assert "train" in found[0].message
    assert "pins its output layout" in found[0].message


def test_jax_shard_break_pinned_programs_are_clean(tmp_path):
    # the two sanctioned pin shapes: out_shardings= on the jit call,
    # and a target threaded through a pinning builder (the
    # `_, train_step, _ = make_step(..., state_shardings=...)` idiom)
    assert lint_src(tmp_path, """
        import jax
        from difacto_tpu.parallel import sharding_tree, state_sharding
        from difacto_tpu.step import state_constrainer

        def train(state, batch):
            return state

        def make_step(fns, state_shardings=None):
            constrain = state_constrainer(state_shardings)
            def step(state, batch):
                return constrain(state)
            return None, step, None

        def build(mesh, state, fns):
            shardings = sharding_tree(state, state_sharding(mesh))
            step = jax.jit(train, donate_argnums=0,
                           out_shardings=shardings)
            _, train_step, _ = make_step(fns, state_shardings=shardings)
            pinned = jax.jit(train_step, donate_argnums=0)
            return step, pinned
    """, ["jax-shard-break"]) == []


def test_jax_shard_break_pin_suppressed_twin(tmp_path):
    src = SHARD_PIN_TP.replace(
        "step = jax.jit(train, donate_argnums=0)",
        "step = jax.jit(train, donate_argnums=0)"
        "  # lint: ok(jax-shard-break) single-device fixture")
    assert lint_src(tmp_path, src, ["jax-shard-break"]) == []


AXIS_BREAK_TP = """
    import jax.numpy as jnp

    def grow(state, extra):
        return jnp.concatenate([state.w, extra])
"""


def test_jax_shard_break_axis_breaker_true_positive(tmp_path):
    found = lint_src(tmp_path, AXIS_BREAK_TP, ["jax-shard-break"])
    assert len(found) == 1, found
    assert "jnp.concatenate" in found[0].message
    assert "capacity axis" in found[0].message


def test_jax_shard_break_reshape_and_boolean_mask(tmp_path):
    found = lint_src(tmp_path, """
        def pack(state):
            return state.w.reshape(-1)

        def live_rows(table):
            return table[table != 0]
    """, ["jax-shard-break"])
    msgs = " | ".join(f.message for f in found)
    assert len(found) == 2, found
    assert "reshape" in msgs
    assert "boolean mask" in msgs


def test_jax_shard_break_gather_on_table_is_clean(tmp_path):
    # the sanctioned access pattern: gather rows by a padded slot
    # vector; axis-breakers over NON-table arrays are fine
    assert lint_src(tmp_path, """
        import jax.numpy as jnp

        def gather(state, slots):
            rows = state.w[slots]
            order = jnp.argsort(slots)
            return rows, order
    """, ["jax-shard-break"]) == []


def test_jax_shard_break_axis_suppressed_twin(tmp_path):
    src = AXIS_BREAK_TP.replace(
        "return jnp.concatenate([state.w, extra])",
        "return jnp.concatenate([state.w, extra])"
        "  # lint: ok(jax-shard-break) host-side checkpoint merge")
    assert lint_src(tmp_path, src, ["jax-shard-break"]) == []


SHARD_REPLICATE_TP = """
    import jax
    from difacto_tpu.parallel import state_sharding

    def publish(mesh, state):
        spec = state_sharding(mesh)
        full = jax.device_put(state.w)
        return full, spec
"""


def test_jax_shard_replicate_true_positive(tmp_path):
    found = lint_src(tmp_path, SHARD_REPLICATE_TP,
                     ["jax-shard-replicate"])
    assert len(found) == 1, found
    assert "device_put with no sharding" in found[0].message


def test_jax_shard_replicate_placed_and_non_table_clean(tmp_path):
    assert lint_src(tmp_path, """
        import jax
        import numpy as np
        from difacto_tpu.parallel import state_sharding

        def publish(mesh, state, rows):
            spec = state_sharding(mesh)
            placed = jax.device_put(state.w, spec)
            host = np.asarray(rows)
            return placed, host
    """, ["jax-shard-replicate"]) == []


def test_jax_shard_replicate_donated_from_replicated_copy(tmp_path):
    # rule (b): the donated argument of an fs-scoped program fed from
    # a replicating coercion at the exact call edge
    found = lint_src(tmp_path, """
        import jax
        from difacto_tpu.parallel import (replicated, sharding_tree,
                                          state_sharding)

        def train(state, batch):
            return state

        def run(mesh, state, batch):
            shardings = sharding_tree(state, state_sharding(mesh))
            step = jax.jit(train, donate_argnums=0,
                           out_shardings=shardings)
            fresh = jax.device_put(state, replicated(mesh))
            return step(fresh, batch)
    """, ["jax-shard-replicate"])
    assert len(found) == 1, found
    assert "donated argument 0" in found[0].message
    assert "replicated" in found[0].message


def test_jax_shard_replicate_suppressed_twin(tmp_path):
    src = SHARD_REPLICATE_TP.replace(
        "full = jax.device_put(state.w)",
        "full = jax.device_put(state.w)"
        "  # lint: ok(jax-shard-replicate) export path, mesh-free")
    assert lint_src(tmp_path, src, ["jax-shard-replicate"]) == []
