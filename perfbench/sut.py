"""The system under test, as the benchmark drives it: the SGD learner's
driver, and what every driver shares.

The driver of every configuration that names none (``run.load_driver``;
``run.py`` lists what a driver owns), and the parts any driver uses: the
device binding, the compile listener, the rec member writer and the
window between two epoch-end marks. It takes from the program the entry
that ``python -m difacto_tpu task=train`` runs (``place_compile_cache``,
``Learner.create(LEARNER)``, ``init``, ``run``), the epoch-end callback,
the obs registry's ``stage_seconds_total``, the rec member writer, and
the accessors of the table's row layout. It plants nothing in the
program.

Two things the program offers no public way to do, and how each is done
here (listed in PERF.md under Open questions for a program PR to replace
by an interface):

- *Seeing single steps.* The learner's ``_dispatch_item(job_type, item,
  push_cnt, want_counts, job, dim_min, pending, ...)`` consumes one
  streamed batch, on one chip or on a mesh: it runs one step and appends
  ``(nrows, objv, auc)`` to ``pending``. For the first ``N_STEPS`` steps
  of epoch 0 the instance's attribute is wrapped so that the step's loss
  and a few sums over the touched table rows are kept; then the wrapper
  is taken off, and the window runs the method as it is.
- *Knowing that the pair-replay program is compiled.* It compiles on a
  thread named ``pair-exec-compile`` and replay switches to it when it is
  ready. The window opens only once no such thread runs and
  ``_pair_execs`` holds no pending entry.
- *Seeing the program that the window times.* A replay window runs one
  compiled executable only, two cached batches a call, kept in
  ``_pair_execs``. Once it is ready its entry is wrapped for its next
  call, the first pair of the warm epoch: the touched rows are read
  before and after that one call of the executable itself, then the
  entry is put back, and the window calls the same object bare.
"""

from __future__ import annotations

import gc
import os
import threading
import time

import numpy as np

LEARNER = "sgd"
N_STEPS = 3          # steps of epoch 0 that the reference follows
HUGE_EPOCHS = 1_000_000


# ---------------------------------------------------------------- device
def bind(chips: int) -> dict:
    """Place the compile cache as the program's entry does, bind the
    backend, and refuse anything but a TPU with at least ``chips``."""
    from difacto_tpu.utils.device import place_compile_cache
    cache_dir = place_compile_cache()
    import jax
    # programs that compile in under a second are kept too: set-up is
    # then the same work in every run after the first
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    backend = jax.default_backend()
    devs = jax.devices()
    if backend != "tpu":
        raise SystemExit(
            f"perfbench: no TPU: jax.default_backend() is {backend!r}; "
            "the benchmark measures the accelerator and does not fall "
            "back to the CPU")
    if len(devs) < chips:
        raise SystemExit(
            f"perfbench: the cell needs {chips} chips, JAX has "
            f"{len(devs)}")
    return describe(cache_dir)


def describe(cache_dir=None) -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "host_cores": os.cpu_count(),
            "compile_cache_dir": cache_dir}


def learner_known(name: str) -> bool:
    """Whether the program registers a learner of that name, as its
    source says: a class under ``@register("<name>")`` in
    ``difacto_tpu/learners/`` (``Learner.create`` looks the name up in
    lower case). Read, not imported: the run imports the program's
    learners after the seed's rows are made, and importing them before
    made epoch 0 of the four-chip cell 2.9 s longer (PERF.md)."""
    import ast
    import importlib.util
    spec = importlib.util.find_spec("difacto_tpu.learners")
    names = set()
    for path in spec.submodule_search_locations:
        for fname in sorted(os.listdir(path)):
            if not fname.endswith(".py"):
                continue
            with open(os.path.join(path, fname)) as f:
                tree = ast.parse(f.read())
            for node in ast.walk(tree):
                for deco in getattr(node, "decorator_list", ()):
                    if isinstance(deco, ast.Call) and getattr(
                            deco.func, "id", "") == "register" \
                            and deco.args and isinstance(
                                deco.args[0], ast.Constant):
                        names.add(str(deco.args[0].value).lower())
    return name.lower() in names


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device, 0 where the backend does
    not say."""
    import jax
    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class Compiles:
    """Seconds JAX spent in backend compiles (or in cache retrievals that
    stand for them), and the cache's hits and misses, from
    ``jax.monitoring`` (copied from ``chip_smoke._Compiles``)."""

    def __init__(self) -> None:
        import jax.monitoring as mon
        self.seconds = 0.0
        self.count = self.hits = self.misses = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.count += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


# ------------------------------------------------------------------ data
def write_member(data_dir: str, m: int, label, uniq, index, width: int
                 ) -> int:
    """One pre-localized rec member, as ``task=convert`` writes them,
    through the program's own writer. Returns its bytes."""
    from difacto_tpu.data.rec import write_rec_block
    from difacto_tpu.data.rowblock import RowBlock
    rows = len(label)
    blk = RowBlock(offset=np.arange(rows + 1, dtype=np.int64) * width,
                   label=label, index=index, value=None)
    path = os.path.join(data_dir, f"part-{m:05d}.rec2")
    write_rec_block(path, blk, uniq=uniq)
    return os.path.getsize(path)


# ----------------------------------------------------------------- probe
class ProbeDone(Exception):
    """Raised out of the run after the compared steps, where only the
    comparison's numbers are wanted (``calibrate.py``)."""


class Probe:
    """What the comparison reads of the program: sums over the table rows
    that the first steps touch, after each of them, and those rows whole
    before and after the first call of the pair-replay executable. All of
    it is read from the learner's own state, in one of two ways, by the
    layout the store holds (``param.V_dim``): a fused row ``VVg[r]`` taken
    apart by the program's own accessors, or the flat table's ``w[r]``,
    ``z[r]``, ``sqrt_g[r]``, ``cnt[r]`` (no embedding: V and Vg of no
    columns). Everything behind the read is one code."""

    LEAVES = ("w", "z", "sg", "cnt", "live", "V", "Vg")

    def __init__(self, learner, rows: np.ndarray, stop_after: str = ""):
        # ``stop_after``: "first" or "pair" ends the run (ProbeDone) once
        # that part is read, for ``calibrate.py``
        self.stop_after = stop_after
        import jax
        import jax.numpy as jnp
        self.learner = learner
        param = learner.store.param
        if param.V_dim > 0:
            self._kept = ("V", "Vg")
            self._table = lambda state: state.VVg
            leaves = self._fused_leaves(param, learner.store.state.capacity)
        else:
            self._kept = ("w", "z", "sg")
            self._table = lambda state: (state.w, state.z, state.sqrt_g,
                                         state.cnt, state.v_live)

            def leaves(table, r):
                none = jnp.zeros((r.shape[0], 0), jnp.float32)
                return tuple(x[r] for x in table) + (none, none)

        def sums(table, r, V0):
            w, _, sg, _, live, V, Vg = leaves(table, r)
            d = V - V0
            return jnp.stack([
                jnp.sum(w * w), jnp.sum(sg * sg), jnp.sum(Vg * Vg),
                jnp.sum(d * d), jnp.sum((w != 0).astype(jnp.float32)),
                jnp.sum(live.astype(jnp.float32))])

        self._rows = self._replicated(np.asarray(rows, np.int32))
        self._sums = jax.jit(sums)
        self._leaves = jax.jit(leaves)
        self.rows = None    # host leaves of the touched rows, at the end
        self._V0 = jax.jit(lambda table, r: leaves(table, r)[5])(
            self._table(learner.store.state), self._rows)
        self.loss = []      # device scalars, one a step
        self.sums = []      # device f32[6], one a step
        self.pair = None    # the pair executable's one watched call
        self._armed = {}    # key -> the executable, while wrapped
        self._orig = learner._dispatch_item
        learner._dispatch_item = self._spy

    @staticmethod
    def _fused_leaves(param, capacity: int):
        import jax.numpy as jnp
        from difacto_tpu.updaters.sgd_updater import (quantized,
                                                      row_layout,
                                                      scal_f32)
        k, h, _, off = row_layout(param, capacity)

        def leaves(VVg, r):
            got = VVg[r]
            f = scal_f32(got[:, off:])
            if quantized(param):
                from difacto_tpu.ops import fused
                V = fused.dequant_half(got[:, :k], f[:, 5],
                                       param.slot_dtype)
                Vg = fused.dequant_half(got[:, h:h + k], f[:, 6],
                                        param.slot_dtype)
            else:
                V = got[:, :k].astype(jnp.float32)
                Vg = got[:, h:h + k].astype(jnp.float32)
            return f[:, 0], f[:, 1], f[:, 2], f[:, 3], f[:, 4] > 0, V, Vg

        return leaves

    def _replicated(self, x):
        import jax.numpy as jnp
        mesh = self.learner.mesh
        if mesh is None:
            return jnp.asarray(x)
        from difacto_tpu.parallel import put_global, replicated
        return put_global(x, replicated(mesh))

    def _host_leaves(self, state) -> dict:
        """The touched rows as the table holds them now, on the host at
        once: nothing of the probe stays on the device."""
        return dict(zip(self.LEAVES, (np.asarray(x) for x in self._leaves(
            self._table(state), self._rows))))

    def _spy(self, *args, **kw):
        pending = kw["pending"] if "pending" in kw else args[6]
        before = len(pending)
        self._orig(*args, **kw)
        if len(pending) != before + 1:
            raise RuntimeError("a streamed batch of epoch 0 ran "
                               f"{len(pending) - before} steps, not one")
        self.loss.append(pending[-1][1])
        state = self.learner.store.state
        self.sums.append(self._sums(self._table(state), self._rows,
                                    self._V0))
        if len(self.loss) >= N_STEPS:
            # kept through the window: the leaves compared row by row
            # and no other. (With all seven kept, each later step of
            # epoch 0 took 95 ms longer on four chips: PERF.md section 6.)
            got = self._host_leaves(state)
            self.rows = {k: got[k] for k in self._kept}
            self.release()
            if self.stop_after == "first":
                raise ProbeDone()

    def release(self) -> None:
        """Take the wrapper off; drop what only it needed."""
        if self._orig is not None:
            del self.learner._dispatch_item     # back to the class's
            self._orig = None
            self._V0 = None

    def arm_pair(self) -> None:
        """Wrap every ready pair-replay executable for its next call."""
        if self.pair is not None:
            return
        execs = getattr(self.learner, "_pair_execs", {})
        for key, ex in list(execs.items()):
            if ex is None or isinstance(ex, Exception):
                continue
            self._armed[key] = ex
            execs[key] = self._pair_spy(ex)
        if self.stop_after == "pair" and not self._armed:
            raise RuntimeError("the learner holds no pair-replay "
                               "executable to read")

    def disarm_pair(self) -> None:
        execs = getattr(self.learner, "_pair_execs", {})
        for key, ex in self._armed.items():
            if key in execs:
                execs[key] = ex
        self._armed = {}

    def _pair_spy(self, ex):
        def spy(state, pa, pb):
            before = self._host_leaves(state)
            out = ex(state, pa, pb)
            self.pair = {"loss": [float(out[1]), float(out[3])],
                         "before": before,
                         "after": self._host_leaves(out[0])}
            self.disarm_pair()
            if self.stop_after == "pair":
                # the run ends here: the learner takes the new state
                self.learner.store.state = out[0]
                raise ProbeDone()
            return out
        return spy

    def numbers(self) -> dict:
        """The program's side of the comparison, as floats and host
        arrays."""
        if len(self.loss) < N_STEPS:
            raise RuntimeError(
                f"the program ran {len(self.loss)} steps through "
                f"_dispatch_item in epoch 0, {N_STEPS} are compared")
        s = np.asarray([np.asarray(x, np.float64) for x in self.sums])
        return {
            "loss": [float(x) for x in self.loss[:N_STEPS]],
            "grad": {"w": float(np.sqrt(s[0][1])),
                     "V": float(np.sqrt(s[1][2]))},
            "change": {"w": float(np.sqrt(s[N_STEPS - 1][0])),
                       "V": float(np.sqrt(s[N_STEPS - 1][3]))},
            "rows": self.rows,
            "Vg_after_step1": float(np.sqrt(s[0][2])),
            "nnz_w": int(s[N_STEPS - 1][4]),
            "live": int(s[N_STEPS - 1][5]),
            "pair": self.pair,
        }


# ------------------------------------------------------------------- run
def learner_kwargs(config: dict, traffic: dict, data_dir: str, seed: int,
                   override: dict = None) -> dict:
    kw = dict(config)
    kw.update(traffic.get("learner", {}))
    kw.update(override or {})
    kw.update(data_in=data_dir, max_num_epochs=HUGE_EPOCHS,
              # the table's own seed: any whole number, as PRNGKey takes it
              seed=int(seed) % (1 << 31))
    return kw


def probe_plan(data: dict, config: dict, ref_mod) -> tuple:
    """(the table rows that the compared steps touch, those steps' batches
    as the reference takes them: [(idx into the rows, labels)])."""
    from perfbench import gen
    cap = int(config["hash_capacity"])
    kept = [data["kept"][m] for m in range(N_STEPS)]
    probe_rows, idx = ref_mod.touched(
        [gen.slots_of(data["tables"].rev_of(g), cap) for _, g in kept])
    return probe_rows, [(i, y) for i, (y, _) in zip(idx, kept)]


def step_work(data: dict, config: dict) -> dict:
    """The least work of one step: ``work.step_work`` of the step's
    distinct rows, rows and non-zeros."""
    from perfbench import work
    return work.step_work(data["uniq_per_step"], data["batch"],
                          data["batch"] * data["width"],
                          int(config["V_dim"]), work.item_size(config))


def _pair_compile_pending(learner) -> bool:
    if any(t.name == "pair-exec-compile" and t.is_alive()
           for t in threading.enumerate()):
        return True
    return any(v is None for v in getattr(learner, "_pair_execs",
                                          {}).values())


OPEN_MARK, CLOSE_MARK = "perfbench_window_open", "perfbench_window_close"


def _mark(name: str) -> None:
    """An event of the host's in the running trace: where the window
    opens and closes on the trace's own clock."""
    import jax
    with jax.profiler.TraceAnnotation(name):
        time.sleep(0.0002)


def _stage_seconds(learner) -> dict:
    snap = learner.obs.snapshot()
    series = snap.get("counters", {}).get("stage_seconds_total", {})
    return {dict(k).get("stage", ""): float(v) for k, v in series.items()}


class Window:
    """The epoch-end marks of a run, and the window between two of them.

    The window opens at the first mark after the one at which ``ready()``
    first held (``on_ready`` runs there): the warm-up before it streams,
    stages and compiles. It closes at the first mark at or after
    ``seconds`` from its opening. With ``trace_dir`` the profiler runs
    over exactly the window, and two marks in the trace give its span
    there. ``stages()`` reads the learner's stage seconds at both ends.
    What one epoch's rows are is the driver's: it hands them to ``mark``.
    """

    def __init__(self, seconds: float, trace_dir, stages, ready,
                 on_ready=None):
        import jax
        self.seconds, self.trace_dir = seconds, trace_dir
        self._profiler = jax.profiler
        self._stages, self._ready, self._on_ready = stages, ready, on_ready
        self.marks = []     # (epoch, time, rows) at each epoch's end
        self._win = {"ready_at": None}

    def mark(self, k: int, rows: float) -> bool:
        """Keep the mark of epoch ``k``'s end; True where it closes the
        window."""
        now = time.perf_counter()
        self.marks.append((k, now, float(rows)))
        win = self._win
        if "open" not in win:
            ready = win["ready_at"]
            if ready is not None and k > ready:
                win["open"] = len(self.marks) - 1
                win["stages_open"] = self._stages()
                if self.trace_dir is not None:
                    self._profiler.start_trace(self.trace_dir)
                    _mark(OPEN_MARK)
                win["t_open"] = time.perf_counter()
            elif ready is None and self._ready():
                win["ready_at"] = k
                if self._on_ready is not None:
                    self._on_ready()
        elif "close" not in win and now - win["t_open"] >= self.seconds:
            win["close"] = len(self.marks) - 1
            win["t_close"] = now
            win["stages_close"] = self._stages()
            if self.trace_dir is not None:
                _mark(CLOSE_MARK)
                self._profiler.stop_trace()
            return True
        return False

    def summary(self, t0: float) -> dict:
        """The window's keys of ``drive``'s result; ``t0``: when the
        learner's run began."""
        win, marks = self._win, self.marks
        if "close" not in win:
            raise RuntimeError("the run ended before the window closed")
        o, c = win["open"], win["close"]
        return {
            "t_open": win["t_open"],
            # the open mark's own clock reading lies before the trace
            # starts; the rate runs from the moment the window is open
            "window_s": win["t_close"] - win["t_open"],
            "window_rows": sum(m[2] for m in marks[o + 1:c + 1]),
            "window_rows_by_epoch": [m[2] for m in marks[o + 1:c + 1]],
            "window_epochs": c - o,
            "warm_epochs": o,
            "epoch0_s": marks[0][1] - t0,
            "warm_s": win["t_open"] - marks[0][1],
            "stages": {k: win["stages_close"].get(k, 0.0)
                       - win["stages_open"].get(k, 0.0)
                       for k in win["stages_close"]},
        }


def drive(kwargs: dict, plan, seconds: float, trace_dir: str = None,
          stop_after: str = "") -> dict:
    """Build the learner, run it as the entry does, and keep the marks.

    An epoch's rows are the rows its training progress counts. The
    window (``Window``) is ready once epoch 0 has streamed, staged and
    compiled and no pair-replay program is left to compile. ``plan`` is
    ``probe_plan``'s, or None for no probe. ``stop_after`` ("first",
    "pair") ends the run once the probe has read that part:
    ``calibrate.py``'s readings need no window.

    Returns ``t_open``, ``window_s``, ``window_rows``,
    ``window_rows_by_epoch``, ``window_epochs``, ``warm_epochs``,
    ``init_s``, ``epoch0_s``, ``warm_s``, ``stages``, ``producer_mode``,
    ``paired_dispatches``, ``device_cache``, ``table_rows``,
    ``table_bytes``, ``probe`` and ``memory_peak_bytes``."""
    import jax
    from difacto_tpu.learners import Learner

    learner = Learner.create(LEARNER)
    # The table's seed is a constant of the program that draws the table,
    # so a seed the cache has seen would load what a fresh seed compiles.
    # Nothing that compiles in ``init`` is written to the cache: every
    # run compiles it, and set-up is the same work for any seed.
    knob = "jax_persistent_cache_min_compile_time_secs"
    kept = getattr(jax.config, knob)
    jax.config.update(knob, 1e9)
    t_init = time.perf_counter()
    try:
        remain = learner.init([(k, str(v)) for k, v in kwargs.items()])
    finally:
        jax.config.update(knob, kept)
    if remain:
        raise ValueError(f"keys the learner does not know: {remain}")
    probe = (Probe(learner, plan[0], stop_after=stop_after)
             if plan is not None else None)
    init_s = time.perf_counter() - t_init    # table init and the probe

    win = Window(seconds, trace_dir,
                 stages=lambda: _stage_seconds(learner),
                 ready=lambda: not _pair_compile_pending(learner),
                 # the pair is read in the epoch that follows
                 on_ready=probe.arm_pair if probe is not None else None)

    def on_epoch_end(k, train_prog, _val):
        if win.mark(k, train_prog.nrows):
            learner.param.max_num_epochs = k + 1

    learner.add_epoch_end_callback(on_epoch_end)
    t0 = time.perf_counter()
    try:
        learner.run()
    except ProbeDone:
        learner.stop()
        numbers = probe.numbers()
        learner.store.state = None
        learner = probe = win = None
        gc.collect()
        return {"probe": numbers}
    finally:
        if probe is not None:
            probe.release()
            probe.disarm_pair()

    out = dict(
        win.summary(t0), init_s=init_s,
        producer_mode=getattr(learner, "_last_producer_mode", "?"),
        paired_dispatches=getattr(learner, "_paired_dispatches", 0),
        device_cache=learner.device_cache_info(),
        table_rows=int(learner.store.state.capacity),
        table_bytes=sum(int(x.nbytes) for x in learner.store.state),
        probe=probe.numbers() if probe is not None else None)
    out["memory_peak_bytes"] = memory_peak_bytes()
    # free the program's state before the reference runs
    learner.epoch_end_callbacks.clear()
    learner.store.state = None
    getattr(learner, "_dev_caches", {}).clear()
    getattr(learner, "_pair_execs", {}).clear()
    del learner, probe, win
    gc.collect()
    return out


# ------------------------------------------------------------ comparison
def compare(ref_mod, config: dict, kwargs: dict, plan, res: dict,
            data: dict) -> dict:
    """The program's first steps and its watched pair call against the
    plain reference from the same seed, and each epoch of the window
    against the traffic's rows (``check.py``). Returns ``numbers``,
    ``names`` (those the table's layout must have) and ``said`` (the
    ``reference`` line)."""
    from perfbench import check
    probe_rows, batches = plan
    hyper = ref_mod.Hyper.of(config)
    t0 = time.perf_counter()
    V0 = ref_mod.initial_V(kwargs["seed"], int(config["hash_capacity"]),
                           probe_rows, hyper)
    V0.block_until_ready()
    t_init = time.perf_counter() - t0
    ref = ref_mod.follow(hyper, V0, batches)
    nums = check.numbers(res["probe"], ref, ref_mod.rel_diff)
    pair = res["probe"].pop("pair")
    pair_said = None
    if pair is not None:
        # the window's own executable: its first call of the warm epoch
        # took the epoch's first two batches
        pref = ref_mod.follow_pair(hyper, pair["before"], batches[:2])
        nums.update(ref_mod.pair_numbers(pair, pref, check.gap))
        pair_said = {"program": pair["loss"], "reference": pref["loss"]}
    nums["epoch_rows"] = check.epoch_rows(res["window_rows_by_epoch"],
                                          data["rows"])
    for side in (res["probe"], ref):
        side.pop("rows")
    return {"numbers": nums, "names": check.names(hyper.V_dim),
            "said": {"initial_table_s": round(t_init, 3),
                     "touched_rows": int(len(probe_rows)),
                     "program": res["probe"], "reference": ref,
                     "pair_loss": pair_said}}
