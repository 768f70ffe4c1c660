"""Serve hot-reload: pick up a newly-trained model without a restart.

The trainer's side of the lifecycle ends at a checkpoint on disk; before
this module the server's side began with a process restart — a cold
executor, recompiled buckets, and a dropped listening socket. The
``ModelReloader`` closes that gap:

- a **watcher** thread polls the model path (manifest generation first,
  mtime/size as the legacy fallback) every ``poll_s`` seconds and
  triggers a reload when the fingerprint moves, so a `model_out` that the
  trainer re-saves is picked up automatically;
- the ``#reload [path]`` control line triggers the same reload on demand
  (handled on the requesting connection's reader thread — scoring never
  stalls behind a load);
- the reload itself loads the new model **weights-only in the
  background** through ``open_serving_store(fallback=False)`` — full
  manifest verification, no silent walk-back — and only then swaps it
  into the executor atomically (``PredictExecutor.swap_store``:
  in-flight batches finish on the old model; the compiled predict
  programs survive because the geometry is checked);
- a failed or corrupt load **keeps the old model serving** and records
  ``reload_failures``; ``#stats`` carries ``model_generation`` /
  ``reloads`` / ``reload_failures`` so a fleet can alert on a replica
  that's stuck behind the model it should be serving;
- a **geometry change** (``V_dim`` / ``hash_capacity`` moved between
  generations) no longer forces a restart: when the reloader is attached
  to a server it runs a **blue/green executor swap** — a second
  ``PredictExecutor`` is built against the new store, seeded with the
  live executor's sticky shape caps and warmed on every bucket the live
  executor has compiled (its recorded warm-set, so no request ever pays
  a compile on green), then the server's executor reference is swapped
  atomically: in-flight batches finish on blue, the next flush runs on
  green, and blue's store/buffers drop with the last reference.
  ``swap_state`` (idle/warming/swapping) rides ``#health``/``#stats``
  and ``serve_bluegreen_swaps_total`` counts the swaps; ``reload.warm``
  is a chaos injection point inside the warm loop
  (utils/faultinject.py).
- the warm-set pre-compilation runs on a small **thread pool**
  (``warm_workers``, default 4): bucket compiles are independent XLA
  compilations that release the GIL, so a live executor with many
  recorded buckets no longer stretches the swap window by compiling
  them one at a time. The swap itself stays atomic and any worker
  failure aborts the whole swap with blue serving; ``last_warm_ms``
  records the wall-clock warm cost.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Optional, Tuple

from ..utils import faultinject, stream
from ..utils.locktrace import mutex

log = logging.getLogger("difacto_tpu")


class ModelReloader:
    def __init__(self, executor, model_uri: str, poll_s: float = 0.0,
                 kwargs=(), server=None, warm_workers: int = 4):
        # server=None (unit use): same-geometry swaps only — there
        # is no batcher whose executor reference a blue/green swap could
        # retarget, so a geometry change stays a reload failure
        self._executor = executor
        self._server = server
        self.model_uri = model_uri
        self.poll_s = poll_s
        self._kwargs = list(kwargs)
        self.warm_workers = warm_workers
        self.reloads = 0
        self.reload_failures = 0
        self.bluegreen_swaps = 0
        self.last_warm_ms = 0.0              # wall cost of the last warm
        self.swap_state = "idle"             # idle | warming | swapping
        self._reload_mu = mutex()            # serialize concurrent reloads
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._cur = self._fingerprint()

    @property
    def executor(self):
        """The LIVE executor — read through the server when attached,
        because a blue/green swap replaces the server's executor object
        and a cached reference would keep reloading into a dead blue."""
        return (self._server.executor if self._server is not None
                else self._executor)

    # ------------------------------------------------------------ watch
    def _fingerprint(self) -> Optional[Tuple]:
        """(path, manifest generation, mtime, size) of the current model
        file; None while unresolvable. Generation is the real signal —
        mtime/size only cover legacy manifest-less files."""
        from ..utils import manifest as mft
        from .model import resolve_model_path
        try:
            path = resolve_model_path(self.model_uri)
            man = mft.read(path)
            gen = man.get("generation") if man else None
            return (path, gen, stream.getmtime(path), stream.getsize(path))
        except (FileNotFoundError, OSError, mft.CheckpointCorrupt):
            return None

    def start(self) -> "ModelReloader":
        if self.poll_s > 0 and self._thread is None:
            self._thread = threading.Thread(target=self._watch,
                                            name="serve-reload-watch",
                                            daemon=True)
            self._thread.start()
        return self

    def close(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def _changed(self, fp: Optional[Tuple]) -> bool:
        """When both fingerprints carry a manifest generation, only a
        generation move counts — the npz lands before its manifest, so a
        new mtime under the old generation is a save in progress, not a
        model to load (reloading mid-write would burn a failure)."""
        if fp is None or fp == self._cur:
            return False
        if self._cur is None:
            return True
        if fp[1] is not None and self._cur[1] is not None:
            return fp[0] != self._cur[0] or fp[1] != self._cur[1]
        return True

    def _watch(self) -> None:
        while not self._stop.wait(self.poll_s):
            fp = self._fingerprint()
            if self._changed(fp):
                log.info("model watcher: %s changed (generation %s); "
                         "reloading", fp[0], fp[1])
                self.reload()

    # ----------------------------------------------------------- reload
    def reload(self, path: Optional[str] = None) -> dict:
        """Load + verify + swap, synchronously on the calling thread.
        Returns {'ok', 'model_generation'} or {'ok': False, 'error'} —
        the old model keeps serving on any failure."""
        from .model import open_serving_store, store_geometry
        target = path or self.model_uri
        with self._reload_mu:
            fp = self._fingerprint() if path is None else None
            try:
                # fallback=False: reloading must never silently regress
                # to an older generation — the current in-memory model IS
                # the fallback
                store, meta, _ = open_serving_store(target, self._kwargs,
                                                    fallback=False)
                blue = self.executor
                if (store_geometry(store.param)
                        != store_geometry(blue.store.param)
                        and self._server is not None):
                    gen = self._bluegreen_swap(blue, store)
                else:
                    gen = blue.swap_store(store)
            except Exception as e:
                # lint: ok(data-race) monotonic counter for #stats; stats()
                # must not block on _reload_mu held across loads
                self.reload_failures += 1
                from ..obs import counter
                counter("model_reload_failures_total",
                        "failed hot-reloads (old model kept)").inc()
                log.warning("model reload from %s failed; keeping the "
                            "current model: %s", target, e)
                return {"ok": False, "error": str(e)}
            # lint: ok(data-race) monotonic counter for #stats (see above)
            self.reloads += 1
            from ..obs import counter
            counter("model_reloads_total",
                    "successful model hot-reloads").inc()
            if fp is not None:
                self._cur = fp
            log.info("model reloaded from %s: generation %d",
                     meta["path"], gen)
            return {"ok": True, "model_generation": gen,
                    "path": meta["path"]}

    # ------------------------------------------------------- blue/green
    def _bluegreen_swap(self, blue, store) -> int:
        """Geometry-changing swap: build + warm a green executor, then
        retarget the server atomically. Runs on the reloading thread
        (watcher or a connection reader) — scoring keeps flowing through
        blue on the batcher thread the whole time. Any failure (corrupt
        warm, injected ``reload.warm`` fault) propagates to the reload
        failure path: green is dropped, blue keeps serving."""
        from concurrent.futures import ThreadPoolExecutor

        from .executor import PredictExecutor
        # lint: ok(data-race) status tag for #stats/#health: GIL-atomic
        # str assignment; stats() must not block on _reload_mu mid-warm
        self.swap_state = "warming"
        try:
            caps, keys = blue.warm_set()
            workers = max(1, min(self.warm_workers, len(keys) or 1))
            log.info("blue/green: warming %d buckets on %d threads for "
                     "geometry (V_dim=%d, hash_capacity=%d)", len(keys),
                     workers, store.param.V_dim,
                     store.param.hash_capacity)
            green = PredictExecutor(store)
            green.seed_caps(caps)

            def _warm_one(key):
                # chaos point: err aborts the swap (blue keeps serving),
                # delay_ms stretches the warm window (the drain-vs-
                # reload race tests live here)
                faultinject.fire("reload.warm")
                green.warm_bucket(key)

            t0 = time.monotonic()
            if workers == 1:
                for key in keys:
                    _warm_one(key)
            else:
                # independent XLA compilations release the GIL, so the
                # warm-set compiles overlap instead of queueing — the
                # swap window shrinks with the pool. Any worker failure
                # propagates out of the result iteration and aborts the
                # swap before the commit point below.
                with ThreadPoolExecutor(
                        max_workers=workers,
                        thread_name_prefix="bluegreen-warm") as pool:
                    for _ in pool.map(_warm_one, keys):
                        pass
            # lint: ok(data-race) gauge for #stats (see swap_state)
            self.last_warm_ms = (time.monotonic() - t0) * 1e3
            self.swap_state = "swapping"
            green.generation = blue.generation + 1
            self._server.swap_executor(green)
            # lint: ok(data-race) monotonic counter for #stats (see above)
            self.bluegreen_swaps += 1
            self._server.obs.counter(
                "serve_bluegreen_swaps_total",
                "geometry-changing blue/green executor swaps").inc()
            log.info("blue/green: swapped to generation %d (%d buckets "
                     "warm)", green.generation, len(keys))
            return green.generation
        finally:
            self.swap_state = "idle"

    # ------------------------------------------------------------ stats
    def stats(self) -> dict:
        return {"reloads": self.reloads,
                "reload_failures": self.reload_failures,
                "bluegreen_swaps": self.bluegreen_swaps,
                "last_warm_ms": round(self.last_warm_ms, 3),
                "swap_state": self.swap_state}
