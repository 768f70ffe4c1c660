"""Online serving subsystem: low-latency batched inference (ISSUE 2)
with a resilient model lifecycle (ISSUE 3).

The missing vertical between "trains the model" and the north star's
"serves heavy traffic": load a trained model weights-only into a
read-only SlotStore (model.py — manifest-verified, walking back to the
newest good generation if the latest is torn), score through a small set
of pre-jitted shape-bucketed predict programs (executor.py — zero
steady-state recompiles), amortize accelerator dispatch over many small
requests with a dynamic micro-batcher (batcher.py — bounded queue,
explicit shed on overload), and speak newline-delimited data rows over
threaded TCP (server.py, client.py — retrying, with `#health` /
`#reload` control lines). Hot-reload swaps a newly-trained model in
without a restart (reload.py); SIGTERM drains admitted work and exits 0
(server.py drain). The continuity layer (ISSUE 5) removes the last
restarts: a geometry-changing reload runs a blue/green executor swap
(reload.py), `#handoff` + SO_REUSEPORT hand the port to a successor
process with zero dropped traffic (server.py, tools/takeover.py), and
ServeClient fails over across a replica endpoint list (client.py).
The fleet layer (ISSUE 6) scales continuity from one replica pair to N:
a health-gated rolling-restart orchestrator replaces replicas one at a
time and aborts on any `#health` regression (fleet.py, tools/fleet.py),
a thin router balances rows with power-of-two-choices and retries
unanswered tails on a peer (router.py), and a shared advisory-locked
blacklist file propagates one client's endpoint ejection to the whole
fleet (fleethealth.py). ``task=serve`` (__main__.py) is the CLI entry;
tools/loadgen.py drives it open-loop; tests/test_chaos.py proves the
failure paths under injected faults (utils/faultinject.py).
"""

from __future__ import annotations

import logging
from dataclasses import dataclass, field

from ..config import KWArgs, Param
from ..utils.manifest import CheckpointCorrupt
from .autoscale import Autoscaler
from .batcher import MicroBatcher, ServeStats
from .client import ServeClient
from .executor import PredictExecutor, sigmoid
from .fleet import (HealthGate, drain_endpoint, notify_backends,
                    run_rolling_restart, run_router_group_roll,
                    run_takeover)
from .fleethealth import FleetHealth
from .model import model_meta, open_serving_store, resolve_model_path
from .reload import ModelReloader
from .router import RouterServer
from .server import ServeServer

log = logging.getLogger("difacto_tpu")


@dataclass
class ServeParam(Param):
    """task=serve knobs (docs/serving.md)."""
    model_in: str = ""
    serve_host: str = "127.0.0.1"
    serve_port: int = 0                 # 0 = ephemeral, logged at startup
    # flush a micro-batch at this many rows ...
    serve_batch_size: int = field(default=256, metadata=dict(lo=1))
    # ... or when the oldest queued request has waited this long
    serve_max_delay_ms: float = field(default=2.0, metadata=dict(lo=0))
    # admission bound, in ROWS of queued work; beyond it requests shed
    serve_queue_cap: int = field(default=1024, metadata=dict(lo=1))
    # reject single rows wider than this before they reach the executor
    # (bounds the shape buckets a hostile/buggy client can compile)
    serve_max_row_nnz: int = field(default=4096, metadata=dict(lo=1))
    # throttle for the reporter stats row (seconds)
    serve_report_every: float = 30.0
    # exit after this many seconds; 0 = serve until interrupted
    serve_max_seconds: float = 0.0
    # write "host port\n" here once listening (scripts/tests poll it)
    serve_ready_file: str = ""
    # graceful shutdown: on SIGTERM/SIGINT stop accepting, answer new
    # rows "!shed draining", wait this long for admitted work to
    # resolve, then exit 0 (serve/server.py drain)
    serve_drain_timeout_s: float = field(default=10.0, metadata=dict(lo=0))
    # hot-reload watcher: poll model_in every this many seconds and swap
    # a new generation in without a restart (0 = off; `#reload` over the
    # wire works either way — serve/reload.py)
    serve_reload_poll_s: float = field(default=0.0, metadata=dict(lo=0))
    # bind the listening socket SO_REUSEPORT so a successor process can
    # bind the SAME port while this replica drains (`#handoff`,
    # tools/takeover.py). Every replica of a takeover pair needs it set,
    # incumbent included — the kernel rejects mixed bindings.
    serve_takeover: bool = False
    # `#handoff <ready_file>`: wait at most this long for the successor
    # before draining anyway (the handoff asked this replica to leave)
    serve_handoff_wait_s: float = field(default=30.0, metadata=dict(lo=0))
    # online continuous learning (online/, docs/serving.md "Continuous
    # learning"): append every served row to this training-log
    # directory; the tailing trainer (task=online) consumes it. Empty =
    # no logging. NOTE: one log instance per directory — CLI replicas
    # need per-replica directories (or share one in-process OnlineLog
    # built by the embedding harness, as the tests do).
    online_log_dir: str = ""
    # rows per sealed rec2 segment
    online_segment_rows: int = field(default=256, metadata=dict(lo=1))
    # feedback-join horizon: how long a served row waits for its
    # delayed label before resolving to the default
    label_delay_s: float = field(default=1.0, metadata=dict(lo=0))
    # what an unlabeled row becomes past the horizon: drop it, or keep
    # it with label 0 (the ad-click non-click convention)
    label_default: str = field(default="negative", metadata=dict(
        enum=["drop", "negative"]))
    data_format: str = "libsvm"
    pred_prob: bool = True


def run_serve(kwargs: KWArgs) -> KWArgs:
    """CLI entry for task=serve (__main__.py): build the read-only store
    from the model file's own metadata (walking back to the newest
    generation that verifies if the latest is torn), start the server
    with the hot-reload and drain machinery attached, block. SIGTERM and
    SIGINT trigger a graceful drain and a zero exit so orchestrators see
    a clean rotation, not a crash."""
    import signal
    import threading

    param, remain = ServeParam.init_allow_unknown(kwargs)
    if not param.model_in:
        raise ValueError("please set model_in")
    # the store-construction kwargs (updater overrides + serve_mesh_fs)
    # also go to the reloader: a hot reload must rebuild the SAME store
    # geometry — in particular the same fs-sharded mesh — or the swap
    # would silently de-shard the table
    store_kwargs = list(remain)
    store, meta, remain = open_serving_store(param.model_in, remain)
    online_log = None
    if param.online_log_dir:
        from ..online.log import OnlineLog
        online_log = OnlineLog(param.online_log_dir,
                               segment_rows=param.online_segment_rows,
                               label_delay_s=param.label_delay_s,
                               label_default=param.label_default)
    server = ServeServer(
        store, host=param.serve_host, port=param.serve_port,
        batch_size=param.serve_batch_size,
        max_delay_ms=param.serve_max_delay_ms,
        queue_cap=param.serve_queue_cap,
        pred_prob=param.pred_prob, data_format=param.data_format,
        max_row_nnz=param.serve_max_row_nnz,
        report_every_s=param.serve_report_every,
        drain_timeout_s=param.serve_drain_timeout_s,
        takeover=param.serve_takeover,
        handoff_wait_s=param.serve_handoff_wait_s,
        online_log=online_log)
    server.ready_file = param.serve_ready_file
    # server= attaches the blue/green path: a geometry-changing reload
    # warms a second executor and swaps it under the batcher instead of
    # failing (serve/reload.py)
    reloader = ModelReloader(server.executor, param.model_in,
                             poll_s=param.serve_reload_poll_s,
                             kwargs=store_kwargs, server=server)
    server.reloader = reloader
    # signal.signal only works on the main thread; tests drive run_serve
    # from worker threads and manage shutdown themselves
    if threading.current_thread() is threading.main_thread():
        def _graceful(signum, _frame):
            log.info("signal %d: draining (timeout %.1fs)", signum,
                     param.serve_drain_timeout_s)
            server.drain()
        signal.signal(signal.SIGTERM, _graceful)
        signal.signal(signal.SIGINT, _graceful)
    server.start()
    reloader.start()
    if param.serve_ready_file:
        from ..utils import stream
        with stream.open_stream(param.serve_ready_file, "w") as f:
            f.write(f"{server.host} {server.port}\n")
    try:
        server.wait(param.serve_max_seconds or None)
    except KeyboardInterrupt:  # pragma: no cover - interactive exit
        log.info("interrupted; shutting down")
    finally:
        reloader.close()
        server.close()
        if online_log is not None:
            # flush, do NOT end(): a restarting replica must not
            # terminate the trainer's tail — only the operator (or the
            # harness driving the loop) ends the log
            online_log.flush()
        log.info("serve done: %s", server.stats_snapshot())
    return remain


__all__ = ["ServeParam", "run_serve", "ServeServer", "ServeClient",
           "PredictExecutor", "MicroBatcher", "ServeStats", "sigmoid",
           "model_meta", "open_serving_store", "resolve_model_path",
           "ModelReloader", "CheckpointCorrupt", "RouterServer",
           "FleetHealth", "HealthGate", "run_rolling_restart",
           "run_takeover", "Autoscaler", "run_router_group_roll",
           "notify_backends", "drain_endpoint"]
