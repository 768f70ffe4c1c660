"""The names of the tracing system, fixed in one place.

Four vocabularies, each read by somebody outside the program
(docs/observability.md, PERF.md section 3, ``perfbench/spans.py``), so a
rename here is a change to what a metric reads:

- **legs** — ``jax.named_scope`` names inside the step program. They are
  HLO metadata (``op_name`` paths), so they reach the profiler's device
  timeline (an ``XLA Ops`` event's ``tf_op``) and change no arithmetic.
  XLA fuses across scopes; a fused operation belongs to the leg of its
  fusion's root. Each scope also sets the frontend attribute
  ``leg=<name>`` on its operations: metadata is stripped from the
  persistent compile cache's key, so without it a program compiled
  before a scope was added or moved would be served from the cache with
  its OLD ``op_name`` paths and the trace would attribute by them. The
  attribute is part of the program text, so the key follows the scopes.
- **stages** — the ``stage`` labels of ``stage_seconds_total``; every one
  is produced by :func:`difacto_tpu.obs.stage`, so each also has a span
  of the same name with the same start and end.
- **spans** — host spans that have no counter (children of a stage, or
  boundaries nobody sums).
- **step fill counters** — what the dispatched steps' padded dimensions
  hold, summed by ``SGDLearner._enqueue``, label ``job=train|eval``.
- **the epoch's record** — ``epoch.counts``, one span a training epoch
  whose arguments are what the epoch did: the differences of the
  counters above since the previous epoch's end (``COUNT_ARGS``).
"""

from __future__ import annotations

import contextlib
import functools

# ------------------------------------------------------------------ legs
UNPACK = "unpack"        # unpack_panel/unpack_batch, dedup_tokens
GATHER = "gather"        # the fused-row gather of the batch's slots
FORWARD = "forward"      # rows_to_params, predict, objective, AUC
BACKWARD = "backward"    # calc_grad
UPDATE = "update"        # row_epilogue / FTRL on the gathered rows
SCATTER = "scatter"      # the write-back of the updated rows
EVALUATE = "evaluate"    # the epoch-end full-table penalty/nnz

LEGS = (UNPACK, GATHER, FORWARD, BACKWARD, UPDATE, SCATTER, EVALUATE)

# ------------------------------------------------------------ sub-scopes
# ``jax.named_scope`` names nested INSIDE a leg, never a leg of their own:
# the 8-bit rows' (slot_dtype int8/fp8) elementwise codes <-> float32
# (ops/fused.dequant_half, quant_half). In the train step ``dequant``
# sits in ``forward`` (rows_to_params, and after each token gather of
# the codes, losses/fm._code_taker) and ``update`` (row_epilogue),
# ``requant`` in ``update``; in ``evaluate`` and the table's init they
# sit in that program. A leg reader takes the last LEG on an op_name
# path, so every leg's time is what it was; ``perfbench/quant.py`` reads
# the time whose fusion root lies under one of these.
DEQUANT = "dequant"
REQUANT = "requant"
SUB_SCOPES = (DEQUANT, REQUANT)

# ---------------------------------------------------------------- stages
STAGE_METRIC = "stage_seconds_total"
STAGE_HELP = ("seconds spent per pipeline stage, summed over threads "
              "(one obs.stage boundary each: a span of the same name "
              "has the same start and end)")

PARSE = "parse"              # read+parse half of the producer pipeline
PACK = "pack"                # localize/slot-map/pack half
RING_WAIT = "ring_wait"      # producer blocked on a free shm-ring slot
TRANSFER = "transfer"        # host->device staging of packed buffers
DISPATCH = "dispatch"        # host time to enqueue one step program
FETCH_WAIT = "fetch_wait"    # blocked in the metric fetch
STEP = "step"                # dispatch + fetch_wait, by construction
EPOCH_TURN = "epoch_turn"    # final fetch's return -> next first enqueue
COMPILE = "compile"          # backend-compile seconds (jax.monitoring)

STAGES = (PARSE, PACK, RING_WAIT, TRANSFER, DISPATCH, FETCH_WAIT, STEP,
          EPOCH_TURN, COMPILE)

# spans under which a stage's seconds are recorded, where the span's name
# predates the stage's and dashboards know it
STAGE_SPAN = {PARSE: "producer.parse", PACK: "producer.pack",
              RING_WAIT: "producer.ring_wait"}

# --------------------------------------------------- step fill counters
# each reaches the traced window through the epoch's record (EPOCH_COUNTS
# below); PERF.md section 3 names the benchmark metric that reads it
STEPS = "steps_total"                # steps of the dispatched programs
STEP_DISPATCHES = "step_dispatches_total"  # enqueues: 2 steps each paired
STEP_ROW_CAP = "step_row_cap_total"  # sum of the steps' unique-row caps
STEP_ROWS = "step_rows_total"        # sum of their distinct table rows
# of the steps that carry a chunked-run backward layout (ops/batch.py):
# rows / cap is the fill of every u_cap-sized leg, chunks / cap that of
# the backward's chunk gather and partial scatter; a sticky cap that
# grew mid-run shows as a step in the ratio
STEP_CHUNK_CAP = "step_chunk_cap_total"  # sum of the steps' chunk caps
STEP_CHUNKS = "step_chunks_total"        # sum of the chunks they need
# of the mesh panel steps whose table legs take a shard's owned run
# (ops/fused.gather_rows, mesh_fs > 1): rows / cap is the fill of the
# run, cap / step_row_cap_total ~ 1/fs says the run engaged (1: it did
# not, the fullest shard owned a whole row cap)
STORE_OWNED_ROWS = "store_owned_rows_total"  # sum of the fullest shard's rows
STORE_OWNED_CAP = "store_owned_cap_total"    # sum of the steps' own_cap

# backend compiles by the jitted function's name, from the listener that
# feeds ``stage_seconds_total{stage=compile}`` (obs/stage.py); a load
# from the persistent cache counts (and takes milliseconds)
COMPILES = "compiles_total"
COMPILES_HELP = ("backend compiles by the jitted function's name (a load "
                 "from the persistent compile cache counts)")

# 1 where the job's panel forward gathers a 16-bit source that fits fast
# memory: 8-bit rows' codes with w and the V scale (losses/fm.packs_codes)
# or float32 [w | V] rows as two 16-bit halves (losses/fm.packs_forward),
# else 0; set once when the learner builds its step programs, label
# ``job=train``. Trace/#metrics only
STEP_FORWARD_PACKED = "step_forward_packed"

# ---------------------------------------------------------- model gauges
# set at every training epoch's end from the scalars the epoch line
# prints (SGDLearner.run), label ``job=train``; they ride the epoch's
# record as ``nnz_w`` and ``live_V``
MODEL_NNZ_W = "model_nnz_w"          # nnz(w): an l1 model's product
MODEL_PENALTY = "model_penalty"      # l1 |w| + l2/2 w^2 over the table
# rows with a live embedding (cnt > V_threshold met w != 0): what the
# memory-adaptive FM allocates; nnz(w) charges V_dim for each
MODEL_LIVE_V = "model_live_V"

# ----------------------------------------------------------------- spans
EPOCH = "epoch"
CONSUMER_DISPATCH = "consumer.dispatch"
MERGE_STACK = "merge.stack"        # the eager stack before a fetch
TURN_MERGE = "epoch.merge"
TURN_EVAL = "epoch.eval_scalars"
TURN_EVICT = "epoch.evict_check"
TURN_CALLBACKS = "epoch.callbacks"
TURN_ITER_PARTS = "replay.iter_parts"
COMPILE_PAIR = "compile.pair_exec"
# the epoch's record: no length, its arguments are the content. Emitted
# by ``SGDLearner.run`` inside the open ``epoch_turn``, between
# ``epoch.eval_scalars`` and ``epoch.evict_check``
EPOCH_COUNTS = "epoch.counts"
# its arguments after ``epoch`` and ``job``, each the change of a series
# of the learner's registry since the previous record (``compile_s`` in
# seconds, the rest whole numbers), then the two model gauges as they
# stand. ``perfbench/counts.py`` sums them between the window's marks (a
# test pins its list to this one)
COUNT_ARGS = ("steps", "dispatches", "examples", "row_cap", "rows",
              "chunk_cap", "chunks", "own_cap", "own_rows",
              "gather_bytes", "exchange_bytes", "compile_s", "compiles",
              "nnz_w", "live_V")

# the children of ``epoch_turn``: idle time under one of them is idle
# time of the turn even where the turn's own span is cut by the start or
# the stop of a profiler session
TURN_CHILDREN = (TURN_MERGE, TURN_EVAL, TURN_EVICT, TURN_CALLBACKS,
                 TURN_ITER_PARTS)


@contextlib.contextmanager
def scope(name: str):
    """Trace the body under leg ``name``: ``jax.named_scope(name)`` plus
    the frontend attribute ``leg=name`` (see the module's docstring).
    The one seam every leg goes through (a test stubs it to prove the
    scopes change no arithmetic)."""
    import jax
    from jax.experimental.xla_metadata import set_xla_metadata
    with jax.named_scope(name), set_xla_metadata(leg=name):
        yield


@contextlib.contextmanager
def sub_scope(name: str):
    """Trace the body under sub-scope ``name`` of the enclosing leg:
    ``jax.named_scope(name)`` plus the frontend attribute
    ``part=name`` (in the program text, so the compile cache's key
    follows it, as ``leg=`` does for legs)."""
    import jax
    from jax.experimental.xla_metadata import set_xla_metadata
    with jax.named_scope(name), set_xla_metadata(part=name):
        yield


def leg(name: str):
    """Decorator: trace the function's body under leg ``name``."""
    def deco(fn):
        @functools.wraps(fn)
        def scoped(*args, **kw):
            with scope(name):
                return fn(*args, **kw)
        return scoped
    return deco
