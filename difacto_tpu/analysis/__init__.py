"""difacto-lint: an AST-based project analyzer (docs/static_analysis.md).

The tree is ~16k lines of multiprocess/multithreaded Python whose
correctness rests on conventions no generic tool checks: fault-point and
metric names are free strings that must stay in sync with the chaos
suite and the docs catalogs, ``#control`` lines must match on both ends
of the wire, shm-ring leases and sockets must be released on every path,
and the JAX hot loop silently miscompiles if a donated buffer is reused
or a jitted closure captures mutable state. This package encodes those
conventions as checkable rules — stdlib ``ast`` only, no new deps.

Layout:

- :mod:`core`        — rule framework: findings, ``# lint: ok(rule-id)``
  inline suppressions, the checked-in baseline, output formats, exit
  codes, the project index cross-file rules read.
- :mod:`localrules`  — single-file rules (thread lifecycle, lock
  release, resource close, the monotonic-clock contract, broad
  excepts, the three JAX tracing rules).
- :mod:`crossrules`  — project-wide registry-drift rules (fault points,
  metric names, ``#control`` lines, config knobs).
- :mod:`callgraph`   — the project-wide call graph (imports, methods,
  thread hand-off edges) the interprocedural layer is built on.
- :mod:`concurrency` — held-lock-set propagation over the call graph:
  lock-order cycle detection (``lock-order``), blocking-calls-under-
  lock (``lock-blocking``), Condition-wait discipline
  (``cond-wait-while``); the static half of the lock sentinel
  (utils/locktrace.py is the runtime half, tools/lockmap.py the
  merged view). Its one walk per function also records the shared-
  state accesses the race pass reads.
- :mod:`races`       — Eraser-style data-race detection (``data-race``):
  thread-root discovery, the shared-state index, per-field lockset
  intersection and GuardedBy inference; the static half of the
  shared-state sentinel (utils/shared.py is the runtime half).
- :mod:`jaxflow`     — JAX compile/transfer flow analysis
  (``jax-recompile`` compile-key boundedness, ``jax-host-sync``
  implicit device->host coercions on the hot path,
  ``jax-donate-flow`` cross-edge donation safety, ``jax-dtype64``
  fp32-pipeline drift); the static half of the jit/transfer sentinel
  (utils/jaxtrace.py is the runtime half, tools/jitmap.py the merged
  view).
- :mod:`shardflow`   — sharding-flow analysis (``jax-shard-break``
  fs-scoped programs must pin their output layout / no capacity-axis
  breakers, ``jax-shard-replicate`` no table-sized replication); the
  static half of the sharding sentinel
  (utils/hloscan.py — the compiled-HLO collective/memory scan — is
  the runtime half, tools/hlomap.py the merged view).
- :mod:`cli`         — ``python -m difacto_tpu.analysis`` /
  ``tools/lint.py`` / ``make lint`` (``--changed-only`` for the
  incremental loop; ``--format=sarif`` for code scanning).
"""

from .core import Finding, Project, all_rules, run_project  # noqa: F401
