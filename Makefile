# Local CI gate — the same checks .github/workflows/ci.yml runs.
# (Reference analog: Makefile `make test` + .travis.yml.)
#
#   make test   - full pytest suite on a virtual 8-device CPU mesh
#   make smoke  - driver entry smoke (catches a broken artifact)
#   make ci     - both

PY ?= python
# obs-report inputs: the metrics JSONL a run wrote (metrics_path knob)
# and optionally its Chrome trace (DIFACTO_TRACE)
METRICS ?= run.metrics.jsonl
TRACE ?=
# convert inputs (make convert): text in -> rec2 cache out
DATA_IN ?= data.txt
DATA_FORMAT ?= criteo
DATA_OUT ?= $(basename $(DATA_IN)).rec

.PHONY: test smoke ci lint lint-changed lint-baseline lockmap jitmap \
	hlomap chaos fleet-chaos online-chaos durability-chaos obs-report \
	convert

test:
	$(PY) -m pytest tests/ -x -q

# difacto-lint (docs/static_analysis.md): compileall as a cheap syntax
# pass, then the AST analyzer — concurrency/JAX/registry-drift rules
# over difacto_tpu/, tools/, launch.py. Exit 0 = no
# unsuppressed, non-baselined findings. LINT_FORMAT=github emits PR
# annotations (ci.yml uses it).
LINT_FORMAT ?= text
lint:
	$(PY) -m compileall -q difacto_tpu tests tools launch.py
	$(PY) tools/lint.py --format=$(LINT_FORMAT)

# fast local loop: local rules only on files changed vs the merge-base
# (worktree edits + untracked included); cross-file and concurrency
# rules still see the whole tree — their findings can live in files the
# change never touched
lint-changed:
	$(PY) tools/lint.py --changed-only --format=$(LINT_FORMAT)

# regenerate the grandfathered-finding baseline INTENTIONALLY (e.g.
# after adding a rule that flags pre-existing code you are not fixing
# in the same change) — never to silence a finding you just introduced
lint-baseline:
	$(PY) tools/lint.py --write-baseline

# merged static+dynamic lock-order graph, with each lock labeled by the
# fields the race pass proves it guards (docs/static_analysis.md):
#   make lockmap                          # static model only
#   make lockmap LOCKTRACE=run.locks.json # + a DIFACTO_LOCKTRACE_OUT dump
LOCKTRACE ?=
lockmap:
	$(PY) tools/lockmap.py --dot lockmap.dot --json lockmap.json \
	  $(if $(LOCKTRACE),--dynamic $(LOCKTRACE))

# merged static+dynamic jit-program map: every jit site with its
# compile-key verdict, plus a real run's per-site compile counts and
# fetch points (docs/static_analysis.md v4):
#   make jitmap                            # static model only
#   make jitmap JAXTRACE=run.jax.json      # + a DIFACTO_JAXTRACE_OUT dump
JAXTRACE ?=
jitmap:
	$(PY) tools/jitmap.py --json jitmap.json \
	  $(if $(JAXTRACE),--dynamic $(JAXTRACE))

# merged static+dynamic sharding map (docs/static_analysis.md v5): the
# shardflow layout-pin verdicts next to a compiled-HLO collective/
# memory scan of the REAL fs=4 train step + serve executor on the CPU
# virtual mesh, plus a bounded-delay leg (--tau 4) driving the windowed
# fs=4 train step through the 2+τ pipeline. --check fails on any
# table-axis all-gather/all-to-all, temp-budget breach, or scan site
# outside the static model:
#   make hlomap                            # scan + merge + gate
#   make hlomap HLOSCAN=run.hlo.json       # merge a DIFACTO_HLOSCAN_OUT dump
HLOSCAN ?=
hlomap:
	$(PY) tools/hlomap.py --json hlomap.json \
	  $(if $(HLOSCAN),--dynamic $(HLOSCAN),--scan --fs 4 --tau 4) --check

# resilience suite alone (fault injection, drain, blue/green, takeover,
# client failover — tests/test_chaos.py and friends)
chaos:
	$(PY) -m pytest tests/ -m chaos -q

# fleet suite alone (rolling restart behind the router under load,
# abort-on-regression legs, router peer retry, shared blacklist, the
# router HA group + elastic autoscaler compound scenario —
# docs/serving.md "Fleet operations", "Router HA & autoscaling")
fleet-chaos:
	$(PY) -m pytest tests/ -m chaos -q -k "fleet or router or rolling or autoscale"

# online-learning loop suite alone (serve→log→train→reload under
# injected faults and a SIGKILL'd trainer — docs/serving.md
# "Continuous learning")
online-chaos:
	$(PY) -m pytest tests/ -m chaos -q -k online

# durability suite alone (WAL append/replay faults, torn replicas, the
# SIGKILL + disk-loss recovery ladder leg — docs/serving.md
# "Durability & recovery")
durability-chaos:
	$(PY) -m pytest tests/ -m chaos -q -k "wal or replica or durab"

smoke:
	$(PY) -c "import jax, __graft_entry__; \
	fn, args = __graft_entry__.entry(); \
	jax.block_until_ready(jax.jit(fn)(*args)); \
	__graft_entry__.dryrun_multichip(8); \
	print('entry + dryrun ok')"

ci: lint test hlomap fleet-chaos durability-chaos smoke

# human summary of a run's observability artifacts (docs/observability.md):
#   make obs-report METRICS=run.metrics.jsonl TRACE=run.trace.json
obs-report:
	$(PY) tools/obs_report.py --metrics $(METRICS) $(if $(TRACE),--trace $(TRACE))

# one-time text -> rec2 convert: parallel across cores, zero-copy
# members out.
#   make convert DATA_IN=criteo.txt DATA_FORMAT=criteo [DATA_OUT=criteo.rec]
convert:
	$(PY) -m difacto_tpu task=convert data_in=$(DATA_IN) \
	  data_format=$(DATA_FORMAT) data_out=$(DATA_OUT) data_out_format=rec
