"""CLI entry: ``python -m difacto_tpu config_file key1=val1 key2=val2 ...``

Equivalent of the reference binary's main (src/main.cc:54-90): parse the
config file + CLI overrides into KWArgs, dispatch on ``task``:

- ``train`` (default) — build the learner named by ``learner`` (default sgd),
  init with the remaining kwargs, run.
- ``pred`` — prediction with a saved model (routes to the learner's predict
  task, main.cc:70-77 sets task=pred and requires model_in).
- ``dump`` — binary model -> readable TSV (src/reader/dump.h).
- ``convert`` — data format conversion (src/reader/converter.h).
- ``serve`` — online inference server over a saved model (serve/: dynamic
  micro-batching over the bucketed predict executor; no reference analog —
  the WSDM'16 system trained the models its production stack served).
- ``online`` — continuous learning: tail a serve-fleet training log,
  checkpoint on a wall-clock cadence, push each generation to the fleet
  (online/: the serve→log→train→reload loop, docs/serving.md).

Unknown leftover keys warn, as in main.cc:40-46.
"""

from __future__ import annotations

import logging
import os
import sys
from dataclasses import dataclass, field

from .config import KWArgs, Param, parse_cli_args, warn_unknown
from .learners import Learner

log = logging.getLogger("difacto_tpu")


@dataclass
class DifactoParam(Param):
    task: str = field(default="train", metadata=dict(
        enum=["train", "dump", "pred", "convert", "serve", "online"]))
    learner: str = "sgd"


def _pred_routing_error(learner: str, kwargs: KWArgs) -> ValueError:
    """task=pred with a non-sgd learner: name the learner that actually
    produced model_in (from the checkpoint's own meta) and route the user
    at the tasks that exist, instead of the bare 'only supported by sgd'
    dead end."""
    model_in = next((v for k, v in reversed(kwargs) if k == "model_in"), "")
    produced = ""
    if model_in:
        try:
            from .serve.model import model_meta
            meta = model_meta(model_in)
            if meta["learner"]:
                produced = (f"; model_in={model_in!r} was produced by "
                            f"learner={meta['learner']!r}")
        except Exception as e:  # unreadable/missing model: keep the
            # base message, but leave a trace for whoever debugs it
            log.debug("model meta unreadable for %s: %s", model_in, e)
    return ValueError(
        f"task=pred runs the bucketed sgd predict executor and is not "
        f"implemented by learner={learner!r}{produced}. Batch-score sgd "
        f"models with learner=sgd, or use task=serve for online scoring "
        f"(docs/serving.md)")


@dataclass
class DumpParam(Param):
    """src/reader/dump.h:12-31."""
    updater: str = "sgd"
    model_in: str = ""
    name_dump: str = "dump.txt"
    need_reverse: bool = False
    dump_aux: bool = False


def run_dump(kwargs: KWArgs) -> KWArgs:
    from .store.local import SlotStore
    from .updaters.sgd_updater import SGDUpdaterParam

    param, remain = DumpParam.init_allow_unknown(kwargs)
    if not param.model_in:
        raise ValueError("please set model_in")
    if param.updater != "sgd":
        raise ValueError(f"unknown updater: {param.updater}")
    # V_dim is recorded in the checkpoint; probe it so the store allocates
    # the right row width before load
    from .utils import stream
    with stream.load_npz(param.model_in) as z:
        v_dim = int(z["V_dim"]) if "V_dim" in z.files else 0
    uparam, remain = SGDUpdaterParam.init_allow_unknown(remain)
    import dataclasses
    store = SlotStore(dataclasses.replace(uparam, V_dim=v_dim))
    store.load(param.model_in)
    n = store.dump(param.name_dump, param.dump_aux, param.need_reverse)
    log.info("dumped %d features to %s", n, param.name_dump)
    return remain


def main(argv: list[str] | None = None) -> int:
    logging.basicConfig(
        level=logging.INFO,
        format="[%(asctime)s] %(levelname)s %(message)s")
    argv = sys.argv[1:] if argv is None else argv
    if not argv:
        print("usage: python -m difacto_tpu config_file key1=val1 ...",
              file=sys.stderr)
        return 1

    # before the first backend touch — multihost initialize below binds
    # devices
    from .utils.device import bound_device, place_compile_cache
    cache_dir = place_compile_cache()

    if "DIFACTO_NPROCS" in os.environ:
        from .parallel.multihost import initialize
        initialize()

    kwargs = parse_cli_args(argv)
    param, remain = DifactoParam.init_allow_unknown(kwargs)
    if param.task != "convert":
        # convert is host-only and must not take the chip from a trainer
        # running beside it; every other task names what it bound, so a
        # process that could not get the accelerator and came up on the
        # CPU is visible in its first log line
        log.info("device: %s (compile cache: %s)", bound_device(),
                 cache_dir)

    if param.task in ("train", "pred"):
        if param.task == "pred" and param.learner != "sgd":
            # only the sgd learner implements the prediction task (like the
            # reference, where pred routes through SGDLearner's job types);
            # the error names the learner that made the model and points
            # at the serve path
            raise _pred_routing_error(param.learner, remain)
        learner = Learner.create(param.learner)
        if param.task == "pred":
            remain.append(("task", "2"))
        remain = learner.init(remain)
        warn_unknown(remain)
        from .parallel.fault import HostFailure, exit_code_for
        try:
            learner.run()
        except HostFailure as e:
            # a peer host died; exit with the recovery code so the
            # launcher (launch.py --max-restarts) evicts it and resumes
            # from the last checkpoint (parallel/fault.py)
            log.error("aborting for restart: %s", e)
            return exit_code_for(e.dead)
    elif param.task == "serve":
        from .serve import run_serve
        warn_unknown(run_serve(remain))
    elif param.task == "online":
        from .online import run_online
        warn_unknown(run_online(remain))
    elif param.task == "dump":
        warn_unknown(run_dump(remain))
    elif param.task == "convert":
        from .data.converter import Converter
        conv = Converter()
        warn_unknown(conv.init(remain))
        conv.run()
    return 0


if __name__ == "__main__":
    sys.exit(main())
