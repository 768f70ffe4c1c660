"""CLI task tests: train / pred / dump / convert round trips on the fixture,
mirroring the reference's main.cc dispatch (src/main.cc:66-90)."""

import os

import numpy as np
import pytest

from difacto_tpu.__main__ import main


def test_cli_train_pred_dump(rcv1_path, tmp_path, capsys):
    model = str(tmp_path / "model")
    conf = tmp_path / "train.conf"
    conf.write_text(
        f"data_in = {rcv1_path}\n"
        "# comment line\n"
        "lr = 1\nl1 = 1\nl2 = 1\n"
        "batch_size = 100\nmax_num_epochs = 3\nshuffle = 0\n"
        "num_jobs_per_epoch = 1\nreport_interval = 0\n"
        f"model_out = {model}\n")
    assert main([str(conf)]) == 0
    assert os.path.exists(model + "_part-0")

    pred_out = str(tmp_path / "pred")
    assert main([str(conf), "task=pred", f"model_in={model}",
                 f"data_val={rcv1_path}", f"pred_out={pred_out}"]) == 0
    assert len(open(pred_out + "_part-0").readlines()) == 100

    dump_out = str(tmp_path / "dump.tsv")
    assert main(["task=dump", f"model_in={model}_part-0",
                 f"name_dump={dump_out}", "need_reverse=true"]) == 0
    lines = open(dump_out).read().strip().splitlines()
    assert lines
    # need_reverse=true: ids are back in the original (small) libsvm space
    ids = [int(l.split("\t")[0]) for l in lines]
    assert max(ids) < 1 << 17


def test_cli_convert_roundtrip(rcv1_path, tmp_path):
    rec_dir = str(tmp_path / "cache.rec")
    assert main(["task=convert", f"data_in={rcv1_path}",
                 "data_format=libsvm", f"data_out={rec_dir}",
                 "data_out_format=rec"]) == 0
    back = str(tmp_path / "back.libsvm")
    assert main(["task=convert", f"data_in={rec_dir}", "data_format=rec",
                 f"data_out={back}", "data_out_format=libsvm"]) == 0

    from difacto_tpu.data import Reader
    a = [b for b in Reader(rcv1_path, "libsvm")]
    b = [b for b in Reader(back, "libsvm")]
    na, nb = sum(x.size for x in a), sum(x.size for x in b)
    assert na == nb == 100
    ia = np.concatenate([x.index for x in a])
    ib = np.concatenate([x.index for x in b])
    np.testing.assert_array_equal(ia, ib)
    va = np.concatenate([x.values_or_ones() for x in a])
    vb = np.concatenate([x.values_or_ones() for x in b])
    np.testing.assert_allclose(va, vb, rtol=1e-5)


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("conf,overrides", [
    ("local.conf", ["report_interval=0"]),
    ("fm.conf", ["report_interval=0"]),
    ("lbfgs.conf", []),   # report_interval is an sgd-family knob
    ("bcd.conf", []),
])
def test_cli_example_confs_train(conf, overrides, monkeypatch, caplog):
    # every runnable example conf trains end-to-end through the CLI
    # (epochs capped; fixture paths inside the confs are repo-relative);
    # a key falling through the whole chain only WARNS in main(), so the
    # key-rot guard here is the absence of that warning
    monkeypatch.chdir(REPO)
    with caplog.at_level("WARNING", logger="difacto_tpu"):
        assert main([os.path.join(REPO, "examples", conf),
                     "max_num_epochs=2"] + overrides) == 0
    rot = [r.message for r in caplog.records
           if "unknown config key" in r.getMessage()]
    assert not rot, f"unconsumed keys in examples/{conf}: {rot}"


@pytest.mark.parametrize("conf,shrink", [
    # shrink the tables (last occurrence wins) so the guard doesn't
    # allocate the confs' production-size state just to check keys
    ("criteo_hashed.conf", ["hash_capacity=4096", "V_dim=2"]),
    ("criteo_dict.conf", ["V_dim=2"]),
    # the flat table (V_dim = 0 stays): l1 logistic regression, one chip
    ("criteo_lr_l1.conf", ["hash_capacity=4096"]),
    # the memory-adaptive FM at the reference's default gates, one chip
    ("avazu_fm.conf", ["hash_capacity=4096"]),
])
def test_cli_example_conf_templates_parse(conf, shrink):
    # the criteo confs are templates (data_in commented out): guard them
    # against key rot — every key must be consumed by the learner chain
    # (an unknown key would survive init as a leftover). Their 2x4 mesh
    # builds on the 8 virtual devices the conftest provides. The kwargs
    # go through the same DifactoParam consumption main() applies.
    from difacto_tpu.__main__ import DifactoParam
    from difacto_tpu.config import parse_cli_args
    from difacto_tpu.learners import Learner
    kwargs = parse_cli_args(
        [os.path.join(REPO, "examples", conf)] + shrink)
    param, remain = DifactoParam.init_allow_unknown(kwargs)
    remain = Learner.create(param.learner).init(remain)
    assert not remain, f"unknown keys in examples/{conf}: {remain}"


def test_cli_bad_task(tmp_path):
    with pytest.raises(ValueError):
        main(["task=nonsense"])


def test_cli_usage():
    assert main([]) == 1
