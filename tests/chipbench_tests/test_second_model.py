"""The rehearsal of a `model_config` PR: a second training model added to a
copy of the benchmark as files and appended entries only.

`second_model/` holds what such a PR would bring: `models/nextid.py` (not a
transformer and not a sequence pair; its own parameters, traffic kind,
count and plain float32 reference), a configuration that names it under
`model`, a traffic file and the entries to append. chipbench_tiny.py copies
the three files into the temporary root's `chipbench/` and appends the
entries; nothing that is there is edited, `entries/train.py` least of all.
All on the CPU."""
import io
import json
import os
import sys
from contextlib import redirect_stdout

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_tiny as tiny  # noqa: E402

from chipbench import correct, manifest  # noqa: E402
from chipbench import run as cb_run  # noqa: E402

CELL = "nextid_train"
APPENDED = ["train_mfu.nextid", "train_op_ms_per_step.mul.nextid",
            "compiles_in_window.nextid"]


def _run(root, *argv):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = cb_run.main(list(argv), root=root)
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("second"),
                          second_model=True)


def test_the_manifest_validates_with_the_appended_entries(root):
    man = manifest.Manifest(root).validate()
    real = json.load(open(os.path.join(tiny.REPO, "BENCHMARK.json")))
    names = [m["name"] for m in man.doc["per_layer"]]
    # appended: the entries that were there keep their order before them
    assert names[-len(APPENDED):] == APPENDED
    assert names[:-len(APPENDED)] == [m["name"] for m in real["per_layer"]]
    assert man.config("nextid_small")["model"] == "nextid"
    assert {m["name"] for m in man.cell_per_layer(CELL)} == set(APPENDED)
    assert [m["name"] for m in man.cell_end_to_end(CELL)] == [
        "train_tokens_per_s", "setup_s"]
    # the cell that was there reports what it reported
    assert not set(APPENDED) & {
        m["name"] for m in man.cell_per_layer("nmt_train_1chip")}
    # only files were added beside the copies of the benchmark's own
    for sub in ("entries", "metrics", "models"):
        ours = set(os.listdir(os.path.join(tiny.REPO, "chipbench", sub)))
        theirs = set(os.listdir(os.path.join(root, "chipbench", sub)))
        assert theirs - ours - {"__pycache__"} <= {"nextid.py"}, sub
        for name in ours - {"__pycache__"}:
            with open(os.path.join(tiny.REPO, "chipbench", sub, name)) as a, \
                    open(os.path.join(root, "chipbench", sub, name)) as b:
                assert a.read() == b.read(), name


def test_a_model_file_that_is_not_there_is_refused(tmp_path):
    root = tiny.make_root(tmp_path, second_model=True)
    os.remove(os.path.join(root, "chipbench", "models", "nextid.py"))
    with pytest.raises(manifest.ManifestError, match="models/nextid.py"):
        manifest.Manifest(root).validate()


@pytest.mark.parametrize("trace", [0, 1])
def test_the_second_models_cell_prints_the_contract_line(root, trace):
    rc, res = _run(root, "--workload", CELL, "--seed", str(2**31 + 29),
                   "--seconds", "1", "--trace", str(trace))
    assert rc == 0 and res["correct"] is True and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"loss_gap", "grad_gap", "delta_gap"}
    assert res["attempted"] >= 1 and res["device"]["platform"] == "cpu"
    if trace:
        # the rehearsal claims no device metric; the count is reported
        assert set(res["metrics"]) == {"compiles_in_window.nextid"}
        assert res["metrics"]["compiles_in_window.nextid"]["value"] == 0
        assert res["device"]["busy_s"] == 0.0
    else:
        assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
        assert all(v["value"] > 0 for v in res["metrics"].values())


def test_the_first_models_cell_still_runs_beside_it(root):
    rc, res = _run(root, "--workload", "nmt_train_1chip", "--seed", "29",
                   "--seconds", "1", "--trace", "0")
    assert rc == 0 and res["correct"] is True


@pytest.mark.parametrize("fault", ["half_batch", "state_unchanged"])
def test_the_second_models_broken_path_comes_out_not_correct(
        root, monkeypatch, fault):
    Trainer = manifest.Manifest(root).driver("train").Trainer
    real = Trainer.step
    if fault == "state_unchanged":
        monkeypatch.setattr(Trainer, "step", lambda self, feed: 4.0)
    else:
        def step(self, feed):
            return real(self, {k: v[:len(v) // 2] for k, v in feed.items()})
        monkeypatch.setattr(Trainer, "step", step)
    rc, res = _run(root, "--workload", CELL, "--seed", "31",
                   "--seconds", "1", "--trace", "0")
    assert rc == 0 and res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_the_second_models_control_and_faults_read_apart(root):
    """`--control 1` drives the driver's control block through the model's
    own reference: int8, the state left unchanged, half the batch."""
    rc, res = _run(root, "--workload", CELL, "--seed", "37", "--seconds",
                   "1", "--trace", "0", "--control", "1")
    assert rc == 0 and res["correct"] is True
    limits = manifest.Manifest(root).config("nextid_small")["limits"]
    control = res["control"]
    assert set(control) == {"int8", "state_unchanged", "half_batch"}
    for fault in ("state_unchanged", "half_batch"):
        assert not correct.judge(control[fault], limits)[1], fault
    assert control["state_unchanged"]["delta_gap"] == pytest.approx(1.0)
