"""A whole run on the CPU at a small size, with the timed path broken
underneath: each fault the cell can have must turn ``correct`` false, and
the sound program must not. Also the control: the program's own
lower-precision path (bf16 tiles) must fail the comparison."""
import dataclasses
import sys

import pytest

from benchutil import BENCH, run_cell, tiny_root  # noqa: F401 (fixture)

sys.path.insert(0, str(BENCH))
from harness import faults  # noqa: E402

# a fit cell's window always finishes one job: this gives exactly one, so
# the batches compared do not depend on how fast the host is
ONE_JOB = 0.01


def test_sound_fit_is_correct(tiny_root, capsys):
    res = run_cell(tiny_root, capsys, "mnist-tab1.restarts", seconds=ONE_JOB)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"fit_rows_per_s", "nmi", "setup_s"}


@pytest.mark.parametrize("fault", ["half_batch", "label_altered"])
@pytest.mark.parametrize("workload", ["mnist-tab1.restarts",
                                      "imnist-8m.stream"])
def test_inner_faults_are_caught(tiny_root, capsys, monkeypatch, fault,
                                 workload):
    faults.plant(fault, monkeypatch.setattr)
    res = run_cell(tiny_root, capsys, workload, seconds=ONE_JOB)
    assert not res["correct"], res["checks"]


def test_truncated_inner_loop_fails_the_fixpoint(tiny_root, capsys,
                                                 monkeypatch):
    # labels one step from u0 keep their counts, cost and medoids
    # consistent with themselves: only the fixpoint number sees the cut.
    # (A stream's later batches start at a fixpoint of the carried state
    # at this size, so one iteration is all they need there.)
    # One fit of seed 4 at this size: its cost is still within its limit.
    faults.plant("max_iters_1", monkeypatch.setattr)
    res = run_cell(tiny_root, capsys, "mnist-tab1.restarts", seed=4,
                   seconds=ONE_JOB)
    c = res["checks"]
    assert not res["correct"]
    assert c["label_regret"]["value"] > c["label_regret"]["limit"], c
    assert c["cost_rel_err"]["value"] < c["cost_rel_err"]["limit"], c
    assert c["rows_mismatch"]["value"] == 0, c


@pytest.mark.parametrize("workload", ["mnist-tab1.restarts",
                                      "imnist-8m.stream"])
def test_state_left_unchanged_is_caught(tiny_root, capsys, monkeypatch,
                                        workload):
    faults.plant("state_unchanged", monkeypatch.setattr)
    res = run_cell(tiny_root, capsys, workload, seconds=ONE_JOB)
    assert not res["correct"], res["checks"]


def test_lower_precision_control_fails(tiny_root, capsys, monkeypatch):
    from harness.fit import FitDriver
    base = FitDriver.base_config

    def bf16(self, p, seed):
        return dataclasses.replace(base(self, p, seed), precision="bf16")
    monkeypatch.setattr(FitDriver, "base_config", bf16)
    res = run_cell(tiny_root, capsys, "mnist-tab1.restarts", seconds=ONE_JOB)
    assert not res["correct"], res["checks"]
    assert res["checks"]["cost_rel_err"]["value"] > \
        res["checks"]["cost_rel_err"]["limit"]


def test_sound_serving_is_correct(tiny_root, capsys):
    res = run_cell(tiny_root, capsys, "mnist-tab1.assign-poisson")
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 100
    assert res["metrics"]["setup_s"]["value"] > 0


def test_served_label_altered_is_caught(tiny_root, capsys, monkeypatch):
    faults.plant("served_label_altered", monkeypatch.setattr)
    res = run_cell(tiny_root, capsys, "mnist-tab1.assign-poisson")
    assert not res["correct"], res["checks"]
