"""The training driver's comparison at a test size on the CPU, with the
reference at `high` precision in the program's place (the cell itself is
not in BENCHMARK.json; see PERF.md)."""

import io
import time

import _paths  # noqa: F401
import pytest

from harness import spec
from harness.drivers import train
from harness.runner import run


def _cell():
    cell = spec.make_cell("aids_train_b128", "simgnn_aids_gcn_nobias",
                          "train_b128")
    cell.traffic.update(batch_pairs=8, pool_batches=4)
    cell.limits = {"loss_rel_gap": 1e-3, "grad_norm_gap": 1e-2,
                   "update_norm_gap": 1e-2}
    return cell


def _run(tmp_path):
    return run(_cell(), 2**31 + 5, 0.3, False, t_start=time.perf_counter(),
               control="high", require_device=False, cache=False,
               runs_dir=str(tmp_path), log=io.StringIO())


def test_reference_at_high_precision_follows_the_reference(tmp_path):
    result = _run(tmp_path)
    checks = result["checks"]
    assert set(checks) == {"loss_rel_gap", "grad_norm_gap",
                           "update_norm_gap", "skipped_steps",
                           "error_counters"}
    assert 0 < checks["loss_rel_gap"]["value"] < 1e-3
    assert 0 < checks["grad_norm_gap"]["value"] < 1e-2
    assert result["attempted"] > 0


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        tmp_path, monkeypatch):
    sound = train.Driver._control_step

    def frozen(self, state, batch):
        _, loss = sound(self, state, batch)
        return state, loss

    monkeypatch.setattr(train.Driver, "_control_step", frozen)
    result = _run(tmp_path)
    assert not result["correct"]
    assert result["checks"]["update_norm_gap"]["value"] == pytest.approx(1.0)


def test_norm_gap_is_taken_by_the_worst_leaf_against_the_median_floor():
    import numpy as np

    ref = [np.ones(4), np.ones(4) * 1e-9, np.ones(4) * 2]
    prog = [np.ones(4) * 1.1, np.ones(4) * 2e-9, np.ones(4) * 2]
    # leaf 0: 0.2 / 2 = 0.1; leaf 1: 2e-9 / 2 (the median leaf's norm).
    assert train.norm_gap(prog, ref) == pytest.approx(0.1)
