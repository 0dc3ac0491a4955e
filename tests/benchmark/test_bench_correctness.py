"""``correct`` holds for a sound run and fails for the control and for each
fault a cell can have.

Runs go through ``run.run`` on a small checkout (``conftest.checkout``),
past the look for a chip, on the CPU.  Each fault is planted underneath the
timed path, in the program's own entry points:

* a step that returns its state unchanged (``train.loop.make_train_step``);
* half of the batch left out, the loss a mean over the rest
  (``GNNBundle.loss_fn``);
* an answer altered where it is produced (``ServeEngine.process_batch``).

The cells run on one chip, so no exchange between chips can be left out.
The control, the reference one step of precision below the configuration's
in the program's place, must fail one of the cell's limits.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_cell

import harness
import readings
from run import Timers

TRAIN_CELLS = ("gcn-tiny.train", "gcn-tiny3.train")


def failed_checks(out):
    return {k for k, c in out["checks"].items() if c["value"] > c["limit"]}


@pytest.mark.parametrize("cell", TRAIN_CELLS + ("gcn-tiny.serve",))
def test_a_sound_run_is_correct(checkout, cell):
    out = run_cell(checkout, cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["compiles_in_window"] == 0
    assert list(out)[-1] == "checks"
    want = {"setup_s"} | ({"step_ms"} if cell.endswith("train")
                          else {"serve_p50_ms", "serve_p95_ms"})
    if cell == "gcn-tiny3.train":       # borrows gcn-arxiv.train's metrics
        want.add("train_edges_per_s")
    assert set(out["metrics"]) == want
    assert out["device"]["count"] == 1


def test_a_step_that_keeps_its_state_is_caught(checkout, monkeypatch):
    import repro.train.loop as loop
    real = loop.make_train_step

    def frozen(loss_fn, opt, clip_norm=1.0, donate=True):
        step = real(loss_fn, opt, clip_norm, donate=False)

        def keep(params, opt_state, batch):
            _, _, loss = step(params, opt_state, batch)
            return params, opt_state, loss
        return keep
    monkeypatch.setattr(loop, "make_train_step", frozen)
    out = run_cell(checkout, "gcn-tiny.train")
    assert not out["correct"]
    assert {"grad_gap", "change_gap"} <= failed_checks(out)


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_half_the_batch_left_out_is_caught(checkout, monkeypatch, cell):
    from repro.configs.families import GNNBundle
    real = GNNBundle.loss_fn

    def half(self, shape, executor="segment", exec_plan=None):
        loss = real(self, shape, executor, exec_plan)

        def on_half(params, batch):
            mask = batch["train_mask"]
            keep = jnp.arange(mask.shape[0]) < mask.shape[0] // 2
            return loss(params, dict(batch, train_mask=mask & keep))
        return on_half
    monkeypatch.setattr(GNNBundle, "loss_fn", half)
    out = run_cell(checkout, cell)
    assert not out["correct"]
    assert "loss_gap" in failed_checks(out)


def test_an_altered_answer_is_caught(checkout, monkeypatch):
    from repro.serve.engine import ServeEngine
    real = ServeEngine.process_batch
    calls = []

    def altered(self, mb):
        emb = real(self, mb)
        calls.append(1)
        if len(calls) % 50 == 0:
            emb = emb.copy()
            emb[0, 0] += 1e-2 * max(1.0, float(np.abs(emb).max()))
        return emb
    monkeypatch.setattr(ServeEngine, "process_batch", altered)
    out = run_cell(checkout, "gcn-tiny.serve")
    assert not out["correct"]
    assert failed_checks(out) == {"served_gap"}


def _driver(root, cell):
    spec = harness.load_spec(str(root))
    w = harness.workload(spec, cell)
    traffic = harness.traffic(w["traffic"], str(root))
    drv = harness.driver(traffic["driver"])
    cfg = harness.config(spec, w["config"], str(root))
    return drv, drv.Driver(cfg, traffic, 11, Timers()), harness.limits(
        cell, str(root))


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_the_training_control_fails(checkout, cell):
    harness.add_program_path()
    with jax.default_matmul_precision("float32"):
        drv, d, limits = _driver(checkout, cell)
    d.release()
    ctrl = readings.train_control(drv, d)
    assert any(ctrl[k] > limits[k] for k in ctrl), (ctrl, limits)


def test_the_serving_control_fails(checkout):
    harness.add_program_path()
    with jax.default_matmul_precision("float32"):
        drv, d, limits = _driver(checkout, "gcn-tiny.serve")
        d.window(0.5)
    d.release()
    ctrl = readings.serve_control(drv, d)
    assert ctrl["served_gap"] > limits["served_gap"], (ctrl, limits)
