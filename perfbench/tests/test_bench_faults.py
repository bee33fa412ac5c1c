"""`correct` comes out false when the timed path is broken underneath,
and true when it is not. Each run skips the look for a chip and drives a
whole run at a size a CPU test can hold, with one fault planted in the
program: a step that returns its state unchanged, or half of every batch
left out with the mean taken over the rest."""
import jax
import pytest

import benchtest_util
from repro.core import client as client_lib
from repro.launch import train as train_lib


def _fleet_unchanged(orig):
    def make(spec, ccfg, tcfg):
        run = orig(spec, ccfg, tcfg)

        def broken(params, opt, batches, teacher, key):
            _, _, metrics = run(params, opt, batches, teacher, key)
            return params, opt, metrics
        return broken
    return make


def _fleet_half(orig):
    def make(spec, ccfg, tcfg):
        run = orig(spec, ccfg, tcfg)

        def broken(params, opt, batches, teacher, key):
            half = jax.tree.map(lambda a: a[:, : a.shape[1] // 2], batches)
            return run(params, opt, half, teacher, key)
        return broken
    return make


def _lm_unchanged(orig):
    def make(*a, **k):
        step = orig(*a, **k)

        def broken(state, batch, key, participation=None):
            _, metrics = step(state, batch, key)
            return state._replace(step=state.step + 1), metrics
        return broken
    return make


def _lm_half(orig):
    def make(*a, **k):
        step = orig(*a, **k)

        def broken(state, batch, key, participation=None):
            half = jax.tree.map(lambda x: x[:, : x.shape[1] // 2], batch)
            return step(state, half, key)
        return broken
    return make


FAULTS = {
    "fleet_unchanged": ("fleet-lenet5-n256", client_lib,
                        "make_local_update_fn", _fleet_unchanged),
    "fleet_half_batch": ("fleet-lenet5-n256", client_lib,
                         "make_local_update_fn", _fleet_half),
    "lm_unchanged": ("lm-xlstm125m-4x2048", train_lib, "make_train_step",
                     _lm_unchanged),
    "lm_half_batch": ("lm-xlstm125m-4x2048", train_lib, "make_train_step",
                      _lm_half),
}


@pytest.fixture(autouse=True)
def float32_lm(monkeypatch):
    """The CPU runs the small LM in float32, so that a sound run matches
    the reference to rounding and only the planted fault can fail it."""
    small = dict(benchtest_util.SMALL_CONFIG["xlstm-125m"], dtype="float32")
    monkeypatch.setitem(benchtest_util.SMALL_CONFIG, "xlstm-125m", small)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_makes_the_run_incorrect(monkeypatch, fault):
    cell, module, attr, plant = FAULTS[fault]
    monkeypatch.setattr(module, attr, plant(getattr(module, attr)))
    rc, line, _ = benchtest_util.run_small(monkeypatch, cell, 2 ** 33 + 3)
    assert rc == 0
    assert line["correct"] is False, line["checks"]


@pytest.mark.parametrize("cell", ["fleet-lenet5-n256",
                                  "lm-xlstm125m-4x2048"])
def test_a_sound_run_is_correct(monkeypatch, cell):
    rc, line, _ = benchtest_util.run_small(monkeypatch, cell, 2 ** 33 + 3)
    assert rc == 0
    assert line["correct"] is True, line["checks"]


def test_partial_participation_matches_the_reference():
    """Traffic that draws k of N clients per round (the static-k path that
    compacts the client axis) checks against the reference too."""
    from benchlib import compare
    cfg, mod = benchtest_util.small_config("lenet5-fleet")
    traffic = dict(benchtest_util.SMALL_TRAFFIC["fleet-lenet5-n256"],
                   clients=8, participation="uniform_k:3")
    limits = benchtest_util.small_workload("fleet-lenet5-n256")["limits"]
    cell = mod.Cell(cfg, traffic, seed=2 ** 33 + 5, devices=jax.devices()[:1],
                    limits=limits, log=lambda msg: None)
    cell.setup()
    assert cell.step()[0] == 3 * cell.samples_per_client
    cell.release()
    checks = cell.check()
    assert compare.all_within(checks), checks
