"""The check must fail a broken timed path, and the control.

Each test plants one fault underneath a whole rehearsed run (CPU, tiny
size, the chip's kernels interpreted) and sees ``correct`` come out
false, with the number that should catch it over its limit:

* a learner step that returns its state unchanged (model, policy);
* half of the batch left out of the model loss, the mean over the rest;
* an answer altered where it is produced (one reward of the farm);
* a window's drain written wrong into the ring (one reward of each
  burst the window's drains write, set-up's left sound);
* the lower-precision control: the reference computed in bf16x3 put in
  the program's place.

A one-chip cell has no exchange between chips to leave out.
"""
from __future__ import annotations

import jax.numpy as jnp
import pytest

import steer

SEED = 2147483917


def _run(monkeypatch, tmp_path, plant, cell="metrpo-arm7.unpaced64"):
    run = steer.steer(monkeypatch, tmp_path)
    plant(monkeypatch)
    rc, line, err = steer.rehearse(run, "--workload", cell, "--seed",
                                   str(SEED), "--seconds", "2")
    assert rc == 0, err[-3000:]
    return line


def _failing(line):
    return {k for k, c in line["checks"].items()
            if c["limit"] is None or not c["value"] <= c["limit"]}


def model_unchanged(mp):
    from repro.mbrl import dynamics
    mp.setattr(dynamics, "_sgd_epoch_scan",
               lambda opt, params, opt_state, *a, **k:
               (params, opt_state, jnp.zeros(())))


def policy_unchanged(mp):
    from repro.mbrl import trpo
    mp.setattr(trpo, "trpo_step", lambda params, batch, **k: (params, {}))


def half_batch(mp):
    from repro.mbrl import dynamics
    loss = dynamics.masked_mse_loss

    def half(params, obs, act, next_obs):
        n = obs.shape[0]
        return loss(params, obs, act, next_obs, jnp.arange(n) < n // 2)
    mp.setattr(dynamics, "mse_loss", half)


def answer_altered(mp):
    from repro.envs.base import Env
    batch = Env.rollout_batch

    def altered(self, *a, **k):
        out = batch(self, *a, **k)
        return {**out, "rew": out["rew"].at[0, 0].add(1.0)}
    mp.setattr(Env, "rollout_batch", altered)


def window_drain_altered(mp):
    from harness import runner
    instrument = runner.instrument

    def planted(tr, ev, config):    # set-up is done: the window's drains
        buf = tr.model_worker.buffer
        chunk = buf._write_chunk

        def altered(trajs, h, val):
            first = {**trajs[0], "rew": trajs[0]["rew"].at[0].add(1.0)}
            return chunk([first] + list(trajs[1:]), h, val)
        buf._write_chunk = altered
        instrument(tr, ev, config)
    mp.setattr(runner, "instrument", planted)


@pytest.mark.parametrize("plant,caught", [
    (model_unchanged, {"model_grad", "model_change"}),
    (policy_unchanged, {"policy_step", "policy_change"}),
    (half_batch, {"model_grad"}),
    (answer_altered, {"rollout"}),
    (window_drain_altered, {"window_ring"}),
], ids=lambda x: getattr(x, "__name__", ""))
def test_fault_reads_incorrect(plant, caught, monkeypatch, tmp_path):
    line = _run(monkeypatch, tmp_path, plant)
    assert line["correct"] is False
    assert caught <= _failing(line), line["checks"]


@pytest.mark.parametrize("cell", ["metrpo-arm7.paced64",
                                  "meppo-arm7.paced64"])
def test_control_reads_incorrect(cell, monkeypatch, tmp_path):
    from harness import reference, runner
    setup = runner.setup

    def control(tr, config, traffic):
        prog = setup(tr, config, traffic)
        ctl = reference.run_setup(config, traffic, SEED, prog["rounds"],
                                  mm=reference.Matmul(bf16x3=True))
        return {**prog, **{k: ctl[k] for k in (
            "trajs", "ring", "ring_val", "val_loss", "model0", "model_opt1",
            "model3",
            "imagined_return", "policy0", "policy1", "policy3")}}
    monkeypatch.setattr(runner, "setup", control)
    line = _run(monkeypatch, tmp_path, lambda mp: None, cell)
    assert line["correct"] is False, line["checks"]
