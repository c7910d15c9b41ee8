"""The MB-MPO cell, ``mbmpo-arm7.paced64``, rehearsed on the CPU at the
rehearsal's tiny size (``steer.py``: the chip's kernels interpreted, so
the members' rollouts run the pallas path), and its two metrics.

* the sound program reads correct;
* the bf16x3 control and the half-batch fault, each put in the program's
  place, read incorrect on the policy learner's numbers;
* MB-MPO's earlier estimator, which differentiated each loss through the
  rollout it imagined (a pathwise derivative in place of VPG), planted
  in place of the program's, fails ``policy_step``;
* ``meta_imag_ms`` and ``meta_grad_ms`` on synthetic traces;
* the step's operation count at the cell's size.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

import steer
from harness import cells, layers, xplane

SEED = 2147483917
CELL = "mbmpo-arm7.paced64"
POLICY = {"policy_return", "policy_step", "policy_change"}


def _run(monkeypatch, tmp_path, plant, trace=0):
    run = steer.steer(monkeypatch, tmp_path)
    plant(monkeypatch)
    rc, line, err = steer.rehearse(run, "--workload", CELL, "--seed",
                                   str(SEED), "--seconds", "2", "--trace",
                                   str(trace))
    assert rc == 0, err[-3000:]
    return line


def _failing(line):
    return {k for k, c in line["checks"].items()
            if c["limit"] is None or not c["value"] <= c["limit"]}


def _reference_in_place(kind):
    """The reference's set-up, as the bf16x3 control (``"control"``) or
    with the half-batch fault (``"half_batch"``), put in the program's
    place."""
    def plant(mp):
        from harness import reference, runner
        kw = {"control": {"mm": reference.Matmul(bf16x3=True)},
              "half_batch": {"fault": "half_batch"}}[kind]
        setup = runner.setup

        def swapped(tr, config, traffic):
            prog = setup(tr, config, traffic)
            other = reference.run_setup(config, traffic, SEED,
                                        prog["rounds"], **kw)
            return {**prog, **{k: other[k] for k in (
                "trajs", "ring", "ring_val", "val_loss", "model0",
                "model_opt1", "model3", "imagined_return", "policy0",
                "policy1", "policy3")}}
        mp.setattr(runner, "setup", swapped)
    return plant


def pathwise(self, theta, model_params, key):
    """The estimator MB-MPO had: each surrogate built on a rollout
    imagined from the parameters being differentiated, so the gradient
    flows through the states, pre-tanh actions and advantages."""
    from repro.mbrl import algos
    cfg = self.cfg

    def loss(pol, member, k):
        k_s0, k_roll = jax.random.split(k)
        obs, pre, rew = algos._rollout_with_logp(
            member, pol, self.init_state_fn(k_s0, cfg.imagine_batch),
            k_roll, cfg.imagine_horizon, self.reward_fn, self.predict_fn)
        batch = algos._flat_batch(obs, pre, rew, cfg.gamma)
        return self._surrogate(pol, batch), rew.sum(0).mean()

    def per_member(th, m, k):
        member = self._member_params(model_params, m)
        k_in, k_out = jax.random.split(k)
        g, _ = jax.grad(loss, has_aux=True)(th, member, k_in)
        adapted = jax.tree.map(lambda p, gg: p - cfg.inner_lr * gg, th, g)
        return loss(adapted, member, k_out)

    def meta_loss(th):
        losses, rets = jax.vmap(per_member, (None, 0, 0))(
            th, jnp.arange(cfg.n_models),
            jax.random.split(key, cfg.n_models))
        return losses.mean(), rets.mean()

    (l, ret), g = jax.value_and_grad(meta_loss, has_aux=True)(theta)
    return g, None, {"meta_loss": l, "imagined_return": ret}


def plant_pathwise(mp):
    """On the XLA-fused imagination step: differentiating the pallas
    kernel's backward again under the earlier estimator's member vmap
    does not trace, so that estimator never ran on the chip's path."""
    from repro.kernels.imag import ops as imag_ops
    from repro.mbrl import algos
    mp.setattr(algos.MBMPO, "meta_gradient", pathwise)
    mp.setattr(imag_ops, "default_impl", lambda: "fused")


@pytest.mark.parametrize("trace", [0, 1])
def test_sound_run_is_correct(trace, monkeypatch, tmp_path):
    line = _run(monkeypatch, tmp_path, lambda mp: None, trace)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0
    if trace:
        assert "busy_s" in line["device"]
    else:
        assert {"setup_s", "policy_steps_per_s"} <= set(line["metrics"])


@pytest.mark.parametrize("plant,caught,each", [
    (_reference_in_place("control"), POLICY, False),    # one at least
    (_reference_in_place("half_batch"), POLICY, True),
    (plant_pathwise, {"policy_step"}, True),
], ids=["control", "half_batch", "pathwise"])
def test_fault_reads_incorrect(plant, caught, each, monkeypatch, tmp_path):
    line = _run(monkeypatch, tmp_path, plant)
    assert line["correct"] is False
    failing = _failing(line)
    assert (caught <= failing if each else caught & failing), line["checks"]


def test_step_ops():
    from harness import flops
    config = cells.load(CELL).config
    assert flops.policy_step(config) == 1_085_905_305_600


# ------------------------------------------------------- the two metrics
E = xplane.Event
KERNEL = 'fusion = f32[4096,37] custom-call(), ' \
         'custom_call_target="tpu_custom_call"'


STARTS = (1.1, 1.3, 1.5, 1.7, 1.95)


def _ctx(per_execution, extra_kernel=False, allocation=False, cut=None):
    """Four executions of the policy step whole inside the traced window
    [1, 2], with ``per_execution`` kernel calls of 0.1 ms each and one
    other operation of 1 ms; a fifth straddles the window's end.
    ``extra_kernel``: one more call in the third. ``allocation``: an
    event of XLA's own, of no duration, starts inside one call. ``cut``:
    the execution at that index is a part, 214 calls in 50 ms, as the
    profiler leaves of the step it cuts."""
    ops, mods = [], []
    for i, start in enumerate(STARTS):
        n, end = (214, start + 0.05) if i == cut else (per_execution,
                                                       start + 0.1)
        mods.append(E("jit__improve_impl(7)", start, end))
        t = start
        for _ in range(n):
            ops.append(E(KERNEL, t, t + 1e-4))
            t += 1e-4
        ops.append(E("fusion.3 = f32[64]", t, t + 1e-3))
    if extra_kernel:
        ops.append(E(KERNEL, 1.58, 1.5801))
    if allocation:
        ops.append(E('custom-call.4 = custom-call(), '
                     'custom_call_target="AllocateBuffer"', 1.1, 1.1))
    trace = xplane.Trace(
        {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": mods}},
        {"python": [E("traced", 1.0, 2.0)]})
    return layers.Context(cells.load(CELL), None, {"kind": "TPU v5 lite"},
                          trace=trace)


@pytest.mark.parametrize("allocation", [False, True])
def test_meta_metrics_read_the_member_rollouts(allocation):
    c = cells.load(CELL).config
    calls = 2 * c["n_models"] * c["imagine_horizon"]
    assert calls == 400
    ctx = _ctx(calls, allocation=allocation)
    imag = cells.metric_reader("meta_imag_ms")(ctx)
    grad = cells.metric_reader("meta_grad_ms")(ctx)
    assert imag == pytest.approx(calls * 0.1, rel=1e-6)     # 40 ms
    assert ctx.program_ms("jit__improve_impl") == pytest.approx(100.0)
    assert grad == pytest.approx(100.0 - calls * 0.1, rel=1e-6)


@pytest.mark.parametrize("cut", [0, 3])
def test_meta_metrics_leave_out_a_step_cut_at_the_trace_edge(cut):
    """The first or the last execution, cut by the profiler's start or
    stop, is left out; the three whole ones are read."""
    ctx = _ctx(400, cut=cut)
    assert len(ctx.executions("jit__improve_impl")) == 4
    assert cells.metric_reader("meta_imag_ms")(ctx) == pytest.approx(40.0)
    assert cells.metric_reader("meta_grad_ms")(ctx) == pytest.approx(60.0)


@pytest.mark.parametrize("per,extra,cut", [
    (40, False, None), (399, False, None), (400, True, None),
    (0, False, None), (400, False, 1), (400, False, 2)])
def test_meta_metrics_read_none_unless_every_step_holds_2KH(per, extra,
                                                            cut):
    """ME's 40 calls a step, one call missing in every step, one too many
    in one step, no kernel at all, or a step short of calls away from the
    trace's edges: neither metric reads anything."""
    ctx = _ctx(per, extra, cut=cut)
    assert cells.metric_reader("meta_imag_ms")(ctx) is None
    assert cells.metric_reader("meta_grad_ms")(ctx) is None


def test_meta_metrics_read_none_without_a_step():
    trace = xplane.Trace({"/device:TPU:0": {"XLA Ops": [],
                                            "XLA Modules": []}},
                         {"python": [E("traced", 1.0, 2.0)]})
    ctx = layers.Context(cells.load(CELL), None, {"kind": "TPU v5 lite"},
                         trace=trace)
    assert cells.metric_reader("meta_imag_ms")(ctx) is None
    assert cells.metric_reader("meta_grad_ms")(ctx) is None


assert steer  # puts the program and the benchmark on the path
