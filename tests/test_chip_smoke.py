"""``chip_smoke.py``: its refusals, and a CPU rehearsal of its phases.

The script itself only runs on a TPU chip. Here it must refuse (no
chip, or no checkout around it) without printing a result; and its
phases are rehearsed end to end at tiny widths with the device check
steered to the CPU and every dispatcher steered to the Pallas branch the
chip takes, interpreted.
"""
import dataclasses
import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from repro.kernels.gmm import ops as gmm_ops
from repro.kernels.imag import ops as imag_ops

ROOT = Path(__file__).resolve().parent.parent
SCRIPT = ROOT / "chip_smoke.py"


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod        # dataclasses look the module up
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("where", ["alone", "checkout_without_chip"])
def test_chip_smoke_refuses(where, tmp_path):
    if where == "alone":
        shutil.copy(SCRIPT, tmp_path / SCRIPT.name)
        script, cwd = tmp_path / SCRIPT.name, tmp_path
    else:
        script, cwd = SCRIPT, ROOT
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0, proc.stdout
    assert '"ok"' not in proc.stdout, proc.stdout


def test_chip_smoke_rehearsal_on_cpu(monkeypatch, capsys):
    smoke = _load()
    monkeypatch.setattr(smoke, "PLATFORM", "cpu")
    monkeypatch.setattr(smoke, "INTERPRET", True)
    tiny = dataclasses.replace(
        smoke.Widths(), n_models=3, model_hidden=32, policy_hidden=16,
        imagine_batch=64, imagine_horizon=6, envs_per_collector=4,
        total_trajs=10)
    monkeypatch.setattr(smoke, "Widths", lambda: tiny)
    # the chip's dispatch, interpreted: the model loss through the gmm
    # kernel and its custom_vjp, imagination through the imag kernel
    mlp, step = gmm_ops.ensemble_mlp, imag_ops.fused_step
    monkeypatch.setattr(gmm_ops, "ensemble_mlp",
                        lambda m, x, impl=None, interpret=False: mlp(
                            m, x, impl=impl or "pallas", interpret=True))
    monkeypatch.setattr(imag_ops, "default_impl", lambda: "pallas")
    monkeypatch.setattr(imag_ops, "fused_step",
                        lambda *a, interpret=False, **kw: step(
                            *a, interpret=True, **kw))
    assert smoke.main([]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu",
                               "count": 1}}
    assert sum("[parity]" in ln and " ok" in ln for ln in lines) == 4
    assert any(ln.startswith("[trainer] trajs=10/10") for ln in lines)
