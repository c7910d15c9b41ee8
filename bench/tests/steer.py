"""Steer the benchmark onto the CPU at a tiny size, for rehearsals.

The benchmark itself accepts only a chip. A rehearsal loads ``run.py``,
lets its device check accept the CPU, shrinks the cell's configuration
and traffic (widths, ensemble, imagination, ring, robots), and sends the
kernels down the Pallas path the chip takes, interpreted. Nothing here
is an option of the benchmark: the steering lives in the tests.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import io
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {"n_models": 3, "model_hidden": 32, "imagine_batch": 1024,
        "imagine_horizon": 8, "ring_trajs": 20}
TINY_ROBOTS = 8


def load_run():
    spec = importlib.util.spec_from_file_location("bench_run",
                                                  BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def shrink(cell):
    return dataclasses.replace(
        cell, config={**cell.config, **TINY},
        traffic={**cell.traffic, "robots_per_collector": TINY_ROBOTS})


def steer(monkeypatch, tmp_path=None):
    """Device check on the CPU, tiny cells, interpreted Pallas kernels,
    no persistent compile cache. Returns the loaded ``run`` module."""
    from harness import cells, peaks

    from repro.kernels.gmm import ops as gmm_ops
    from repro.kernels.imag import ops as imag_ops
    from repro.core import clear_eval_cache, clear_rollout_cache
    clear_rollout_cache()       # no compiled farm of an earlier rehearsal
    clear_eval_cache()
    run = load_run()
    monkeypatch.setattr(run, "PLATFORM", "cpu")
    if tmp_path is not None:            # traces stay out of the checkout
        monkeypatch.setattr(run, "OUT", tmp_path / "bench_out")
    # rehearsal only: the CPU has no entry in the table of chip peaks
    monkeypatch.setitem(peaks.PEAKS, "cpu", {"flops": 1e12,
                                             "hbm_bytes_per_s": 1e11})
    monkeypatch.setattr(run, "configure_jax", _no_cache(run.configure_jax))
    load = cells.load
    monkeypatch.setattr(cells, "load", lambda name: shrink(load(name)))
    mlp, step = gmm_ops.ensemble_mlp, imag_ops.fused_step
    monkeypatch.setattr(gmm_ops, "ensemble_mlp",
                        lambda m, x, impl=None, interpret=False: mlp(
                            m, x, impl=impl or "pallas", interpret=True))
    monkeypatch.setattr(imag_ops, "default_impl", lambda: "pallas")
    monkeypatch.setattr(imag_ops, "fused_step",
                        lambda *a, interpret=False, **kw: step(
                            *a, interpret=True, **kw))
    return run


def _no_cache(configure):
    def wrapped(config):
        import jax
        configure(config)
        jax.config.update("jax_compilation_cache_dir", None)
    return wrapped


def rehearse(run, *argv) -> tuple:
    """Run ``run.main`` in this process; (exit code, result line, stderr)."""
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = run.main(list(argv))
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), err.getvalue()
