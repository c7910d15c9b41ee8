"""CLI demo of the serving tier on the reduced world model.

    PYTHONPATH=src python -m repro.serve [--requests 12] [--n-slots 4]

Submits a stream of random-token requests with mixed prompt lengths,
serves them with continuous batching, hot-swaps the model once mid-run
(simulating a training push), and prints the server stats as JSON.
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro.configs import get_config
from repro.core.servers import ParameterServer
from repro.launch.mesh import make_smoke_mesh
from repro.models import api
from repro.serve import WorldModelServer
from repro.utils.compile_cache import enable_compile_cache


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m repro.serve",
                                 description=__doc__)
    ap.add_argument("--arch", default="glm4-9b")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--n-slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=96)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch, reduced=True)
    key_w, key_w2 = jax.random.split(jax.random.key(args.seed))
    server_params = ParameterServer()
    ctx = api.shard_ctx(make_smoke_mesh())
    server_params.push(api._mod(cfg).init_params(cfg, ctx, key_w))
    srv = WorldModelServer(cfg, param_server=server_params,
                           n_slots=args.n_slots, max_seq=args.max_seq)

    rng = np.random.default_rng(args.seed)
    rids = []
    for i in range(args.requests):
        plen = int(rng.integers(4, srv.sched.buckets[-1] + 1))
        prompt = rng.integers(0, cfg.vocab_size, plen)
        rids.append(srv.submit(prompt, max_new=args.max_new))
        srv.step()
        if i == args.requests // 2:  # a mid-run training push
            server_params.push(api._mod(cfg).init_params(cfg, ctx, key_w2))
    srv.run()

    for rid in rids[:3]:
        print(f"request {rid}: {srv.result(rid).tolist()}")
    print(json.dumps(srv.stats(), indent=2, default=float))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
