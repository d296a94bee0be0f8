"""Serving launcher: hosts a model behind the rFaaS stack and drives a
stream of equal-length prompts through it.

The path is the served one: ``ServeEngine`` -> ``Invoker`` -> lease ->
``ExecutorWorker`` thread -> ``ModelServer`` -> device.  Weights are
random, generated from ``--seed``; nothing is downloaded.  One warm-up
wave compiles prefill and decode before the timed requests, and its time
is reported as compile time.

    PYTHONPATH=src python -m repro.launch.serve --arch h2o-danube-3-4b
    PYTHONPATH=src python -m repro.launch.serve --smoke   # reduced, CPU
"""
from __future__ import annotations

import argparse
import os
import time
from dataclasses import dataclass
from typing import List, Optional

import jax
import numpy as np

from repro.configs import get_config, get_smoke
from repro.configs.base import ArchConfig
from repro.core import BatchSystem, Invoker, Ledger, ResourceManager
from repro.distribution.context import make_context
from repro.distribution.sharding import param_shardings
from repro.models.factory import build_model
from repro.serving import ModelServer, ServeEngine
from repro.serving.engine import GenRequest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the cache
    there and nothing is set here.  Otherwise the cache sits at the fixed
    path ``<repo>/.jax_cache``: the path is part of the cache's key, so a
    directory that moved would never hit."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def init_params(model, seed: int):
    """Random weights from ``seed``, generated on the device(s) where they
    will live: the model's mesh when it has one, else the default
    device."""
    key = jax.random.PRNGKey(seed)
    if not model.dist.active:
        return jax.jit(model.init)(key)
    shapes = jax.eval_shape(model.init, key)
    return jax.jit(model.init,
                   out_shardings=param_shardings(model, shapes))(key)


def peak_bytes(devices) -> Optional[int]:
    """Largest ``peak_bytes_in_use`` over ``devices``; None where the
    backend keeps no such count."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


@dataclass
class ServeRun:
    """What one ``serve`` call measured, on the host's clock."""
    arch: str
    platform: str
    device_kind: str
    n_devices: int
    init_s: float              # weights generated and placed
    compile_s: float           # warm-up wave: prefill + decode compiles
    wall_s: float              # timed requests, first enqueue to last token
    requests: List[GenRequest]
    bill_invocations: int
    bill_compute_s: float
    peak_bytes: Optional[int]
    server: ModelServer

    @property
    def tokens(self) -> int:
        return sum(len(r.tokens_out) for r in self.requests)

    def lines(self) -> List[str]:
        out = [f"arch={self.arch} device={self.platform}:{self.device_kind}"
               f" x{self.n_devices}",
               f"init_s={self.init_s:.3f} compile_s={self.compile_s:.3f}"]
        for r in self.requests:
            out.append(f"request {r.request_id}: ttft_s={r.ttft:.4f} "
                       f"latency_s={r.latency:.4f} "
                       f"tokens={len(r.tokens_out)}")
        out.append(f"served {len(self.requests)} requests / {self.tokens} "
                   f"tokens in {self.wall_s:.3f} s | "
                   f"{self.tokens / self.wall_s:.2f} tok/s")
        out.append(f"bill: {self.bill_invocations} invocations, "
                   f"{self.bill_compute_s:.3f} s compute")
        out.append(f"peak_bytes_in_use={self.peak_bytes}")
        return out


def serve(cfg: ArchConfig, *, n_requests: int = 8, batch: int = 4,
          prompt_len: int = 512, new_tokens: int = 16, max_len: int = 2048,
          seed: int = 0, nodes: int = 2, churn: bool = False,
          mesh=None) -> ServeRun:
    """Serve ``n_requests`` random prompts of ``prompt_len`` tokens through
    leases, ``batch`` per wave.  With ``mesh`` the weights are sharded
    over it by ``param_shardings``."""
    if n_requests % batch:
        raise ValueError(f"{n_requests} requests do not fill waves of "
                         f"{batch}: a short wave would compile in the "
                         "timed window")
    if prompt_len + new_tokens > max_len:
        raise ValueError(f"prompt {prompt_len} + {new_tokens} new tokens "
                         f"exceed max_len {max_len}")
    # without a mesh the model lives on the default device alone
    devices = (list(mesh.devices.flat) if mesh is not None
               else jax.devices()[:1])
    model = build_model(cfg, make_context(mesh))
    t0 = time.perf_counter()
    params = jax.block_until_ready(init_params(model, seed))
    init_s = time.perf_counter() - t0
    server = ModelServer(model, params, max_len=max_len)

    ledger = Ledger()
    rm = ResourceManager(n_replicas=2)
    cluster = BatchSystem(rm, ledger, n_nodes=nodes, workers_per_node=2,
                          hot_period=10.0)
    cluster.release_idle()
    rm.start_heartbeats()
    invoker = Invoker("serve", rm, server.make_library(), seed=seed)
    try:
        invoker.allocate(1)
        rng = np.random.default_rng(seed)
        prompt = lambda: rng.integers(1, cfg.vocab_size, size=prompt_len)

        warm = ServeEngine(invoker, batch_size=batch)
        for _ in range(batch):
            warm.enqueue(prompt(), max_new_tokens=2)
        t0 = time.perf_counter()
        warm.run()
        compile_s = time.perf_counter() - t0

        engine = ServeEngine(invoker, batch_size=batch)
        t0 = time.perf_counter()
        for _ in range(n_requests):
            engine.enqueue(prompt(), max_new_tokens=new_tokens)
            if churn:
                cluster.churn_step(p_claim=0.1, p_release=0.3)
                if invoker.n_workers == 0:
                    invoker.allocate(1)
        done = engine.run()
        wall_s = time.perf_counter() - t0
    finally:
        invoker.deallocate()
        rm.stop()
    bill = ledger.bill("serve")
    d0 = devices[0]
    return ServeRun(cfg.name, d0.platform, d0.device_kind, len(devices),
                    init_s, compile_s, wall_s, done, bill.invocations,
                    bill.compute_seconds, peak_bytes(devices), server)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="h2o-danube-3-4b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config of --arch, for CPU runs")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=512)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=2048)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--nodes", type=int, default=2)
    ap.add_argument("--churn", action="store_true",
                    help="run batch-system churn during serving")
    return ap.parse_args(argv)


def main(argv=None) -> ServeRun:
    args = parse_args(argv)
    use_compile_cache()
    cfg = (get_smoke if args.smoke else get_config)(args.arch)
    run = serve(cfg, n_requests=args.requests, batch=args.batch,
                prompt_len=args.prompt_len, new_tokens=args.new_tokens,
                max_len=args.max_len, seed=args.seed, nodes=args.nodes,
                churn=args.churn)
    for line in run.lines():
        print(line)
    return run


if __name__ == "__main__":
    main()
