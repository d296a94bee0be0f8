"""Bring-up check of the leased serving path on a TPU.

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --four-chips   # mistral-nemo-12b over four chips

With no option, in one process:

1. the Pallas kernels that the TPU dispatch selects (``wkv6`` at
   rwkv6-1.6b's widths, ``selective_scan`` at jamba-1.5-large's) run once
   and are compared with their ``ref.py``;
2. h2o-danube-3-4b at its published width is served through
   ``ServeEngine`` -> ``Invoker`` -> lease -> ``ExecutorWorker`` ->
   ``ModelServer`` (``repro.launch.serve.serve``): 8 requests of 512
   tokens in waves of 4, 16 new tokens each, ``max_len`` 2048;
3. every served token must lie in the vocabulary and equal the token that
   ``ModelServer.prefill``/``decode`` give for the same prompts when
   called directly, without the invoker.

With ``--four-chips`` only this runs instead: mistral-nemo-12b's weights
are sharded over a ``(1, 4)`` ``("data", "model")`` mesh by
``param_shardings`` and served through the same path; beforehand, the
same seed at a depth that fits one chip is run unsharded and sharded, and
their logits are compared.

Weights are random, made from seed 0.  Any failure exits nonzero and
prints no result; the last line of a run that passed is one JSON object
naming the device.  With no TPU the script fails: it never falls back to
the CPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
SEED = 0                         # of the random weights, prompts and inputs


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


# ------------------------------------------------------------------ kernels
# y is rounded to bf16 (2^-8 relative) by kernel and reference alike; the
# f32 state differs only in summation order
KERNEL_TOLS = {"y": 1e-2, "state": 1e-3}


def kernel_cases():
    """(name, dispatching op, reference, args) at the widths the models
    run: rwkv6-1.6b's heads and jamba-1.5-large's d_inner."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.kernels.mamba_scan.ops import selective_scan
    from repro.kernels.mamba_scan.ref import selective_scan_ref
    from repro.kernels.rwkv6.ops import wkv6
    from repro.kernels.rwkv6.ref import wkv6_ref

    key = jax.random.PRNGKey(SEED)
    rand = lambda i, shape, dtype=jnp.bfloat16, lo=-1.0, hi=1.0: \
        jax.random.uniform(jax.random.fold_in(key, i), shape, jnp.float32,
                           lo, hi).astype(dtype)
    b, s = 1, 512

    rw = get_config("rwkv6-1.6b")
    H, hd = rw.n_heads, rw.rwkv.head_dim
    w = (jax.nn.sigmoid(rand(4, (b, H, s, hd), jnp.float32)) * 0.5
         + 0.45).astype(jnp.bfloat16)
    wkv_args = (rand(1, (b, H, s, hd)), rand(2, (b, H, s, hd)),
                rand(3, (b, H, s, hd)), w, rand(5, (H, hd)),
                rand(6, (b, H, hd, hd), jnp.float32))

    jm = get_config("jamba-1.5-large-398b")
    di, N = jm.mamba.expand * jm.d_model, jm.mamba.d_state
    scan_args = (rand(11, (b, s, di)),
                 (jax.nn.softplus(rand(12, (b, s, di), jnp.float32))
                  * 0.1).astype(jnp.bfloat16),
                 -jnp.exp(rand(13, (di, N), jnp.float32, 0.0, 1.0)),
                 rand(14, (b, s, N)), rand(15, (b, s, N)),
                 rand(16, (di,), jnp.float32),
                 rand(17, (b, di, N), jnp.float32))
    return [(f"wkv6 H={H} hd={hd} s={s}", wkv6, wkv6_ref, wkv_args),
            (f"selective_scan di={di} N={N} s={s}", selective_scan,
             selective_scan_ref, scan_args)]


def check_kernels():
    """Run each dispatched kernel once and compare it with its reference
    (float32 matmuls at full precision, as a reference must be)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    for name, op, ref, args in kernel_cases():
        t0 = time.perf_counter()
        compiled = jax.jit(op).lower(*args).compile()
        compile_s = time.perf_counter() - t0
        check("tpu_custom_call" in compiled.as_text(),
              f"{name}: the dispatch did not select the Pallas kernel")
        t0 = time.perf_counter()
        out = jax.block_until_ready(compiled(*args))
        run_s = time.perf_counter() - t0
        with jax.default_matmul_precision("highest"):
            want = jax.block_until_ready(jax.jit(ref)(*args))
        for (label, tol), got, exp in zip(KERNEL_TOLS.items(), out, want):
            got = np.asarray(got.astype(jnp.float32))
            exp = np.asarray(exp.astype(jnp.float32))
            check(bool(np.isfinite(got).all()), f"{name} {label}: not finite")
            err = float(np.max(np.abs(got - exp)))
            bound = float(np.max(tol + tol * np.abs(exp)))
            print(f"kernel {name} {label}: max_abs_err={err:.3e} "
                  f"tol=atol+rtol*|ref| with atol=rtol={tol:g} "
                  f"(compile {compile_s:.3f} s, first run {run_s:.4f} s)")
            check(bool(np.all(np.abs(got - exp) <= tol + tol * np.abs(exp))),
                  f"{name} {label}: error {err:.3e} beyond {bound:.3e}")


# ------------------------------------------------------------------ serving
def direct_tokens(server, prompts, batch: int, new_tokens: int):
    """The same prompts through ``ModelServer.prefill``/``decode`` called
    directly, in the waves the engine formed."""
    import numpy as np

    out = []
    for i in range(0, len(prompts), batch):
        toks = np.stack(prompts[i:i + batch]).astype(np.int32)
        res = server.prefill({"tokens": toks})
        steps = [res["next_token"]]
        for _ in range(new_tokens - 1):
            res = server.decode({"sid": res["sid"],
                                 "tokens": steps[-1][:, None]})
            steps.append(res["next_token"])
        server.close_session({"sid": res["sid"]})
        out.extend(np.stack(steps, 1).tolist())
    return out


def check_served(run, vocab_size: int, batch: int, new_tokens: int):
    served = [r.tokens_out for r in run.requests]
    flat = [t for toks in served for t in toks]
    check(all(len(t) == new_tokens for t in served),
          "a request ended short of its new tokens")
    check(all(0 <= t < vocab_size for t in flat),
          "a served token lies outside the vocabulary")
    # a forward pass that went NaN would argmax to index 0 everywhere
    check(len(set(flat)) > 1, "every served token is the same")
    direct = direct_tokens(run.server, [r.prompt for r in run.requests],
                           batch, new_tokens)
    same = sum(a == b for a, b in zip(served, direct))
    print(f"served tokens equal direct ModelServer tokens: "
          f"{same}/{len(served)} requests")
    check(same == len(served),
          "served tokens differ from direct ModelServer calls")


def serve_and_check(cfg, *, n_requests, batch, prompt_len, new_tokens,
                    max_len, mesh=None):
    from repro.launch.serve import serve

    run = serve(cfg, n_requests=n_requests, batch=batch,
                prompt_len=prompt_len, new_tokens=new_tokens,
                max_len=max_len, seed=SEED, mesh=mesh)
    for line in run.lines():
        print(line)
    check(bool(run.peak_bytes), "the device reported no peak_bytes_in_use")
    check_served(run, cfg.vocab_size, batch, new_tokens)
    return run


# -------------------------------------------------------------- four chips
def sharded_logit_error(cfg, mesh, *, batch: int, prompt_len: int,
                        max_len: int):
    """Prefill plus one decode step of ``cfg`` unsharded on the first
    device and sharded over ``mesh``, from the same weights.  Returns the
    relative L2 error of the sharded logits, prefill and decode."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.distribution.context import make_context
    from repro.distribution.sharding import param_shardings
    from repro.launch.serve import init_params
    from repro.models.factory import build_model

    ref = build_model(cfg)
    shd = build_model(cfg, make_context(mesh))
    params = init_params(ref, SEED)
    sharded = jax.device_put(
        params, param_shardings(shd, jax.eval_shape(lambda: params)))
    toks = jax.random.randint(jax.random.PRNGKey(SEED + 1),
                              (batch, prompt_len), 0, cfg.vocab_size)

    def logits(model, p):
        lp, cache, length = jax.jit(
            lambda p, t: model.prefill(p, t, max_len))(p, toks)
        nxt = jnp.argmax(lp[:, -1], axis=-1)[:, None].astype(jnp.int32)
        ld, _, _ = jax.jit(model.decode)(p, cache, nxt, length)
        return [np.asarray(x.astype(jnp.float32)) for x in (lp, ld)]

    want = logits(ref, params)
    del params
    got = logits(shd, sharded)
    errs = []
    for g, w in zip(got, want):
        check(bool(np.isfinite(g).all()), "sharded logits not finite")
        errs.append(float(np.linalg.norm(g - w) / np.linalg.norm(w)))
    return errs


def four_chips():
    import jax
    from repro.configs import get_config
    from repro.launch.mesh import make_mesh

    devices = jax.devices()
    check(len(devices) >= 4, f"--four-chips needs 4 devices, "
                             f"found {len(devices)}")
    mesh = make_mesh((1, 4), ("data", "model"), devices=devices[:4])
    cfg = get_config("mistral-nemo-12b")
    # both paths compute in bf16 and round at different points (the
    # row-parallel matmuls add four partial products), so they differ by
    # bf16 noise: about 1% relative L2 at this width and depth, the same
    # as either path against float32 (CPU, 4 virtual devices).  2^-5
    # admits that noise; a path that dropped to a narrower type or lost a
    # partial sum would exceed it
    tol = 2.0 ** -5
    cut = cfg.replace(n_layers=4)
    errs = sharded_logit_error(cut, mesh, batch=4,
                               prompt_len=128, max_len=256)
    print(f"{cut.name} depth {cut.n_layers}: sharded (1,4) vs unsharded "
          f"logits, relative L2 error prefill={errs[0]:.3e} "
          f"decode={errs[1]:.3e} tol={tol:.3e}")
    check(max(errs) <= tol, "sharded logits differ from unsharded")
    return serve_and_check(cfg, n_requests=8, batch=4, prompt_len=512,
                           new_tokens=16, max_len=1024, mesh=mesh)


# --------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="serve mistral-nemo-12b sharded over 4 chips "
                         "and compare it with an unsharded run; nothing "
                         "else")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"FAIL: no TPU: JAX's first device is {dev.platform}",
              file=sys.stderr)
        return 1
    print(f"device_kind={dev.device_kind} devices={len(jax.devices())}")
    try:
        from repro.configs import get_config
        from repro.launch.serve import use_compile_cache
        print(f"compile cache: {use_compile_cache()}")
        if args.four_chips:
            run = four_chips()
        else:
            check_kernels()
            run = serve_and_check(get_config("h2o-danube-3-4b"),
                                  n_requests=8, batch=4, prompt_len=512,
                                  new_tokens=16, max_len=2048)
    except Exception as e:                   # noqa: BLE001 — reported
        import traceback
        traceback.print_exc()
        cause = e.__cause__
        print(f"FAIL: {type(e).__name__}: {e}"
              + (f" (caused by {type(cause).__name__}: {cause})"
                 if cause is not None else ""), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": run.n_devices}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
