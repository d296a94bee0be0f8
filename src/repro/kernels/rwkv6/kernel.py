"""Pallas TPU kernel for the RWKV6 (Finch) WKV recurrence.

TPU adaptation of the (GPU, warp-per-head) reference: one grid cell per
(batch, head, time-chunk); the (hd x hd) f32 state tile stays RESIDENT in
VMEM scratch across the sequential time-chunk grid dim, so the kernel's
HBM traffic is one read of r/k/v/w and one write of y per token — the
recurrence itself never touches HBM.  hd=64 -> 16 KiB state; chunk=128 ->
four (128, 64) operand tiles ~128 KiB: trivially VMEM-resident.

Layout: operands are heads-major, (b, H, s, hd), so that a block's last
two dims are (chunk, hd) — sublane- and lane-aligned for Mosaic.  The
model projects straight into that layout (``models/rwkv6.py``), so no
transpose sits between it and the kernel.  Operands are read in whole
8-row tiles (a dynamic one-row load of a bf16 ref cannot be proven
aligned); each tile is then stepped through row by row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_TILE = 8                        # rows per aligned load (f32 sublane tile)


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sT_ref,
            s_scr, *, chunk, nt):
    pid_t = pl.program_id(2)

    @pl.when(pid_t == 0)
    def _init():
        s_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    u = u_ref[0].astype(jnp.float32)                   # (1, hd)

    def tile(j, S):
        rows = pl.ds(pl.multiple_of(j * _TILE, _TILE), _TILE)
        load = lambda ref: ref[0, 0, rows, :].astype(jnp.float32)
        r, k, v, w = load(r_ref), load(k_ref), load(v_ref), load(w_ref)
        # k, r and w act along the state's rows: take them as columns
        rc, kc, wc = r.T, k.T, w.T                     # (hd, TILE)
        ys = []
        for t in range(_TILE):
            vt = v[t:t + 1]                            # (1, hd)
            # y = r·S + (Σ_k r_k u_k k_k) v   (rank-1 shortcut, no hd²
            # matmul for the u-term)
            ys.append(jnp.sum(rc[:, t:t + 1] * S, axis=0, keepdims=True)
                      + jnp.sum(r[t:t + 1] * u * k[t:t + 1], axis=1,
                                keepdims=True) * vt)
            S = wc[:, t:t + 1] * S + kc[:, t:t + 1] * vt
        y_ref[0, 0, rows, :] = jnp.concatenate(ys, 0).astype(y_ref.dtype)
        return S

    s_scr[...] = jax.lax.fori_loop(0, chunk // _TILE, tile, s_scr[...])

    @pl.when(pid_t == nt - 1)
    def _done():
        sT_ref[0, 0] = s_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6_pallas(r, k, v, w, u, state, *, chunk=128, interpret=False):
    """r/k/v/w (b, H, s, hd); u (H, hd); state (b, H, hd, hd) f32.
    Returns (y (b, H, s, hd) in r.dtype, final state f32).  ``chunk`` must
    be a multiple of 8."""
    if chunk % _TILE:
        raise ValueError(f"chunk={chunk} is not a multiple of {_TILE}")
    b, H, s, hd = r.shape
    nt = -(-s // chunk)
    pad = nt * chunk - s
    if pad:
        zpad = lambda t: jnp.pad(t, ((0, 0), (0, 0), (0, pad), (0, 0)))
        r, k, v = zpad(r), zpad(k), zpad(v)
        w = jnp.pad(w, ((0, 0), (0, 0), (0, pad), (0, 0)),
                    constant_values=1.0)      # identity decay on padding

    io_spec = pl.BlockSpec((1, 1, chunk, hd),
                           lambda bi, hi, ti: (bi, hi, ti, 0))
    state_spec = pl.BlockSpec((1, 1, hd, hd),
                              lambda bi, hi, ti: (bi, hi, 0, 0))
    y, sT = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, nt=nt),
        grid=(b, H, nt),
        in_specs=[io_spec, io_spec, io_spec, io_spec,
                  pl.BlockSpec((1, 1, hd), lambda bi, hi, ti: (hi, 0, 0)),
                  state_spec],
        out_specs=[io_spec, state_spec],
        out_shape=[jax.ShapeDtypeStruct((b, H, nt * chunk, hd), r.dtype),
                   jax.ShapeDtypeStruct((b, H, hd, hd), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
    )(r, k, v, w, u.reshape(H, 1, hd), state.astype(jnp.float32))
    return y[:, :, :s], sT
