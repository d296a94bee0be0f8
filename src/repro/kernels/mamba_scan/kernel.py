"""Pallas TPU kernel for the Mamba selective scan (S6).

TPU adaptation of the CUDA parallel-scan kernel: channels are embarrass-
ingly parallel, so the grid tiles (batch, channel-block, time-chunk) and
keeps each state tile in VMEM scratch across the sequential time-chunk
dim.  B/C are shared across channel blocks (re-read per block, N=16 so
the tile is tiny); dib=512, N=16 -> 32 KiB state, operand tiles
(chunk=128) ~0.5 MiB.

Layout: the state is held transposed, (N, dib), so that the per-channel
operands x and dt enter as lane-dense rows and only the tiny B/C tiles
are transposed into columns.  Operands are read in whole 8-row tiles (a
dynamic one-row load of a bf16 ref cannot be proven aligned); each tile
is then stepped through row by row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_TILE = 8                        # rows per aligned load (f32 sublane tile)


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, h0_ref,
            y_ref, hT_ref, h_scr, *, chunk, nt):
    pid_t = pl.program_id(2)

    @pl.when(pid_t == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)

    A = a_ref[...].astype(jnp.float32)                 # (N, dib)
    D = d_ref[...].astype(jnp.float32)                 # (1, dib)

    def tile(j, h):
        rows = pl.ds(pl.multiple_of(j * _TILE, _TILE), _TILE)
        x = x_ref[0, rows, :].astype(jnp.float32)      # (TILE, dib)
        dt = dt_ref[0, rows, :].astype(jnp.float32)
        Bc = b_ref[0, rows, :].astype(jnp.float32).T   # (N, TILE)
        Cc = c_ref[0, rows, :].astype(jnp.float32).T
        ys = []
        for t in range(_TILE):
            xt, dtt = x[t:t + 1], dt[t:t + 1]          # (1, dib)
            h = jnp.exp(dtt * A) * h + Bc[:, t:t + 1] * (dtt * xt)
            ys.append(jnp.sum(h * Cc[:, t:t + 1], axis=0, keepdims=True)
                      + D * xt)
        y_ref[0, rows, :] = jnp.concatenate(ys, 0).astype(y_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, chunk // _TILE, tile, h_scr[...])

    @pl.when(pid_t == nt - 1)
    def _done():
        hT_ref[0] = h_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "block_d",
                                             "interpret"))
def selective_scan_pallas(x, dt, A, B, C, D, state, *, chunk=128,
                          block_d=512, interpret=False):
    """x, dt (b, s, di); A (di, N); B, C (b, s, N); D (di,);
    state (b, di, N) f32.  Returns (y (b, s, di) in x.dtype, final state).
    Padding uses dt=0 => exp(0·A)=1: state passes through untouched.
    ``chunk`` must be a multiple of 8; ``block_d`` a multiple of 128 or
    at least ``di``."""
    if chunk % _TILE:
        raise ValueError(f"chunk={chunk} is not a multiple of {_TILE}")
    b, s, di = x.shape
    N = A.shape[-1]
    dib = min(block_d, di)
    nd = -(-di // dib)
    nt = -(-s // chunk)
    pad_t = nt * chunk - s
    pad_d = nd * dib - di
    if pad_t or pad_d:
        x = jnp.pad(x, ((0, 0), (0, pad_t), (0, pad_d)))
        dt = jnp.pad(dt, ((0, 0), (0, pad_t), (0, pad_d)))
        B = jnp.pad(B, ((0, 0), (0, pad_t), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad_t), (0, 0)))
    if pad_d:
        A = jnp.pad(A, ((0, pad_d), (0, 0)))
        D = jnp.pad(D, ((0, pad_d),))
        state = jnp.pad(state, ((0, 0), (0, pad_d), (0, 0)))

    xd_spec = pl.BlockSpec((1, chunk, dib),
                           lambda bi, di_, ti: (bi, ti, di_))
    bc_spec = pl.BlockSpec((1, chunk, N), lambda bi, di_, ti: (bi, ti, 0))
    state_spec = pl.BlockSpec((1, N, dib), lambda bi, di_, ti: (bi, 0, di_))
    y, hT = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, nt=nt),
        grid=(b, nd, nt),
        in_specs=[xd_spec, xd_spec,
                  pl.BlockSpec((N, dib), lambda bi, di_, ti: (0, di_)),
                  bc_spec, bc_spec,
                  pl.BlockSpec((1, dib), lambda bi, di_, ti: (0, di_)),
                  state_spec],
        out_specs=[xd_spec, state_spec],
        out_shape=[jax.ShapeDtypeStruct((b, nt * chunk, nd * dib),
                                        x.dtype),
                   jax.ShapeDtypeStruct((b, N, nd * dib), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((N, dib), jnp.float32)],
        interpret=interpret,
    )(x, dt, A.T, B, C, D[None, :],
      state.astype(jnp.float32).transpose(0, 2, 1))
    return y[:, :s, :di], hT.transpose(0, 2, 1)[:, :di]
