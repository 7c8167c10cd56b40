"""Pallas kernel for the batched slowdown factor-aggregation inner loop.

The vectorized slowdown model (core/slowdown.py) reduces every co-run
pool to dense per-rclass pressure arrays; the remaining inner loop is a
pure map over pool members:

    factor[i] = max(1, (1 + mt_term[i])
                       * prod_r(1 + beta[r]*x[i,r]*(1+kappa*x[i,r]) * mem[i]))

The kernel works on the transposed pool: the rclass axis (at most
``len(RCLASSES)`` = 8 rows) fills one sublane tile and the pool members
run along the lanes, so the output is one lane-dense ``(1, N)`` row.  The
product over rclasses is unrolled over the real rclass rows (Mosaic has
no ``reduce_prod`` lowering).  Pools are padded to a power-of-two width
(:func:`bucket`), so a run compiles one kernel per power of two its pools
reach; padded members are dropped after the call.  A pool wider than
``MAX_BUCKET`` goes through in slices of that width (the map is
elementwise per member, so slicing changes no bit), which bounds the
kernel to the buckets from 8 to ``MAX_BUCKET``: a serving process warmed
on those never compiles again, however large a lockstep escalation's
batched check grows.

``core.slowdown`` selects this kernel on a TPU backend and the float64
numpy reference (``ref.slowdown_factors_ref``) everywhere else.  Off-TPU
the kernel runs in interpret mode, which the parity tests use.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import trace

_SUBLANES = 8
_MIN_BUCKET = 8
_MAX_BLOCK = 2048          # lanes per grid step; larger buckets tile
MAX_BUCKET = 8192          # widest pool one call takes; wider pools slice


def bucket(n: int) -> int:
    """Padded pool width for an ``n``-member pool: the next power of two,
    at least 8."""
    return max(_MIN_BUCKET, 1 << (max(n, 1) - 1).bit_length())


def _factors_kernel(x_ref, beta_ref, mem_ref, mt_ref, o_ref, *, kappa, n_r):
    mem = mem_ref[...]                           # (1, bn)
    prod = None
    for r in range(n_r):                         # static unroll
        x = x_ref[r:r + 1, :]                    # (1, bn)
        beta = beta_ref[r:r + 1, :]              # (1, 1)
        term = jnp.where((x > 0.0) & (beta > 0.0),
                         beta * x * (1.0 + kappa * x), 0.0)
        g = 1.0 + term * mem
        prod = g if prod is None else prod * g
    f = 1.0 + mt_ref[...]
    if prod is not None:
        f = f * prod
    o_ref[...] = jnp.maximum(f, 1.0)


@functools.partial(jax.jit, static_argnames=("kappa", "n_r", "interpret"))
def factors_call(xt, beta, mem, mt, *, kappa: float, n_r: int,
                 interpret: bool):
    """The padded kernel call: ``xt`` (Rp, Np) pressures with rclasses on
    the sublanes, ``beta`` (Rp, 1), ``mem``/``mt`` (1, Np) -> (1, Np)."""
    rp, npad = xt.shape
    bn = min(npad, _MAX_BLOCK)
    row = pl.BlockSpec((1, bn), lambda i: (0, i))
    return pl.pallas_call(
        functools.partial(_factors_kernel, kappa=kappa, n_r=n_r),
        grid=(npad // bn,),
        in_specs=[pl.BlockSpec((rp, bn), lambda i: (0, i)),
                  pl.BlockSpec((rp, 1), lambda i: (0, 0)),
                  row, row],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((1, npad), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(xt, beta, mem, mt)


def slowdown_factors_pallas(x, beta, mem, mt_term, kappa: float, *,
                            interpret: Optional[bool] = None) -> np.ndarray:
    """(N, R) pressures -> (N,) float64 factors through the fp32 kernel
    (interpret mode off-TPU unless ``interpret`` says otherwise), one call
    per ``MAX_BUCKET`` members."""
    if len(x) > MAX_BUCKET:
        return np.concatenate([
            slowdown_factors_pallas(x[i:i + MAX_BUCKET], beta,
                                    mem[i:i + MAX_BUCKET],
                                    mt_term[i:i + MAX_BUCKET], kappa,
                                    interpret=interpret)
            for i in range(0, len(x), MAX_BUCKET)])
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    with trace.span("device.slowdown"):
        x = np.asarray(x, dtype=np.float32)
        n, r = x.shape
        nb = bucket(n)
        rp = r + (-r) % _SUBLANES
        # zero pressure in padded rclass rows and padded members
        # contributes a factor term of exactly 1.0; padded members are
        # dropped below
        xt = np.zeros((rp, nb), np.float32)
        xt[:r, :n] = x.T
        betap = np.zeros((rp, 1), np.float32)
        betap[:r, 0] = beta
        memp = np.zeros((1, nb), np.float32)
        memp[0, :n] = mem
        mtp = np.zeros((1, nb), np.float32)
        mtp[0, :n] = mt_term
        with trace.span("device.slowdown.call"):
            trace.count("device.h2d", 4)
            out = factors_call(xt, betap, memp, mtp, kappa=float(kappa),
                               n_r=r, interpret=bool(interpret))
        with trace.span("device.slowdown.fetch"):
            trace.count("device.fetch", 1)
            return np.asarray(out, dtype=np.float64)[0, :n]
