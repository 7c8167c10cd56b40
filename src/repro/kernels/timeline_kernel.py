"""Pallas kernels for the DES timeline engine's two batched inner loops.

The array-native ``core.timeline.TimelineEngine`` reduces every
contention-interval flush to two data-parallel primitives:

* **rate-advance** — settle each job's remaining virtual work to the
  shared timestamp and project its completion:
  ``W' = max(0, W - rate*(now - t_last))``, ``eta = now + W'/rate``
  (+inf where the rate is non-positive; nan residues — the
  ``inf * 0`` corner of infinite-bandwidth transfers — clamp to 0,
  matching the scalar seed's ``max(0.0, nan)``).
* **segment-min** — a transfer's bottleneck bandwidth is the min of its
  route edges' fair shares; the flush evaluates the whole dirty set as
  one segmented reduction.  The kernel takes the dense padded form
  ``(S, Emax)`` (+inf padding), which the wrapper builds from the CSR
  (values, counts) layout the engine keeps.

On a TPU backend both lower natively (rows tile the sublanes, the tiny
edge axis pads the lanes).  The engine itself defaults to its float64
numpy settles on *every* backend — its parity contract against the
seed event loop is a hard 1e-9 bound the fp32 kernels cannot
guarantee, and the per-flush batches are memory-bound — so these
kernels are the opt-in path for TPU-resident pipelines
(``REPRO_TIMELINE_KERNEL=pallas`` routes the engine through the
``*_forced`` variants, interpret-mode off-TPU; the ``rate_advance`` /
``segment_min`` entry points below backend-select for direct callers).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref

_LANES = 128


# ---------------------------------------------------------------------------
# rate-advance: elementwise settle + completion projection
# ---------------------------------------------------------------------------
def _rate_advance_kernel(w_ref, r_ref, t_ref, o_w_ref, o_e_ref, *, now):
    W = w_ref[...].astype(jnp.float32)
    rate = r_ref[...].astype(jnp.float32)
    t_last = t_ref[...].astype(jnp.float32)
    raw = W - rate * (now - t_last)
    W2 = jnp.maximum(0.0, raw)
    W2 = jnp.where(jnp.isnan(raw), 0.0, W2)
    eta = jnp.where(rate > 0.0, now + W2 / rate, jnp.inf)
    o_w_ref[...] = W2
    o_e_ref[...] = eta


def rate_advance_pallas(W, rate, t_last, now: float, *,
                        block_n: int = 1024,
                        interpret: Optional[bool] = None):
    """(N,) settle via pl.pallas_call; returns (W', eta) as numpy."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    W = jnp.asarray(W, jnp.float32)
    N = W.shape[0]
    if N == 0:
        return np.zeros(0), np.zeros(0)
    cols = min(_LANES, max(N, 1))
    pad = (-N) % cols
    rows = (N + pad) // cols
    bn = min(block_n // _LANES if cols == _LANES else 1, rows) or 1

    def shape2d(x):
        return jnp.pad(jnp.asarray(x, jnp.float32), (0, pad),
                       constant_values=1.0).reshape(rows, cols)

    Wp = jnp.pad(W, (0, pad)).reshape(rows, cols)
    rp = shape2d(rate)               # pad rate=1: no div-by-zero lanes
    tp = shape2d(t_last)
    pad_rows = (-rows) % bn
    if pad_rows:
        Wp = jnp.pad(Wp, ((0, pad_rows), (0, 0)))
        rp = jnp.pad(rp, ((0, pad_rows), (0, 0)), constant_values=1.0)
        tp = jnp.pad(tp, ((0, pad_rows), (0, 0)))
    out_w, out_e = rate_advance_call(Wp, rp, tp, now=now, bn=bn,
                                     interpret=interpret)
    return (np.asarray(out_w, np.float64).reshape(-1)[:N],
            np.asarray(out_e, np.float64).reshape(-1)[:N])


def rate_advance_call(Wp, rp, tp, *, now: float, bn: int, interpret: bool):
    """The padded kernel call over (rows, cols) blocks of ``bn`` rows."""
    rows, cols = Wp.shape
    return pl.pallas_call(
        functools.partial(_rate_advance_kernel, now=now),
        grid=(rows // bn,),
        in_specs=[pl.BlockSpec((bn, cols), lambda i: (i, 0))] * 3,
        out_specs=[pl.BlockSpec((bn, cols), lambda i: (i, 0))] * 2,
        out_shape=[jax.ShapeDtypeStruct(Wp.shape, jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(Wp, rp, tp)


# ---------------------------------------------------------------------------
# segment-min: per-transfer bottleneck over padded route-edge shares
# ---------------------------------------------------------------------------
def _segment_min_kernel(v_ref, o_ref):
    o_ref[...] = jnp.min(v_ref[...], axis=-1, keepdims=True)


def segment_min_pallas(values, counts, *, block_s: int = 256,
                       interpret: Optional[bool] = None) -> np.ndarray:
    """CSR (values, counts) -> per-segment min via a dense padded row
    reduction (route lists are short: Emax is single-digit)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    counts = np.asarray(counts, dtype=np.int64)
    S = len(counts)
    if S == 0:
        return np.zeros(0)
    emax = int(counts.max()) if S else 0
    if emax == 0:
        return np.full(S, np.inf)
    dense = np.full((S, emax), np.inf, dtype=np.float32)
    starts = np.cumsum(counts) - counts
    vals = np.asarray(values, dtype=np.float32)
    within = np.arange(int(counts.sum())) - np.repeat(starts, counts)
    rows = np.repeat(np.arange(S), counts)
    dense[rows, within] = vals
    pad_e = (-emax) % _LANES
    bs = min(block_s, S)
    pad_s = (-S) % bs
    dp = jnp.pad(jnp.asarray(dense), ((0, pad_s), (0, pad_e)),
                 constant_values=np.inf)
    out = segment_min_call(dp, bs=bs, interpret=interpret)
    return np.asarray(out, np.float64)[:S, 0]


def segment_min_call(dp, *, bs: int, interpret: bool):
    """The padded kernel call: (S, E) +inf-padded rows -> (S, 1) minima."""
    s, e = dp.shape
    return pl.pallas_call(
        _segment_min_kernel,
        grid=(s // bs,),
        in_specs=[pl.BlockSpec((bs, e), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bs, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((s, 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(dp)


# ---------------------------------------------------------------------------
# backend-selected entry points (the engine's dispatch targets)
# ---------------------------------------------------------------------------
def rate_advance(W, rate, t_last, now: float):
    """TPU: Pallas kernel.  CPU/GPU: the float64 numpy reference (the
    DES parity bound requires float64; no interpret-mode overhead)."""
    if jax.default_backend() == "tpu":
        return rate_advance_pallas(W, rate, t_last, now, interpret=False)
    return ref.rate_advance_ref(W, rate, t_last, now)


def segment_min(values, counts):
    if jax.default_backend() == "tpu":
        return segment_min_pallas(values, counts, interpret=False)
    return ref.segment_min_ref(values, counts)


def rate_advance_forced(W, rate, t_last, now: float):
    """Always the Pallas kernel (interpret off-TPU) — parity testing."""
    return rate_advance_pallas(W, rate, t_last, now)


def segment_min_forced(values, counts):
    return segment_min_pallas(values, counts)
