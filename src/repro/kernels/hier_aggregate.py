"""Hierarchical weighted aggregation Pallas TPU kernels (eqs. 6/10).

The FedAvg hot-spot of the simulation backend, in three flavours over the
flat ``(N, F)`` client-stacked buffer (see ``repro.fl.flatten``):

* ``hier_aggregate_2d``          — global weighted mean, reduce-only:
  ``out[f] = sum_n w[n] x[n,f] / sum_n w[n]``  (eq. 10, returns ``(F,)``).
* ``hier_bcast_aggregate_2d``    — the same cloud mean FUSED with the
  broadcast-back ``out[n] = mean`` (returns ``(N, F)``), so one kernel
  call replaces the reduce + broadcast pair in the hot loop.
* ``hier_segment_aggregate_2d``  — edge aggregation (eq. 6): per-edge
  weighted segment mean fused with the scatter-back
  ``out[n] = mean[group_ids[n]]`` (returns ``(N, F)``).

TPU adaptation: the grid tiles the flattened feature axis in lane-aligned
blocks; each instance loads the full (N, blk_f) client slab into VMEM
(N = clients per edge, O(10-100), so the slab is small).  The segment
kernel receives the group membership as a dense one-hot ``(M, N)`` matrix
so both the per-edge reduction (``onehot_w @ x`` on the MXU) and the
broadcast-back (``onehot^T @ mean``) are matmuls — no gather/scatter on
TPU.  The per-group weight normaliser is precomputed by the wrapper and
folded into the same pass, with an ``(M, blk_f)`` VMEM accumulator
carrying partial segment sums when client-blocking (N > MAX_N_UNBLOCKED)
kicks in: the grid grows a two-step phase axis — phase 0 accumulates
segment sums over client blocks, phase 1 scatters the means back — so one
aggregation event stays ONE pallas_call at every size.

Every operand and output is 2-D: weights travel as a ``(1, N)`` row (the
segment kernels) or an ``(N, 1)`` column (the cloud kernels), per-group
sums as ``(M, 1)``, the reduce-only mean as ``(1, F)``.  Mosaic tiles a
1-D f32 array differently from XLA once it outgrows one tile, so a 1-D
block of a larger array does not compile for the chip.  The matmuls ask
for fp32 contraction: eqs. 6 and 10 are exact weighted means, and a
bf16 pass would round them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MAX_N_UNBLOCKED = 512
_FP32 = jax.lax.Precision.HIGHEST


def _dot(a, b):
    return jnp.dot(a, b, precision=_FP32, preferred_element_type=jnp.float32)


def _agg_kernel(x_ref, w_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)          # (N, blk_f)
    w = w_ref[...].astype(jnp.float32)          # (N, 1)
    o_ref[...] = (w * x).sum(0, keepdims=True) / w.sum()


def _agg_kernel_blocked(x_ref, w_ref, o_ref, acc_ref, *, n_n: int):
    ni = pl.program_id(1)

    @pl.when(ni == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)          # (blk_n, blk_f)
    w = w_ref[...].astype(jnp.float32)          # (blk_n, 1) zero-padded
    acc_ref[...] += (w * x).sum(0, keepdims=True)

    @pl.when(ni == n_n - 1)
    def _finish():
        o_ref[...] = acc_ref[...]


def hier_aggregate_2d(x, w, *, blk_f: int = 512, blk_n: int = 256,
                      interpret: bool = False):
    """x: (N, F) float, w: (N,) -> (F,) weighted mean in fp32."""
    N, F = x.shape
    blk_f = min(blk_f, F)
    n_f = pl.cdiv(F, blk_f)
    w = w.astype(jnp.float32)

    if N <= MAX_N_UNBLOCKED:
        out = pl.pallas_call(
            _agg_kernel,
            grid=(n_f,),
            in_specs=[
                pl.BlockSpec((N, blk_f), lambda fi: (0, fi)),
                pl.BlockSpec((N, 1), lambda fi: (0, 0)),
            ],
            out_specs=pl.BlockSpec((1, blk_f), lambda fi: (0, fi)),
            out_shape=jax.ShapeDtypeStruct((1, F), jnp.float32),
            interpret=interpret,
        )(x, w[:, None])
        return out[0]

    blk_n = min(blk_n, N)
    n_n = pl.cdiv(N, blk_n)
    pad_n = n_n * blk_n - N
    if pad_n:
        # zero weights make the padded client rows contribute nothing
        x = jnp.pad(x, ((0, pad_n), (0, 0)))
        w = jnp.pad(w, (0, pad_n))
    wsum = jnp.sum(w)
    out = pl.pallas_call(
        functools.partial(_agg_kernel_blocked, n_n=n_n),
        grid=(n_f, n_n),
        in_specs=[
            pl.BlockSpec((blk_n, blk_f), lambda fi, ni: (ni, fi)),
            pl.BlockSpec((blk_n, 1), lambda fi, ni: (ni, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk_f), lambda fi, ni: (0, fi)),
        out_shape=jax.ShapeDtypeStruct((1, F), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, blk_f), jnp.float32)],
        interpret=interpret,
    )(x, w[:, None])
    return out[0] / wsum


# ---------------------------------------------------------------------------
# Fused broadcast-back variants: one pallas_call per aggregation EVENT.
# ---------------------------------------------------------------------------


def _bcast_kernel(x_ref, w_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)          # (N, blk_f)
    w = w_ref[...].astype(jnp.float32)          # (N, 1)
    mean = (w * x).sum(0, keepdims=True) / w.sum()
    o_ref[...] = jnp.broadcast_to(mean, o_ref.shape)


def hier_bcast_aggregate_2d(x, w, *, blk_f: int = 512,
                            interpret: bool = False):
    """Cloud aggregation (eq. 10) fused with broadcast-back.

    x: (N, F), w: (N,) -> (N, F) fp32 where out[n] = weighted mean row.
    Large N falls through to the segment kernel with a single group.
    """
    N, F = x.shape
    w = w.astype(jnp.float32)
    if N > MAX_N_UNBLOCKED:
        onehot = jnp.ones((1, N), jnp.float32)
        gw = jnp.sum(w)[None]
        return hier_segment_aggregate_2d(x, w, onehot, gw, blk_f=blk_f,
                                         interpret=interpret)
    blk_f = min(blk_f, F)
    n_f = pl.cdiv(F, blk_f)
    return pl.pallas_call(
        _bcast_kernel,
        grid=(n_f,),
        in_specs=[
            pl.BlockSpec((N, blk_f), lambda fi: (0, fi)),
            pl.BlockSpec((N, 1), lambda fi: (0, 0)),
        ],
        out_specs=pl.BlockSpec((N, blk_f), lambda fi: (0, fi)),
        out_shape=jax.ShapeDtypeStruct((N, F), jnp.float32),
        interpret=interpret,
    )(x, w[:, None])


def _seg_kernel(x_ref, w_ref, oh_ref, gw_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)          # (N, blk_f)
    w = w_ref[...].astype(jnp.float32)          # (1, N)
    oh = oh_ref[...]                            # (M, N) one-hot membership
    gw = gw_ref[...]                            # (M, 1) per-group weight sums
    mean = _dot(oh * w, x) / jnp.maximum(gw, 1e-12)          # (M, blk_f)
    o_ref[...] = _dot(oh.T, mean)                            # (N, blk_f)


def _seg_kernel_blocked(x_ref, w_ref, oh_ref, gw_ref, o_ref, acc_ref):
    ph = pl.program_id(1)                       # 0 = accumulate, 1 = scatter
    ni = pl.program_id(2)

    @pl.when((ph == 0) & (ni == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)          # (blk_n, blk_f)
    w = w_ref[...].astype(jnp.float32)          # (1, blk_n) zero-padded
    oh = oh_ref[...]                            # (M, blk_n)

    @pl.when(ph == 0)
    def _accumulate():
        acc_ref[...] += _dot(oh * w, x)

    @pl.when(ph == 1)
    def _scatter():
        gw = gw_ref[...]                        # (M, 1)
        o_ref[...] = _dot(oh.T, acc_ref[...] / jnp.maximum(gw, 1e-12))


def hier_segment_aggregate_2d(x, w, onehot, gw, *, blk_f: int = 512,
                              blk_n: int = 256, interpret: bool = False):
    """Edge aggregation (eq. 6) fused with scatter-back, one pallas_call.

    x: (N, F), w: (N,), onehot: (M, N) fp32 group membership,
    gw: (M,) per-group weight sums -> (N, F) fp32 with
    out[n] = sum_{i in group(n)} w[i] x[i] / gw[group(n)].
    """
    N, F = x.shape
    M = onehot.shape[0]
    blk_f = min(blk_f, F)
    n_f = pl.cdiv(F, blk_f)
    w = w.astype(jnp.float32)
    gw = gw.astype(jnp.float32)[:, None]

    if N <= MAX_N_UNBLOCKED:
        return pl.pallas_call(
            _seg_kernel,
            grid=(n_f,),
            in_specs=[
                pl.BlockSpec((N, blk_f), lambda fi: (0, fi)),
                pl.BlockSpec((1, N), lambda fi: (0, 0)),
                pl.BlockSpec((M, N), lambda fi: (0, 0)),
                pl.BlockSpec((M, 1), lambda fi: (0, 0)),
            ],
            out_specs=pl.BlockSpec((N, blk_f), lambda fi: (0, fi)),
            out_shape=jax.ShapeDtypeStruct((N, F), jnp.float32),
            interpret=interpret,
        )(x, w[None, :], onehot, gw)

    blk_n = min(blk_n, N)
    n_n = pl.cdiv(N, blk_n)
    pad_n = n_n * blk_n - N
    if pad_n:
        # zero weights + zero one-hot columns: padded clients contribute
        # nothing to any segment and their output rows are sliced off.
        x = jnp.pad(x, ((0, pad_n), (0, 0)))
        w = jnp.pad(w, (0, pad_n))
        onehot = jnp.pad(onehot, ((0, 0), (0, pad_n)))
    out = pl.pallas_call(
        _seg_kernel_blocked,
        grid=(n_f, 2, n_n),
        in_specs=[
            pl.BlockSpec((blk_n, blk_f), lambda fi, ph, ni: (ni, fi)),
            pl.BlockSpec((1, blk_n), lambda fi, ph, ni: (0, ni)),
            pl.BlockSpec((M, blk_n), lambda fi, ph, ni: (0, ni)),
            pl.BlockSpec((M, 1), lambda fi, ph, ni: (0, 0)),
        ],
        out_specs=pl.BlockSpec((blk_n, blk_f), lambda fi, ph, ni: (ni, fi)),
        out_shape=jax.ShapeDtypeStruct((N + pad_n, F), jnp.float32),
        scratch_shapes=[pltpu.VMEM((M, blk_f), jnp.float32)],
        interpret=interpret,
    )(x, w[None, :], onehot, gw)
    return out[:N]


# ---------------------------------------------------------------------------
# Reduce-only segment sums: the streaming-accumulator kernel.
# ---------------------------------------------------------------------------


def _seg_sum_kernel(x_ref, w_ref, oh_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)          # (N, blk_f)
    w = w_ref[...].astype(jnp.float32)          # (1, N)
    o_ref[...] = _dot(oh_ref[...] * w, x)       # (M, blk_f)


def _seg_sum_kernel_blocked(x_ref, w_ref, oh_ref, o_ref):
    ni = pl.program_id(1)

    @pl.when(ni == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)          # (blk_n, blk_f)
    w = w_ref[...].astype(jnp.float32)          # (1, blk_n) zero-padded
    o_ref[...] += _dot(oh_ref[...] * w, x)      # oh: (M, blk_n)


def hier_segment_sum_2d(x, w, onehot, *, blk_f: int = 512,
                        blk_n: int = 256, interpret: bool = False):
    """Per-group WEIGHTED SUMS, no normalize, no scatter-back.

    x: (N, F), w: (N,), onehot: (M, N) -> (M, F) fp32 with
    ``out[m] = sum_{n in group m} w[n] x[n]``.  This is the chunk step of
    the streaming edge accumulator (``repro.fl.aggregate``): each arrival
    wave reduces straight into an ``(M, F)`` accumulator, so no O(N*F)
    buffer ever exists.  The blocked variant revisits the same output
    block along the minor client-block axis (init at ni == 0, then
    accumulate in place) — output-as-accumulator instead of the fused
    kernel's scratch + scatter phase, because here (M, F) IS the result.
    """
    N, F = x.shape
    M = onehot.shape[0]
    blk_f = min(blk_f, F)
    n_f = pl.cdiv(F, blk_f)
    w = w.astype(jnp.float32)

    if N <= MAX_N_UNBLOCKED:
        return pl.pallas_call(
            _seg_sum_kernel,
            grid=(n_f,),
            in_specs=[
                pl.BlockSpec((N, blk_f), lambda fi: (0, fi)),
                pl.BlockSpec((1, N), lambda fi: (0, 0)),
                pl.BlockSpec((M, N), lambda fi: (0, 0)),
            ],
            out_specs=pl.BlockSpec((M, blk_f), lambda fi: (0, fi)),
            out_shape=jax.ShapeDtypeStruct((M, F), jnp.float32),
            interpret=interpret,
        )(x, w[None, :], onehot)

    blk_n = min(blk_n, N)
    n_n = pl.cdiv(N, blk_n)
    pad_n = n_n * blk_n - N
    if pad_n:
        # zero weights + zero one-hot columns: padded clients add nothing.
        x = jnp.pad(x, ((0, pad_n), (0, 0)))
        w = jnp.pad(w, (0, pad_n))
        onehot = jnp.pad(onehot, ((0, 0), (0, pad_n)))
    return pl.pallas_call(
        _seg_sum_kernel_blocked,
        grid=(n_f, n_n),
        in_specs=[
            pl.BlockSpec((blk_n, blk_f), lambda fi, ni: (ni, fi)),
            pl.BlockSpec((1, blk_n), lambda fi, ni: (0, ni)),
            pl.BlockSpec((M, blk_n), lambda fi, ni: (0, ni)),
        ],
        out_specs=pl.BlockSpec((M, blk_f), lambda fi, ni: (0, fi)),
        out_shape=jax.ShapeDtypeStruct((M, F), jnp.float32),
        interpret=interpret,
    )(x, w[None, :], onehot)
