"""Jit'd public wrappers for the Pallas kernels.

Handles layout (GQA head grouping, flatten/unflatten), padding to
hardware-aligned block multiples, dtype promotion, and the CPU fallback:
``interpret=True`` executes the kernel body in Python on CPU so the exact
kernel logic is validated everywhere (the dry-run/TPU path compiles the
same kernels natively).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as da
from repro.kernels import flash_attention as fa
from repro.kernels import hier_aggregate as ha
from repro.kernels import rglru_scan as rs


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# Mosaic's default scoped VMEM limit is 16 MB.  The formula below counts
# one copy of each block; the pipeline double-buffers the input and output
# blocks, and the fp32 (HIGHEST) contraction keeps split copies of its
# operands, so one counted copy may take about a quarter of the limit.
_AGG_VMEM_BUDGET = 4 * 2**20


def pick_agg_blk_f(num_rows: int, num_groups: int, f_local: int) -> int:
    """Feature-block width for the aggregation kernels, sized to VMEM.

    One grid step holds fp32 (rows, blk_f) input + (rows, blk_f) output
    blocks plus the (M, blk_f) accumulator/mean pair, so the working set is
    ``4 * blk_f * (2*rows + 2*M)`` bytes.  Used by the sharded aggregation
    engine to adapt the block width to each device's feature slab
    (``f_local = f_padded / num_model``) instead of the fixed default.
    """
    rows = min(int(num_rows), ha.MAX_N_UNBLOCKED)
    per_col = 4 * (2 * rows + 2 * max(int(num_groups), 1))
    blk = _AGG_VMEM_BUDGET // max(per_col, 1)
    blk = max(128, (blk // 128) * 128)
    return int(min(blk, 2048, max(int(f_local), 8)))


def _pad_to(x, axis: int, mult: int):
    s = x.shape[axis]
    pad = (-s) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


@functools.partial(jax.jit, static_argnames=("causal", "window", "blk_q", "blk_k"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    blk_q: int = 128, blk_k: int = 128):
    """GQA flash attention.  q: (B,Sq,H,hd), k/v: (B,Sk,K,hd) -> (B,Sq,H,hd)."""
    B, Sq, H, hd = q.shape
    Sk, K = k.shape[1], k.shape[2]
    g = H // K
    # group layout: (B*K, S, g, hd) / (B*K, S, hd)
    qg = q.reshape(B, Sq, K, g, hd).transpose(0, 2, 1, 3, 4).reshape(B * K, Sq, g, hd)
    kg = k.transpose(0, 2, 1, 3).reshape(B * K, Sk, hd)
    vg = v.transpose(0, 2, 1, 3).reshape(B * K, Sk, hd)
    # pad sequence dims to block multiples; padded k columns are masked out
    # by position (they fall outside the causal/window range of every q).
    blk_q_ = min(blk_q, max(Sq, 8))
    blk_k_ = min(blk_k, max(Sk, 8))
    qg, pad_q = _pad_to(qg, 1, blk_q_)
    kg, pad_k = _pad_to(kg, 1, blk_k_)
    vg, _ = _pad_to(vg, 1, blk_k_)
    if pad_k and not causal:
        raise ValueError("non-causal attention requires Sk % blk_k == 0")
    # offset from the ORIGINAL (unpadded) shapes; padded k columns sit past
    # every real q position, so the causal mask drops them.
    o = fa.flash_attention_bkh(qg, kg, vg, causal=causal, window=window,
                               blk_q=blk_q_, blk_k=blk_k_, offset=Sk - Sq,
                               interpret=_interpret())
    if pad_q:
        o = o[:, :Sq]
    return o.reshape(B, K, Sq, g, hd).transpose(0, 2, 1, 3, 4).reshape(B, Sq, H, hd)


@functools.partial(jax.jit, static_argnames=("blk_s", "blk_d"))
def rglru_scan(a, b, *, blk_s: int = 256, blk_d: int = 128):
    """Linear recurrence h_t = a_t h_{t-1} + b_t.  a, b: (B,S,D) -> fp32."""
    B, S, D = a.shape
    blk_d_ = min(blk_d, max(D, 8))
    a, pad_d = _pad_to(a, 2, blk_d_)
    b, _ = _pad_to(b, 2, blk_d_)
    blk_s_ = min(blk_s, a.shape[1])
    a, pad_s = _pad_to(a, 1, blk_s_)
    b, _ = _pad_to(b, 1, blk_s_)
    h = rs.rglru_scan_blocked(a, b, blk_s=blk_s_, blk_d=blk_d_,
                              interpret=_interpret())
    return h[:, :S, :D]


@functools.partial(jax.jit, static_argnames=("blk_f",))
def hier_aggregate(x, w, *, blk_f: int = 512):
    """Weighted mean over the leading client axis.  x: (N, ...) -> (...)."""
    N = x.shape[0]
    shape = x.shape[1:]
    x2 = x.reshape(N, -1)
    x2, pad_f = _pad_to(x2, 1, min(blk_f, max(x2.shape[1], 8)))
    out = ha.hier_aggregate_2d(x2, w, blk_f=blk_f, interpret=_interpret())
    F = 1
    for s in shape:
        F *= s
    return out[:F].reshape(shape)


@functools.partial(jax.jit, static_argnames=("blk_f",))
def hier_cloud_aggregate(x, w, *, blk_f: int = 512):
    """Cloud aggregation (eq. 10) fused with broadcast-back.

    x: (N, ...) any float dtype, w: (N,) -> (N, ...) fp32 where every
    client slot holds the global weighted mean.  One pallas_call.
    """
    N = x.shape[0]
    shape = x.shape[1:]
    x2 = x.reshape(N, -1)
    F = x2.shape[1]
    x2, _ = _pad_to(x2, 1, min(blk_f, max(F, 8)))
    out = ha.hier_bcast_aggregate_2d(x2, w.astype(jnp.float32), blk_f=blk_f,
                                     interpret=_interpret())
    return out[:, :F].reshape((N,) + shape)


@functools.partial(jax.jit, static_argnames=("num_groups", "blk_f"))
def hier_segment_aggregate(x, w, group_ids, *, num_groups: int,
                           blk_f: int = 512):
    """Edge aggregation (eq. 6) fused with scatter-back.

    x: (N, ...) any float dtype, w: (N,), group_ids: (N,) ints in
    [0, num_groups) -> (N, ...) fp32 with out[n] = weighted mean of n's
    group.  Membership is lowered to a dense (M, N) one-hot so the kernel
    does matmuls instead of gathers; one pallas_call per event.
    """
    N = x.shape[0]
    shape = x.shape[1:]
    w32 = w.astype(jnp.float32)
    gid = group_ids.astype(jnp.int32)
    onehot = (gid[None, :] ==
              jnp.arange(num_groups, dtype=jnp.int32)[:, None]
              ).astype(jnp.float32)                       # (M, N)
    gw = jnp.dot(onehot, w32, precision=jax.lax.Precision.HIGHEST)  # (M,)
    x2 = x.reshape(N, -1)
    F = x2.shape[1]
    x2, _ = _pad_to(x2, 1, min(blk_f, max(F, 8)))
    out = ha.hier_segment_aggregate_2d(x2, w32, onehot, gw, blk_f=blk_f,
                                       interpret=_interpret())
    return out[:, :F].reshape((N,) + shape)


@functools.partial(jax.jit, static_argnames=("num_groups", "blk_f"))
def hier_segment_accumulate(x, w, group_ids, *, num_groups: int,
                            blk_f: int = 512):
    """Streaming edge accumulation: per-group weighted SUMS (eq. 6
    numerator), reduce-only.

    x: (N, ...) any float dtype, w: (N,), group_ids: (N,) ints in
    [0, num_groups) -> (num_groups, ...) fp32 with
    out[m] = sum_{n in group m} w[n] x[n].  The streaming variant of
    ``hier_segment_aggregate``: a chunk of arriving client rows reduces
    straight into the (M, F) accumulator, so the caller never holds an
    O(N*F) buffer (see ``repro.fl.aggregate.StreamingEdgeAccumulator``).
    """
    N = x.shape[0]
    shape = x.shape[1:]
    w32 = w.astype(jnp.float32)
    gid = group_ids.astype(jnp.int32)
    onehot = (gid[None, :] ==
              jnp.arange(num_groups, dtype=jnp.int32)[:, None]
              ).astype(jnp.float32)                       # (M, N)
    x2 = x.reshape(N, -1)
    F = x2.shape[1]
    x2, _ = _pad_to(x2, 1, min(blk_f, max(F, 8)))
    out = ha.hier_segment_sum_2d(x2, w32, onehot, blk_f=blk_f,
                                 interpret=_interpret())
    return out[:, :F].reshape((num_groups,) + shape)


@functools.partial(jax.jit, static_argnames=("window", "blk_w"))
def decode_attention(q, k_cache, v_cache, slot_pos, pos, *, window: int = 0,
                     blk_w: int = 256):
    """One-token GQA ring-cache attention.  q: (B,1,H,hd) -> (B,1,H,hd)."""
    B, _, H, hd = q.shape
    W, K = k_cache.shape[1], k_cache.shape[2]
    g = H // K
    qg = q.reshape(B, K, g, hd).reshape(B * K, g, hd)
    kg = k_cache.transpose(0, 2, 1, 3).reshape(B * K, W, hd)
    vg = v_cache.transpose(0, 2, 1, 3).reshape(B * K, W, hd)
    blk = min(blk_w, max(W, 8))
    pad = (-W) % blk
    if pad:
        kg = jnp.pad(kg, ((0, 0), (0, pad), (0, 0)))
        vg = jnp.pad(vg, ((0, 0), (0, pad), (0, 0)))
        slot_pos = jnp.pad(slot_pos, (0, pad), constant_values=-(10 ** 9))
    o = da.decode_attention_bk(qg, kg, vg, slot_pos.astype(jnp.int32), pos,
                               window=window, blk_w=blk,
                               interpret=_interpret())
    return o.reshape(B, K, g, hd).reshape(B, 1, H, hd)
