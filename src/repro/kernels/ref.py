"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -2.0e38


def flash_attention_ref(q, k, v, *, causal: bool = True, window: int = 0):
    """Dense softmax attention with GQA.  q: (B,Sq,H,hd), k/v: (B,Sk,K,hd)."""
    B, Sq, H, hd = q.shape
    Sk, K = k.shape[1], k.shape[2]
    g = H // K
    qg = q.reshape(B, Sq, K, g, hd).astype(jnp.float32)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, k.astype(jnp.float32))
    s = s / jnp.sqrt(hd).astype(jnp.float32)
    qp = jnp.arange(Sq)[:, None]
    kp = jnp.arange(Sk)[None, :]
    d = (qp + (Sk - Sq)) - kp          # aligned ends (decode-style offset)
    m = jnp.ones((Sq, Sk), bool)
    if causal:
        m &= d >= 0
    if window > 0:
        m &= d < window
    s = jnp.where(m[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bqkgh", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, hd).astype(q.dtype)


def rglru_scan_ref(a, b, h0=None):
    """h_t = a_t * h_{t-1} + b_t (RG-LRU recurrence).  a, b: (B,S,D)."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if h0 is not None:
        b = b.at[:, 0].add(a[:, 0] * h0.astype(jnp.float32))

    def combine(x, y):
        a1, b1 = x
        a2, b2 = y
        return a1 * a2, a2 * b1 + b2

    _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    return h


def hier_aggregate_ref(x, w):
    """Weighted mean over the leading client axis.  x: (N,...), w: (N,)."""
    xf = x.reshape(x.shape[0], -1).astype(jnp.float32)
    wf = w.astype(jnp.float32)
    out = (wf[:, None] * xf).sum(0) / wf.sum()
    return out.reshape(x.shape[1:])


def hier_bcast_aggregate_ref(x, w):
    """Cloud aggregation (eq. 10) with broadcast-back: (N, F) -> (N, F)."""
    xf = x.reshape(x.shape[0], -1).astype(jnp.float32)
    wf = w.astype(jnp.float32)
    mean = (wf[:, None] * xf).sum(0) / wf.sum()
    return jnp.broadcast_to(mean[None], xf.shape).reshape(x.shape)


def hier_segment_aggregate_ref(x, w, group_ids, num_groups: int):
    """Edge aggregation (eq. 6) with scatter-back, fp32.

    x: (N, ...), w: (N,), group_ids: (N,) ints in [0, num_groups) ->
    (N, ...) where out[n] is the weighted mean of n's group.  Zero-member
    groups never appear in the output (no n maps to them).
    """
    gid = group_ids.astype(jnp.int32)
    acc = hier_segment_sum_ref(x, w, gid, num_groups).reshape(num_groups, -1)
    gw = jax.ops.segment_sum(w.astype(jnp.float32), gid,
                             num_segments=num_groups)
    mean = acc / jnp.maximum(gw, 1e-12)[:, None]
    return mean[gid].reshape(x.shape)


def hier_segment_sum_ref(x, w, group_ids, num_groups: int):
    """Per-group weighted sums (eq. 6 numerator), fp32: (N, ...) ->
    (num_groups, ...) with out[m] = sum_{n in group m} w[n] x[n]."""
    xf = x.reshape(x.shape[0], -1).astype(jnp.float32)
    acc = jax.ops.segment_sum(w.astype(jnp.float32)[:, None] * xf,
                              group_ids.astype(jnp.int32),
                              num_segments=num_groups)
    return acc.reshape((num_groups,) + x.shape[1:])


def decode_attention_ref(q, k_cache, v_cache, slot_pos, pos, *,
                         window: int = 0):
    """One-token GQA attention over a ring KV cache.

    q: (B,1,H,hd); caches (B,W,K,hd); slot_pos (W,) absolute positions
    (negative sentinel = empty); pos scalar.  Mirrors
    attention.decode_self_attention's masking.
    """
    B, _, H, hd = q.shape
    W, K = k_cache.shape[1], k_cache.shape[2]
    g = H // K
    qg = q.reshape(B, K, g, hd).astype(jnp.float32)
    s = jnp.einsum("bkgh,bwkh->bkgw", qg, k_cache.astype(jnp.float32))
    s = s / jnp.sqrt(hd).astype(jnp.float32)
    valid = (slot_pos >= 0) & (slot_pos <= pos)
    if window > 0:
        valid &= (pos - slot_pos) < window
    s = jnp.where(valid[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgw,bwkh->bkgh", p, v_cache.astype(jnp.float32))
    return o.reshape(B, 1, H, hd).astype(q.dtype)
