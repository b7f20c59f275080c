"""Weighted model aggregation — eqs. (6) and (10).

Three layouts:

* list-of-pytrees (simulation backend bookkeeping): ``weighted_average``;
* STACKED pytrees whose leaves carry a leading UE axis (the vmap layout):
  ``stacked_weighted_average``;
* the FLAT buffer (``repro.fl.flatten``): ``flat_edge_aggregate`` /
  ``flat_cloud_aggregate`` — the hot path.

Flat-buffer layout: the whole stacked model is one contiguous
``(N, F_total)`` fp32 buffer (leaf order = treedef order, each leaf
flattened row-major into its column slice).  Each aggregation event is
then ONE operation over the buffer instead of one per pytree leaf:

* edge (eq. 6)  — per-edge weighted segment mean, scattered back to the
  members' rows;
* cloud (eq. 10) — global weighted mean, broadcast back to every row.

Kernel dispatch rules: on TPU both events lower to a single fused Pallas
kernel (``repro.kernels.ops.hier_segment_aggregate`` /
``hier_cloud_aggregate``); elsewhere a pure-jnp segment_sum/tensordot path
is used (running the Pallas kernels in interpret mode off-TPU would be
strictly slower).  ``use_kernel=None`` (the default) applies this backend
auto-selection; pass True/False to force a path (tests do).

Mesh sharding (``mesh=``): pass a ('data', 'model') mesh and a buffer in
the padded ``ShardedFlatLayout`` form (rows a multiple of the data axis,
columns a multiple of the model axis) and both events run under
``shard_map``, each device invoking the kernel/jnp body on ONLY its own
``(N/num_data, F/num_model)`` slab with the feature block width sized to
its slab (``repro.kernels.ops.pick_agg_blk_f``).  Collective pattern:

* edge (eq. 6): ZERO cross-device traffic.  The layout's group-aligned
  row permutation guarantees no edge straddles a data shard, so local
  segment means ARE the global ones; the feature axis is embarrassingly
  parallel to begin with.
* cloud (eq. 10): exactly ONE small collective — a psum over 'data' of
  the per-shard ``(F/num_model + 1,)`` partial weighted sums (numerator
  concatenated with the weight denominator), then a local broadcast-back.
  Devices in the same 'data' row never exchange feature columns.

``stacked_weighted_average`` keeps the pytree API for callers outside the
hot loop: it ravels through the flat buffer, aggregates once, and
unravels back to the original dtypes/shapes.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.fl.flatten import FlatLayout
from repro.kernels.ops import (hier_aggregate, hier_cloud_aggregate,
                               hier_segment_aggregate, pick_agg_blk_f)
from repro.launch.mesh import DATA_AXIS, MODEL_AXIS
from repro.parallel.sharding import (flat_buffer_col_spec,
                                     flat_buffer_row_spec, flat_buffer_spec)


def _shard_map_novma(fn, mesh, in_specs, out_specs):
    """shard_map without varying-axes checking: a ``pallas_call`` output
    carries no vma annotation, so the check would reject the kernel
    bodies; the aggregation bodies are checked by parity tests instead."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def weighted_sum(weights, buf):
    """``sum_n weights[n] * buf[n]`` in fp32 over the leading axis: the
    numerator of eqs. 6 and 10.  The contraction is asked for at fp32
    precision, since the TPU's default rounds f32 matmul operands to
    bf16 and these are exact weighted means of model weights."""
    return jnp.tensordot(weights, buf.astype(jnp.float32), axes=1,
                         precision=jax.lax.Precision.HIGHEST)


def _select_kernel(use_kernel: Optional[bool]) -> bool:
    if use_kernel is None:
        return jax.default_backend() == "tpu"
    return bool(use_kernel)


def _axis_size(mesh, axis: str) -> int:
    return int(dict(mesh.shape).get(axis, 1))


def _trivial_mesh(mesh) -> bool:
    """A 1-device mesh shards nothing; skip shard_map (pure overhead)."""
    sizes = list(dict(mesh.shape).values())
    return int(np.prod(sizes)) == 1 if sizes else True


def weighted_average(params_list: Sequence, weights: Sequence[float]):
    """eq. (6)/(10): sum_n D_n w_n / sum_n D_n over a list of pytrees."""
    w = jnp.asarray(weights, jnp.float32)
    w = w / jnp.sum(w)

    def avg(*leaves):
        stack = jnp.stack([l.astype(jnp.float32) for l in leaves])
        out = weighted_sum(w, stack)
        return out.astype(leaves[0].dtype)

    return jax.tree.map(avg, *params_list)


def psum_weighted_mean(num, den, axis):
    """ONE-collective weighted mean inside shard_map/pmap (eq. 10's
    ``sum_n D_n w_n / sum_n D_n`` with the sums split across devices).

    ``num`` is the locally pre-weighted numerator vector, ``den`` the local
    weight sum; they are concatenated so the cross-device reduction is a
    SINGLE psum of ``len(num) + 1`` floats (the pattern both the sharded
    cloud aggregate and the SPMD backend's per-event flat psum use).
    """
    v = jnp.concatenate([num, jnp.reshape(den, (1,)).astype(num.dtype)])
    v = jax.lax.psum(v, axis)
    return v[:-1] / v[-1]


def psum_staleness_merge(global_vec, num, wd_sum, w_total, axis):
    """Staleness-weighted variant of ``psum_weighted_mean`` — the async
    cloud-merge rule (BEYOND-PAPER; FedAsync-style mixing).

    Inside shard_map each device contributes its local decayed-weight
    numerator ``num = sum_n w_n d_n row_n`` and scalar mass
    ``wd_sum = sum_n w_n d_n`` (``d_n = decay**staleness`` for rows of
    arrived edges, 0 otherwise); one psum of ``len(num) + 1`` floats later
    the cloud model updates as

        g <- (1 - Lambda) g + psum(num) / W,   Lambda = psum(wd_sum) / W

    with ``W = sum_n w_n`` the TOTAL fleet weight (eq. 10's denominator,
    passed in — it is static, no collective needed).  When every edge has
    arrived with staleness 0, Lambda == 1 and this reduces EXACTLY to
    eq. 10's weighted mean — the ``max_staleness=0`` parity path.
    """
    v = jnp.concatenate([num, jnp.reshape(wd_sum, (1,)).astype(num.dtype)])
    v = jax.lax.psum(v, axis)
    lam = v[-1] / w_total
    return (1.0 - lam) * global_vec + v[:-1] / w_total


# ---------------------------------------------------------------------------
# Flat-buffer aggregation — the hot path (one dispatch per event).
# ---------------------------------------------------------------------------


def _cloud_body(buf, weights, kernel: bool, blk_f: int):
    """Single-slab cloud aggregation (eq. 10): mean + broadcast-back."""
    if kernel:
        return hier_cloud_aggregate(buf, weights, blk_f=blk_f)
    mean = weighted_sum(weights, buf) / jnp.sum(weights)
    return jnp.broadcast_to(mean[None], buf.shape).astype(jnp.float32)


def _edge_body(buf, weights, group_ids, ng: int, kernel: bool, blk_f: int):
    """Single-slab edge aggregation (eq. 6): segment mean + scatter-back."""
    if kernel:
        return hier_segment_aggregate(buf, weights, group_ids,
                                      num_groups=ng, blk_f=blk_f)
    bf = buf.astype(jnp.float32)
    acc = jax.ops.segment_sum(weights[:, None] * bf, group_ids,
                              num_segments=ng)
    gw = jax.ops.segment_sum(weights, group_ids, num_segments=ng)
    mean = acc / jnp.maximum(gw, 1e-12)[:, None]
    return mean[group_ids]


def flat_cloud_aggregate(buf, weights, *, use_kernel: Optional[bool] = None,
                         mesh=None):
    """Cloud aggregation (eq. 10) over the flat buffer.

    buf: (N, F_total) float, weights: (N,) -> (N, F_total) fp32 with every
    row holding the global weighted mean.

    With ``mesh`` (a ('data', 'model') mesh; buf in the padded
    ``ShardedFlatLayout`` form) the event runs under shard_map: each device
    reduces its own slab, the per-shard partial sums meet in one small
    psum over 'data', and the broadcast-back stays device-local.
    """
    weights = jnp.asarray(weights, jnp.float32)
    kernel = _select_kernel(use_kernel)
    if mesh is None or _trivial_mesh(mesh):
        blk = pick_agg_blk_f(buf.shape[0], 1, buf.shape[1])
        return _cloud_body(buf, weights, kernel, blk)

    nd = _axis_size(mesh, DATA_AXIS)
    nm = _axis_size(mesh, MODEL_AXIS)
    spec = flat_buffer_spec(mesh)
    row_spec = flat_buffer_row_spec(mesh)
    blk = pick_agg_blk_f(buf.shape[0] // nd, 1, buf.shape[1] // nm)

    if nd == 1:
        def local_fn(b, w):
            return _cloud_body(b, w, kernel, blk)
    else:
        def local_fn(b, w):
            den = jnp.sum(w)
            if kernel:
                # local weighted mean * local weight sum = local weighted
                # sum; guard the all-padding shard (den == 0 -> mean NaN).
                num = jnp.where(den > 0,
                                hier_aggregate(b, w, blk_f=blk) * den, 0.0)
            else:
                num = weighted_sum(w, b)
            mean = psum_weighted_mean(num, den, DATA_AXIS)
            return jnp.broadcast_to(mean[None], b.shape).astype(jnp.float32)

    fn = _shard_map_novma(local_fn, mesh, (spec, row_spec), spec)
    return fn(buf, weights)


def flat_edge_aggregate(buf, weights, group_ids, num_groups: int, *,
                        use_kernel: Optional[bool] = None, mesh=None):
    """Edge aggregation (eq. 6) over the flat buffer.

    buf: (N, F_total) float, weights: (N,), group_ids: (N,) ints ->
    (N, F_total) fp32 with row n holding the weighted mean of n's edge.

    With ``mesh`` the event runs under shard_map with ZERO cross-device
    traffic: rows must be group-aligned to the data shards (no edge
    straddles a shard — ``ShardedFlatLayout`` guarantees this), so every
    device's local segment means equal the global ones.
    """
    weights = jnp.asarray(weights, jnp.float32)
    group_ids = jnp.asarray(group_ids, jnp.int32)
    ng = int(num_groups)
    kernel = _select_kernel(use_kernel)
    if mesh is None or _trivial_mesh(mesh):
        blk = pick_agg_blk_f(buf.shape[0], ng, buf.shape[1])
        return _edge_body(buf, weights, group_ids, ng, kernel, blk)

    nd = _axis_size(mesh, DATA_AXIS)
    nm = _axis_size(mesh, MODEL_AXIS)
    spec = flat_buffer_spec(mesh)
    row_spec = flat_buffer_row_spec(mesh)
    blk = pick_agg_blk_f(buf.shape[0] // nd, ng, buf.shape[1] // nm)

    def local_fn(b, w, g):
        return _edge_body(b, w, g, ng, kernel, blk)

    fn = _shard_map_novma(local_fn, mesh, (spec, row_spec, row_spec), spec)
    return fn(buf, weights, group_ids)


def flat_staleness_merge(global_vec, buf, eff_weights, w_total, *, mesh=None):
    """Async cloud merge (BEYOND-PAPER): staleness-weighted update of the
    cloud model from the arrived edges' rows of the flat buffer.

    global_vec:  (F,) fp32 cloud model (padded F under ``mesh``);
    buf:         (N, F) flat buffer (padded/sharded form under ``mesh``);
    eff_weights: (N,) effective row weights ``w_n * decay**staleness`` for
                 members of arrived edges, 0 for everything else (including
                 padding rows);
    w_total:     python float, TOTAL fleet weight ``sum_n w_n`` (eq. 10's
                 denominator — static, so no collective is spent on it).

    Update rule (reduces to eq. 10 when all edges arrive with staleness 0,
    i.e. the ``max_staleness=0`` barrier — that is the sync-parity path):

        g <- (1 - Lambda) g + sum_n eff_n row_n / W,  Lambda = sum_n eff_n / W

    With ``mesh`` the merge runs under shard_map reusing the ONE-collective
    pattern of the sharded cloud aggregate: each device reduces its own
    slab and the partials meet in a single psum over 'data'
    (``psum_staleness_merge``); feature columns never leave their shard.
    """
    eff_weights = jnp.asarray(eff_weights, jnp.float32)
    w_total = float(w_total)
    g32 = global_vec.astype(jnp.float32)
    if mesh is None or _trivial_mesh(mesh):
        num = weighted_sum(eff_weights, buf)
        lam = jnp.sum(eff_weights) / w_total
        return (1.0 - lam) * g32 + num / w_total

    nd = _axis_size(mesh, DATA_AXIS)
    spec = flat_buffer_spec(mesh)
    row_spec = flat_buffer_row_spec(mesh)
    col_spec = flat_buffer_col_spec(mesh)

    if nd == 1:
        def local_fn(g, b, w):
            num = weighted_sum(w, b)
            lam = jnp.sum(w) / w_total
            return (1.0 - lam) * g + num / w_total
    else:
        def local_fn(g, b, w):
            num = weighted_sum(w, b)
            return psum_staleness_merge(g, num, jnp.sum(w), w_total,
                                        DATA_AXIS)

    fn = _shard_map_novma(local_fn, mesh, (col_spec, spec, row_spec),
                          col_spec)
    return fn(g32, buf, eff_weights)


def survivor_weights(weights, survivors, group_ids, num_groups: int):
    """Renormalized survivor weights — the UNBIASED-mean masking rule
    for fault-injected rounds (BEYOND-PAPER, ``repro.core.faults``).

    Zeroing a dropped UE's weight already excludes it from the eq. 6
    segment mean, but it also shrinks its edge's total mass, biasing any
    downstream weighting that uses raw masses.  This rescales each
    edge's SURVIVING weights so the edge's total mass is preserved:

        w'_n = w_n * survivor_n * (W_m / W_m^surv),   n in edge m

    An edge with NO survivors keeps all-zero weights — combined with the
    zero-weight guard in ``flat_edge_aggregate`` (``max(gw, 1e-12)``) a
    fully-dropped cohort contributes an exact 0, never a NaN, on both
    the jnp and the Pallas kernel paths.
    """
    w = jnp.asarray(weights, jnp.float32)
    s = jnp.asarray(survivors)
    gids = jnp.asarray(group_ids, jnp.int32)
    ng = int(num_groups)
    masked = w * s.astype(jnp.float32)
    w_full = jax.ops.segment_sum(w, gids, num_segments=ng)
    w_surv = jax.ops.segment_sum(masked, gids, num_segments=ng)
    scale = jnp.where(w_surv > 0, w_full / jnp.maximum(w_surv, 1e-12), 0.0)
    return masked * scale[gids]


# ---------------------------------------------------------------------------
# Streaming edge aggregation (BEYOND-PAPER): cohort-scale eq. 6.
# ---------------------------------------------------------------------------


class StreamingEdgeAccumulator:
    """Chunked/streaming edge aggregation (eq. 6) with O(M*F) residency.

    At N = 10^5-10^6 the flat ``(N, F_total)`` buffer is untenable; with
    sampled participation (``repro.fl.sampling``) only a cohort uploads
    per round anyway, and arrivals come in waves.  This accumulator folds
    each arriving chunk of client rows into a persistent
    ``(num_groups, F)`` weighted-sum accumulator plus an ``(M,)`` mass
    vector — the resident state is independent of N (cohort chunks are
    transient), and the final per-edge means are bit-for-bit the same
    ratio ``sum w x / sum w`` the one-shot path computes.

    Kernel dispatch mirrors ``flat_edge_aggregate``: on TPU each chunk
    reduces through the fused ``hier_segment_accumulate`` Pallas kernel,
    elsewhere through ``jax.ops.segment_sum``.

    Typical use (see ``benchmarks/bench_scale.py``)::

        acc = StreamingEdgeAccumulator(num_edges, f_total)
        for rows, w, gid in arrival_waves:      # each a cohort chunk
            acc.add(rows, w, gid)
        means = acc.edge_means()                # (M, F)
    """

    def __init__(self, num_groups: int, f_total: int, *,
                 use_kernel: Optional[bool] = None):
        self.num_groups = int(num_groups)
        self.f_total = int(f_total)
        self.kernel = _select_kernel(use_kernel)
        self.num = jnp.zeros((self.num_groups, self.f_total), jnp.float32)
        self.mass = jnp.zeros((self.num_groups,), jnp.float32)

    def add(self, buf, weights, group_ids):
        """Fold one chunk: buf (n_chunk, F), weights (n_chunk,), group_ids
        (n_chunk,).  Zero-weight rows (pad rows, masked UEs) add nothing."""
        w = jnp.asarray(weights, jnp.float32)
        gid = jnp.asarray(group_ids, jnp.int32)
        if self.kernel:
            from repro.kernels.ops import hier_segment_accumulate
            blk = pick_agg_blk_f(buf.shape[0], self.num_groups, buf.shape[1])
            num = hier_segment_accumulate(buf, w, gid,
                                          num_groups=self.num_groups,
                                          blk_f=blk)
        else:
            num = jax.ops.segment_sum(w[:, None] * buf.astype(jnp.float32),
                                      gid, num_segments=self.num_groups)
        self.num = self.num + num
        self.mass = self.mass + jax.ops.segment_sum(
            w, gid, num_segments=self.num_groups)
        return self

    def edge_means(self):
        """(M, F) fp32 per-edge weighted means; an edge that never saw
        mass yields an exact 0 row (same guard as ``_edge_body``)."""
        mean = self.num / jnp.maximum(self.mass, 1e-12)[:, None]
        return jnp.where((self.mass > 0)[:, None], mean, 0.0)

    def cloud_mean(self):
        """(F,) eq. 10 over everything folded so far: the accumulator
        already holds per-edge numerators, so the cloud mean is one more
        reduction — no per-row pass."""
        total = jnp.maximum(self.mass.sum(), 1e-12)
        return self.num.sum(0) / total

    def scatter(self, group_ids):
        """Broadcast edge means back to rows: (n,) ids -> (n, F)."""
        return self.edge_means()[jnp.asarray(group_ids, jnp.int32)]

    def reset(self) -> "StreamingEdgeAccumulator":
        """Zero the accumulator for reuse.  Long-lived consumers (the
        service's merge queue folds one edge cohort per arrival) keep ONE
        accumulator alive instead of re-allocating per wave."""
        self.num = jnp.zeros_like(self.num)
        self.mass = jnp.zeros_like(self.mass)
        return self

    def resident_bytes(self) -> int:
        """Bytes of persistent accumulator state (independent of N)."""
        return int(self.num.size * 4 + self.mass.size * 4)


def streaming_edge_aggregate(buf, weights, group_ids, num_groups: int, *,
                             chunk_size: int,
                             use_kernel: Optional[bool] = None):
    """One-shot-parity wrapper over ``StreamingEdgeAccumulator``.

    Folds ``buf`` through the accumulator in ``chunk_size``-row chunks
    and scatters the means back — equals ``flat_edge_aggregate`` to
    <= 1e-5 at any chunk size (fp32 chunk-order reassociation only;
    property-tested at chunk sizes {1, 7, N}).
    """
    n = buf.shape[0]
    chunk = max(1, int(chunk_size))
    w = jnp.asarray(weights, jnp.float32)
    gid = jnp.asarray(group_ids, jnp.int32)
    acc = StreamingEdgeAccumulator(int(num_groups), int(buf.shape[1]),
                                   use_kernel=use_kernel)
    for start in range(0, n, chunk):
        stop = min(start + chunk, n)
        acc.add(buf[start:stop], w[start:stop], gid[start:stop])
    return acc.scatter(gid)


# ---------------------------------------------------------------------------
# Stacked-pytree API (ravels through the flat buffer).
# ---------------------------------------------------------------------------


def stacked_weighted_average(stacked, weights, *, group_ids=None,
                             num_groups: Optional[int] = None,
                             use_kernel: Optional[bool] = None):
    """Weighted mean over the leading UE axis of every leaf.

    group_ids=None      -> cloud aggregation (eq. 10): one global mean,
                           broadcast back to every UE slot.
    group_ids=(N,) ints -> edge aggregation (eq. 6): segment mean per edge,
                           broadcast back to that edge's members.

    Internally packs the pytree into the flat ``(N, F_total)`` buffer so
    the whole event is one dispatch, then restores leaf dtypes/shapes.
    """
    layout = FlatLayout.of(stacked)
    buf = layout.ravel(stacked)
    if group_ids is None:
        out = flat_cloud_aggregate(buf, weights, use_kernel=use_kernel)
    else:
        out = flat_edge_aggregate(buf, weights, group_ids,
                                  int(num_groups), use_kernel=use_kernel)
    return layout.unravel(out)
