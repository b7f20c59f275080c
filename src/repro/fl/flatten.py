"""Flat-buffer packing of stacked pytrees — the aggregation hot-path layout.

Every aggregation event in Alg. 1 (edge eq. 6, cloud eq. 10) is a weighted
mean over the leading UE axis of EVERY leaf.  Doing that leaf-by-leaf costs
one XLA dispatch per leaf per event; packing the stacked pytree into one
contiguous ``(N, F_total)`` fp32 buffer turns each event into a single
fused kernel call over the whole model (the layout Liu et al. 2019 and
Lin et al. 2023 use to scale their hierarchical-FL evaluations).

``FlatLayout`` caches everything needed to round-trip:

* ``treedef``  — the pytree structure;
* ``shapes``   — per-leaf trailing shapes (without the leading N);
* ``dtypes``   — per-leaf dtypes, restored on unravel;
* ``offsets``  — per-leaf start column in the flat feature axis.

``ravel``/``unravel`` are pure jnp reshapes + concat/slice, so under jit
they fuse to (nearly) free layout ops; the simulation backend keeps its
state as the flat buffer and unravels only at train/eval boundaries.

Sharded layout (``ShardedFlatLayout``): on a ('data', 'model') mesh the
buffer is distributed without replication —

* the feature axis is zero-PADDED from ``F_total`` to ``f_padded``, a
  multiple of the model-axis size, and sharded over 'model' (logical axis
  'feat'), so each device owns one contiguous ``f_padded / num_model``
  column slab;
* the UE axis is sharded over 'data' (logical axis 'ue') after a GROUP-
  ALIGNED row permutation: edges are bin-packed onto data shards (largest
  group first) and every shard is padded with zero-weight rows to the
  common ``rows_per_shard``, so no edge ever straddles a shard boundary.

That alignment is what makes edge aggregation (eq. 6) embarrassingly
parallel — every device segment-means only rows it owns, ZERO cross-device
traffic — while the cloud mean (eq. 10) needs exactly one small
``psum`` of per-shard partial sums over 'data' (see repro.fl.aggregate).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_LAYOUT_CACHE: dict = {}


@dataclasses.dataclass(frozen=True)
class FlatLayout:
    treedef: Any
    shapes: Tuple[tuple, ...]      # trailing (per-UE) shape of each leaf
    dtypes: Tuple[Any, ...]
    sizes: Tuple[int, ...]         # prod(shape) per leaf
    offsets: Tuple[int, ...]       # start column of each leaf
    total: int                     # F_total

    # -- construction ---------------------------------------------------

    @classmethod
    def of(cls, stacked) -> "FlatLayout":
        """Layout of a STACKED pytree (every leaf ``(N, *shape)``) — the
        shape the aggregation events (eqs. 6/10) reduce over."""
        leaves, treedef = jax.tree.flatten(stacked)
        shapes = tuple(tuple(l.shape[1:]) for l in leaves)
        dtypes = tuple(l.dtype for l in leaves)
        return cls._build(treedef, shapes, dtypes)

    @classmethod
    def of_single(cls, params) -> "FlatLayout":
        """Layout of an UNSTACKED pytree (one model, no UE axis)."""
        leaves, treedef = jax.tree.flatten(params)
        shapes = tuple(tuple(l.shape) for l in leaves)
        dtypes = tuple(l.dtype for l in leaves)
        return cls._build(treedef, shapes, dtypes)

    @classmethod
    def _build(cls, treedef, shapes, dtypes) -> "FlatLayout":
        key = (treedef, shapes, dtypes)
        hit = _LAYOUT_CACHE.get(key)
        if hit is not None:
            return hit
        sizes = tuple(int(np.prod(s, dtype=np.int64)) for s in shapes)
        offsets = tuple(int(o) for o in np.cumsum((0,) + sizes)[:-1])
        layout = cls(treedef=treedef, shapes=shapes, dtypes=dtypes,
                     sizes=sizes, offsets=offsets, total=int(sum(sizes)))
        _LAYOUT_CACHE[key] = layout
        return layout

    # -- stacked round-trip ---------------------------------------------

    def ravel(self, stacked):
        """Pack a stacked pytree into one ``(N, F_total)`` fp32 buffer.

        Derivation: eqs. 6/10 apply the SAME weighted mean to every leaf,
        so concatenating the flattened leaves turns the whole event into
        one row-space reduction; under jit the reshapes/concat fuse to
        (nearly) free layout ops."""
        leaves = self.treedef.flatten_up_to(stacked)
        n = leaves[0].shape[0]
        cols = [l.reshape(n, -1).astype(jnp.float32) for l in leaves]
        return jnp.concatenate(cols, axis=1)

    def unravel(self, buf):
        """Inverse of ``ravel``: restore per-leaf shapes AND dtypes."""
        n = buf.shape[0]
        leaves = [
            buf[:, o:o + s].reshape((n,) + shp).astype(dt)
            for o, s, shp, dt in zip(self.offsets, self.sizes,
                                     self.shapes, self.dtypes)
        ]
        return self.treedef.unflatten(leaves)

    # -- single-model round-trip (eval / checkpoint boundaries) ---------

    def ravel_single(self, params):
        """One UNSTACKED model -> (F_total,) fp32 vector (the cloud/global
        model of eq. 10 outside the hot loop)."""
        leaves = self.treedef.flatten_up_to(params)
        return jnp.concatenate(
            [l.reshape(-1).astype(jnp.float32) for l in leaves])

    def unravel_single(self, vec):
        """Inverse of ``ravel_single``: restore leaf shapes AND dtypes."""
        leaves = [
            vec[o:o + s].reshape(shp).astype(dt)
            for o, s, shp, dt in zip(self.offsets, self.sizes,
                                     self.shapes, self.dtypes)
        ]
        return self.treedef.unflatten(leaves)


# ---------------------------------------------------------------------------
# Mesh-sharded layout of the flat buffer.
# ---------------------------------------------------------------------------


def _pack_groups(group_ids: np.ndarray, num_shards: int):
    """Bin-pack whole groups onto ``num_shards`` row shards (LPT greedy).

    Returns (perm, n_padded): ``perm`` has length ``n_padded`` (a multiple
    of num_shards); entry i is the original row index living at padded slot
    i, or -1 for a zero-weight padding row.  Every group's rows land on
    exactly one shard, so per-shard segment means equal global ones.
    """
    group_ids = np.asarray(group_ids)
    groups = np.unique(group_ids)
    rows = {g: np.flatnonzero(group_ids == g) for g in groups}
    order = sorted(groups, key=lambda g: -len(rows[g]))   # largest first
    bins: list = [[] for _ in range(num_shards)]
    loads = np.zeros(num_shards, dtype=np.int64)
    for g in order:
        s = int(np.argmin(loads))
        bins[s].extend(rows[g].tolist())
        loads[s] += len(rows[g])
    rows_per_shard = int(loads.max())
    perm = []
    for b in bins:
        perm.extend(b)
        perm.extend([-1] * (rows_per_shard - len(b)))
    return np.asarray(perm, dtype=np.int64), num_shards * rows_per_shard


@dataclasses.dataclass
class ShardedFlatLayout:
    """A ``FlatLayout`` distributed over a ('data', 'model') mesh.

    External API works in the ORIGINAL row order and true ``F_total``;
    internally the buffer is the padded ``(n_padded, f_padded)`` form whose
    row/column shards divide the mesh axes evenly (see module docstring).
    """
    base: FlatLayout
    mesh: Any
    num_data: int
    num_model: int
    num_rows: int                   # original N
    n_padded: int
    f_padded: int
    perm: np.ndarray                # (n_padded,) original index or -1
    inv_perm: np.ndarray            # (num_rows,) padded slot of each row

    @classmethod
    def build(cls, base: FlatLayout, mesh, num_rows: int,
              group_ids: Optional[np.ndarray] = None) -> "ShardedFlatLayout":
        """Derive the padded/permuted layout for ``mesh``.

        ``group_ids`` (the eq. 6 edge of each UE row) is required whenever
        the data axis is >1: edges are bin-packed whole onto row shards
        (``_pack_groups``) so each shard's LOCAL segment means equal the
        GLOBAL eq. 6 means — that is what keeps edge aggregation free of
        collectives.  Feature columns are zero-padded to a model-axis
        multiple (zero columns drop out of every weighted mean).
        """
        from repro.launch.mesh import DATA_AXIS, MODEL_AXIS
        shape = dict(mesh.shape)
        num_data = int(shape.get(DATA_AXIS, 1))
        num_model = int(shape.get(MODEL_AXIS, 1))
        f_padded = -(-base.total // num_model) * num_model
        if num_data > 1:
            if group_ids is None:
                raise ValueError("data-axis sharding needs group_ids to "
                                 "keep edges whole per shard")
            assert len(group_ids) == num_rows
            perm, n_padded = _pack_groups(np.asarray(group_ids), num_data)
        else:
            perm = np.arange(num_rows, dtype=np.int64)
            n_padded = num_rows
        inv_perm = np.empty(num_rows, dtype=np.int64)
        inv_perm[perm[perm >= 0]] = np.flatnonzero(perm >= 0)
        return cls(base=base, mesh=mesh, num_data=num_data,
                   num_model=num_model, num_rows=num_rows,
                   n_padded=n_padded, f_padded=f_padded,
                   perm=perm, inv_perm=inv_perm)

    # -- padded-form helpers (permuted rows, padded columns) ------------

    @property
    def spec(self):
        """PartitionSpec of the padded buffer on ``self.mesh``."""
        from repro.parallel.sharding import flat_buffer_spec
        return flat_buffer_spec(self.mesh)

    @property
    def row_spec(self):
        """PartitionSpec of per-row vectors (weights, group ids)."""
        from repro.parallel.sharding import flat_buffer_row_spec
        return flat_buffer_row_spec(self.mesh)

    @property
    def col_spec(self):
        """PartitionSpec of per-column vectors (the eq. 10 global / async
        cloud model)."""
        from repro.parallel.sharding import flat_buffer_col_spec
        return flat_buffer_col_spec(self.mesh)

    def per_device_bytes(self) -> int:
        """fp32 bytes of one device's (rows, cols) slab."""
        return (self.n_padded // self.num_data) * \
               (self.f_padded // self.num_model) * 4

    def pad(self, buf):
        """(N, F_total) -> padded (n_padded, f_padded); pad rows are row-0
        copies (their weight is zero wherever it matters)."""
        if self.f_padded > self.base.total:
            buf = jnp.pad(buf, ((0, 0), (0, self.f_padded - self.base.total)))
        if self.n_padded != self.num_rows or np.any(self.perm !=
                                                    np.arange(self.num_rows)):
            buf = buf[jnp.asarray(np.maximum(self.perm, 0))]
        return buf

    def unpad(self, buf):
        """Inverse of ``pad``: original row order, true F_total columns."""
        out = buf[:, :self.base.total]
        if self.n_padded != self.num_rows or np.any(self.perm !=
                                                    np.arange(self.num_rows)):
            out = out[jnp.asarray(self.inv_perm)]
        return out

    def pad_rows(self, x):
        """Permute+pad any per-row array/pytree (leading axis num_rows).
        Host (numpy) leaves are permuted on the host, so that placing the
        result sends each device only its own rows."""
        idx = np.maximum(self.perm, 0)
        return jax.tree.map(lambda l: l[idx] if isinstance(l, np.ndarray)
                            else l[jnp.asarray(idx)], x)

    def pad_weights(self, w):
        """Permute+pad the aggregation weights D_n; padding rows get
        weight 0, so they contribute nothing to the eq. 6/10 sums."""
        w = jnp.asarray(w, jnp.float32)
        mask = jnp.asarray(self.perm >= 0, jnp.float32)
        return w[jnp.asarray(np.maximum(self.perm, 0))] * mask

    def pad_mask(self, mask):
        """Permute+pad a boolean per-row mask; pad rows get **False**.

        ``pad_rows`` pads with row-0 copies — fine for latencies/ids whose
        pad slots are weight-masked anyway, but a hazard for booleans: a
        participation or survivor mask padded that way would mark a pad
        row as "sampled" whenever UE 0 is.  This variant forces every pad
        slot to False, so samplers and fault masks can never resurrect a
        zero-weight pad row.  Accepts any array whose LEADING axis is
        ``num_rows`` (matching ``pad_rows``).
        """
        idx = jnp.asarray(np.maximum(self.perm, 0))
        keep = jnp.asarray(self.perm >= 0)
        m = jnp.asarray(mask, bool)
        return m[idx] & keep.reshape((-1,) + (1,) * (m.ndim - 1))

    def gather_rows(self, buf, rows):
        """Materialize only the cohort ``rows`` (padded-order indices) of a
        padded buffer — the sampled-participation gather.  ``rows`` is a
        host int array; the result is ``(len(rows), f_padded)``."""
        return buf[jnp.asarray(np.asarray(rows, np.int64))]

    def scatter_rows(self, buf, rows, values):
        """Write cohort ``values`` back into the padded buffer at
        ``rows`` (inverse of ``gather_rows``); other rows untouched."""
        return buf.at[jnp.asarray(np.asarray(rows, np.int64))].set(values)

    # -- original-order round-trip --------------------------------------

    def ravel(self, stacked):
        """Stacked pytree -> padded sharded-ready buffer."""
        return self.pad(self.base.ravel(stacked))

    def unravel(self, buf):
        """Padded buffer -> stacked pytree in original row order."""
        return self.base.unravel(self.unpad(buf))

    def ravel_padded(self, stacked):
        """Stacked pytree ALREADY in padded row order -> padded buffer
        (the hot-loop round-trip: no permutation, just the column pad)."""
        buf = self.base.ravel(stacked)
        if self.f_padded > self.base.total:
            buf = jnp.pad(buf, ((0, 0), (0, self.f_padded - self.base.total)))
        return buf

    def unravel_padded(self, buf):
        """Padded buffer -> stacked pytree keeping the padded row order."""
        return self.base.unravel(buf[:, :self.base.total])
