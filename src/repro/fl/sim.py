"""Simulation backend — Algorithm 1 with a simulated wall clock.

Executes the exact 3-layer schedule on stacked UE replicas (vmap over the
leading UE axis; local iterations are a ``lax.fori_loop``), while the
CLOCK advances according to the paper's delay model:

    one cloud round costs  T = max_m { b * tau_m + t_{m->c} }   (eq. 34)

so the reported time-to-accuracy curves (Figs. 4/6) reflect the wireless
delay model, not CPU wall time.  Every UE's local data is resampled to a
common per-UE size so the replicas stack (documented simplification —
the true D_n still drives both the aggregation weights and the clock).

Hot-loop layout: the UE replicas live in ONE flat (N, F_total) fp32
buffer (``repro.fl.flatten``); the whole b-iteration edge loop carries
the buffer (donated, so it is updated in place) and every aggregation event
is a single fused dispatch (``repro.fl.aggregate.flat_*``).  Pytrees are
materialized only at train/eval/checkpoint boundaries.

Pass ``mesh=`` (a ('data', 'model') mesh) and the hot loop goes
mesh-parallel end-to-end: the buffer is carried in the padded
``ShardedFlatLayout`` form (UE rows group-aligned over 'data', feature
columns over 'model' — no replication), the b-iteration edge loop (local
training and eq. 6) runs under shard_map on each shard's own rows,
collective-free (``_edge_cycle``), and the cloud mean costs one small
psum (see repro.fl.aggregate).  Batches, weights and group ids are
permuted/padded once at construction.

Async mode (``mode="async"``, BEYOND-PAPER): the cloud barrier of eq. 34
is dropped.  ``repro.core.events`` simulates each edge's cycle
``b * tau_m + t_mc`` on its own clock with SSP staleness gating
(``max_staleness`` cycles of lead, 0 = exact synchronous barrier), and the
run REPLAYS that event trace: departures re-seed the departing edges' rows
from the cloud model and run their b-iteration cycle on those rows alone,
gathered into a row bucket of a few static sizes, then written back
(``flat_edge_aggregate``; the whole buffer at N_hot rows or under a
mesh), arrivals merge into the cloud vector with weights decayed by
``staleness_decay ** version_lag`` (``flat_staleness_merge`` — one psum
under a mesh).  At
``max_staleness=0`` the trajectory reproduces the synchronous path to
float tolerance; with a bound > 0 fast edges re-enter immediately and the
makespan drops strictly below the eq. 34 bound on heterogeneous fleets.

Stochastic clock (``delay_model=``, BEYOND-PAPER): a
``repro.core.stochastic.DelayModel`` replaces the constant delays with
keyed per-cycle draws — sync rounds cost the per-round ``max_m`` draw,
async departures each consume a fresh row of the pre-sampled cycle
matrix.  ``delay_seed`` keys the draws; ``DeterministicDelays`` (and the
default ``None``) keep today's behavior exactly.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.core import delay, faults, stochastic
from repro.core.schedule import HFLSchedule
from repro.fl import aggregate, clients
from repro.fl.flatten import FlatLayout, ShardedFlatLayout
from repro.parallel.sharding import flat_buffer_row_spec


def _combine_masks(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """AND two (C, N) bool mask matrices with mismatched row counts by
    clamping each to its last row (the same clamp the async replay applies
    per event), so faults x sampling compose into ONE mask."""
    rows = max(a.shape[0], b.shape[0])
    ai = np.minimum(np.arange(rows), a.shape[0] - 1)
    bi = np.minimum(np.arange(rows), b.shape[0] - 1)
    return a[ai] & b[bi]


def _vmapped(local_steps, batches):
    """Every UE row's local steps at once, each on its own batch."""
    return lambda p: jax.vmap(local_steps)(p, batches)


def _edge_round(train, unravel, ravel, weights, group_ids, num_edges, mesh):
    """Body of the b-iteration edge loop over the flat buffer: the local
    steps (``train`` on the unravelled rows), then eq. 6 in one dispatch.
    The ``hfl.*`` named scopes label the program's instructions (HLO
    metadata only; see ``HFLSimulator.op_scopes``)."""
    def edge_round(_, buf):
        with jax.named_scope("hfl.local_step"):
            trained = ravel(train(unravel(buf)))
        with jax.named_scope("hfl.edge_agg"):
            return aggregate.flat_edge_aggregate(
                trained, weights, group_ids, num_edges, mesh=mesh)
    return edge_round


def _edge_cycle(local_steps, unravel, ravel, b, num_edges, mesh):
    """``cycle(flat, batches, weights, group_ids)``: the b-iteration edge
    loop of a cloud round (``_edge_round`` b times) over the flat buffer.

    Under a mesh the loop runs under shard_map over the row axis: each
    device trains and edge-aggregates only the rows it holds, with the
    one-chip body (the layout keeps every edge whole on one row shard, so
    its local eq. 6 means are the global ones).  A feature-sharded buffer
    is first gathered to whole rows, inside ``hfl.local_step``."""
    def cycle(flat, batches, weights, group_ids):
        return jax.lax.fori_loop(
            0, b, _edge_round(_vmapped(local_steps, batches), unravel, ravel,
                              weights, group_ids, num_edges, None), flat)
    if mesh is None:
        return cycle
    rows = flat_buffer_row_spec(mesh)
    sharded = jax.shard_map(cycle, mesh=mesh, in_specs=(rows,) * 4,
                            out_specs=rows, check_vma=False)

    def row_cycle(flat, batches, weights, group_ids):
        with jax.named_scope("hfl.local_step"):
            flat = jax.lax.with_sharding_constraint(
                flat, NamedSharding(mesh, rows))
        return sharded(flat, batches, weights, group_ids)
    return row_cycle


def _wave_ladder(group_ids: np.ndarray) -> tuple:
    """Row-bucket sizes of a departure wave, ascending: W, 2W and 4W rows,
    W the largest cohort, each rounded up to a whole 8-row sublane tile and
    kept only below N_hot, then N_hot itself (the whole buffer)."""
    n = int(group_ids.shape[0])
    w = int(np.bincount(group_ids).max())
    sizes = {-(-k * w // 8) * 8 for k in (1, 2, 4)}
    return tuple(sorted(s for s in sizes if s < n)) + (n,)


def _departure_wave(local_steps, unravel, ravel, b, group_ids, num_edges,
                    mesh):
    """Body of a departure wave, ``wave(flat, g, batches, rows, weights)``.

    ``rows`` is either the (N_hot,) bool mask of the departing rows (the
    N_hot bucket: every row trains, only the masked ones are committed)
    or an int32 (bucket,) index of them, padded with N_hot.  An index
    gathers only its rows' batches, weights and group ids, seeds every
    row from ``g``, runs the b-iteration edge cycle on the bucket and
    scatters the real rows back; padding rows carry weight 0 and the
    group id of a departing row, and the scatter drops them."""
    def cycle(seeded, batches, weights, gids):
        return jax.lax.fori_loop(
            0, b, _edge_round(_vmapped(local_steps, batches), unravel, ravel,
                              weights, gids, num_edges, mesh), seeded)

    def wave(flat, g, batches, rows, weights):
        if rows.dtype == jnp.bool_:
            with jax.named_scope("hfl.wave_select"):
                seeded = jnp.where(rows[:, None], g[None, :], flat)
            new = cycle(seeded, batches, weights, group_ids)
            with jax.named_scope("hfl.wave_select"):
                return jnp.where(rows[:, None], new, flat)
        n = flat.shape[0]
        with jax.named_scope("hfl.wave_select"):
            real = rows < n
            at = jnp.where(real, rows, jnp.minimum(rows[0], n - 1))
            seeded = jnp.broadcast_to(g[None, :], (rows.shape[0],) + g.shape)
            sub = jax.tree.map(lambda x: x[at], batches)
        new = cycle(seeded, sub, jnp.where(real, weights[at], 0.0),
                    group_ids[at])
        with jax.named_scope("hfl.wave_select"):
            return flat.at[rows].set(new, mode="drop")
    return wave


@dataclasses.dataclass
class SimResult:
    times: np.ndarray          # (R,) cumulative simulated seconds per eval
    test_acc: np.ndarray       # (R,)
    test_loss: np.ndarray      # (R,)
    train_loss: np.ndarray     # (R,)
    schedule: HFLSchedule
    final_params: object
    timeline: object = None    # core.events.AsyncTimeline (async mode only)


class _EvalLog:
    """The evaluation points of one ``run``: clock, test accuracy and
    loss, and weighted train loss, one entry each per point."""

    def __init__(self):
        self.times, self.accs, self.tlosses, self.trlosses = [], [], [], []

    def last(self) -> str:
        return f"acc={self.accs[-1]:.4f}  loss={self.tlosses[-1]:.4f}"

    def result(self, schedule, final_params, timeline=None) -> SimResult:
        return SimResult(times=np.array(self.times),
                         test_acc=np.array(self.accs),
                         test_loss=np.array(self.tlosses),
                         train_loss=np.array(self.trlosses),
                         schedule=schedule, final_params=final_params,
                         timeline=timeline)


class HFLSimulator:
    """Run Alg. 1 for a schedule over a federated dataset.

    loss_fn(params, batch) -> (loss, metrics) — one UE's full-batch loss.
    """

    def __init__(self, schedule: HFLSchedule, loss_fn: Callable,
                 init_params, ue_data: List[dict], *, lr: float = 0.05,
                 solver: str = "gd", dane_mu: float = 0.1,
                 samples_per_ue: Optional[int] = None, seed: int = 0,
                 mesh=None, mode: str = "sync",
                 max_staleness: Optional[int] = 0,
                 staleness_decay: float = 0.9, delay_model=None,
                 delay_seed: int = 0, fault_model=None, fault_policy=None,
                 fault_seed: int = 0, sampler=None, sample_seed: int = 0):
        """``delay_model`` (a ``repro.core.stochastic.DelayModel``) makes
        the CLOCK stochastic in both modes: sync rounds cost that round's
        ``max_m`` cycle draw instead of the constant eq. 34 ``T``, async
        departures each consume a fresh per-cycle draw.  The draws are
        keyed by ``delay_seed`` (same seed => identical clock and trace);
        ``DeterministicDelays()`` — or the default ``None`` — reproduces
        the constant-delay behavior exactly.  The MODEL trajectory only
        depends on the event order, so under ``DeterministicDelays`` it
        is unchanged too.

        ``fault_model`` (a ``repro.core.faults.FaultModel``, BEYOND-PAPER)
        injects UE dropout / uplink loss / edge outages into both the
        clock and the MODEL: rounds (sync) or departure cycles (async)
        aggregate only the cycle's SURVIVORS with per-edge-mass-preserving
        renormalized weights (``aggregate.survivor_weights``), a
        fully-dropped cohort contributes zero (never NaN) to the cloud
        mean, and the clock pays the policy's price — deadline cuts /
        capped retries / failover under ``deadline_failover_policy()``
        (the default), comeback-waits / unbounded retries / repair stalls
        under ``wait_for_all_policy()``.  A null fault model (``None`` or
        ``is_null()``) takes the exact legacy code paths, so all parity
        guarantees above are untouched.  ``fault_seed`` keys the fault
        draws (which subsume the delay draws in fault runs — see
        ``core.faults.faulty_cycle_stats``).

        ``sampler`` (a ``repro.fl.sampling.ClientSampler``, BEYOND-PAPER)
        turns on partial participation: each cloud round (sync) or
        departure cycle (async) aggregates only a sampled cohort per
        edge, with per-edge-mass-preserving reweighting
        (``sampling.participation_weights``) keeping eqs. 6/10 unbiased,
        and the CLOCK paced by the participants only (an unsampled UE
        never uploads, so it cannot straggle its edge).  Composes with
        ``fault_model`` by ANDing the masks and renormalizing ONCE —
        faults and sampling never double-discount (the fault run's clock
        pricing stays full-fleet: the policy cannot know the cohort when
        it sets deadlines).  ``sample_seed`` keys the draws.  A sampler
        with ``participation_rate=1.0`` is routed to ``None`` at
        construction, so full participation takes the exact legacy code
        paths (byte-identical, like a null fault model)."""
        if mode not in ("sync", "async"):
            raise ValueError(f"mode must be 'sync' or 'async', got {mode!r}")
        if max_staleness is None:
            # Joint-planned schedules (core.schedule.plan_joint) carry the
            # co-optimized SSP bound; None means "take the schedule's".
            max_staleness = int(schedule.meta.get("max_staleness", 0))
        if mode == "async" and solver != "gd":
            raise ValueError("mode='async' supports solver='gd' only (DANE's "
                             "global gradient assumes a synchronized fleet)")
        if max_staleness < 0:
            raise ValueError("max_staleness must be >= 0")
        if delay_model is not None and schedule.problem is None:
            raise ValueError("delay_model= needs schedule.problem to sample "
                             "the delay ingredients (eqs. 1-5, 8)")
        if fault_model is not None and fault_model.is_null():
            fault_model = None           # exact legacy paths (parity)
        if fault_model is not None:
            if schedule.problem is None:
                raise ValueError("fault_model= needs schedule.problem to "
                                 "price retries/deadlines (eqs. 1-5, 33)")
            if solver != "gd":
                raise ValueError("fault_model= supports solver='gd' only "
                                 "(DANE's global gradient assumes every UE "
                                 "reports; survivor masking breaks it)")
        if sampler is not None and sampler.is_full():
            sampler = None               # exact legacy paths (parity)
        if sampler is not None and solver != "gd":
            raise ValueError("sampler= supports solver='gd' only (DANE's "
                             "global gradient assumes every UE reports; "
                             "cohort masking breaks it)")
        self.sampler = sampler
        self.sample_seed = int(sample_seed)
        self.fault_model = fault_model
        self.fault_policy = (fault_policy if fault_policy is not None
                             else faults.deadline_failover_policy())
        self.fault_seed = int(fault_seed)
        self.delay_model = delay_model
        self.delay_seed = int(delay_seed)
        self.schedule = schedule
        self.loss_fn = loss_fn
        self.lr = lr
        self.solver = solver
        self.dane_mu = dane_mu
        self.mesh = mesh
        self.mode = mode
        self.max_staleness = int(max_staleness)
        self.staleness_decay = float(staleness_decay)
        n = schedule.num_ues
        assert len(ue_data) == n, (len(ue_data), n)

        # Stack UE datasets to a common size (resample with replacement).
        sizes = [d["labels"].shape[0] for d in ue_data]
        k = samples_per_ue or int(np.median(sizes))
        rng = np.random.default_rng(seed)
        resample = []
        for d in ue_data:
            m = d["labels"].shape[0]
            resample.append(rng.choice(m, size=k, replace=m < k)
                            if m != k else np.arange(k))
        stacked = {
            key: np.stack([d[key][ix] for d, ix in zip(ue_data, resample)])
            for key in ue_data[0]
        }
        # leaves (N, k, ...); on the device unless a mesh shards them below
        self.batches = (stacked if mesh is not None else
                        jax.tree.map(jnp.asarray, stacked))

        # Aggregation weights: the paper's D_n (eq. 6/10).
        if schedule.problem is not None:
            self.weights = jnp.asarray(schedule.problem.samples, jnp.float32)
        else:
            self.weights = jnp.asarray(sizes, jnp.float32)
        self.group_ids = jnp.asarray(schedule.assoc.argmax(1), jnp.int32)

        stacked_params = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), init_params)
        # Hot-loop state is the flat (N, F_total) buffer; the pytree form
        # is materialized only at eval/checkpoint boundaries.  With a mesh
        # the buffer (and the per-row hot inputs) live in the padded,
        # group-aligned sharded form end-to-end.
        self._layout = FlatLayout.of(stacked_params)
        if mesh is not None:
            self._slayout = ShardedFlatLayout.build(
                self._layout, mesh, num_rows=n,
                group_ids=np.asarray(self.group_ids))
            sl = self._slayout
            self._flat = jax.device_put(
                sl.ravel(stacked_params), NamedSharding(mesh, sl.spec))
            self._hot_batches = jax.device_put(
                sl.pad_rows(self.batches), NamedSharding(mesh, sl.row_spec))
            self._hot_weights = sl.pad_weights(self.weights)
            self._hot_gids = sl.pad_rows(self.group_ids)
        else:
            self._slayout = None
            self._flat = self._layout.ravel(stacked_params)
            self._hot_batches = self.batches
            self._hot_weights = self.weights
            self._hot_gids = self.group_ids
        # Inverse-propensity base measure for sampled aggregation: under a
        # non-uniform sampler the raw self-normalized cohort mean tilts
        # toward high-propensity UEs; `ipw_base_weights` divides that
        # tilt out once (static per run — propensities are pure in the
        # run key) while preserving every edge's true mass W_m.  Uniform
        # sampling (and no sampler) leaves the weights untouched.
        if self.sampler is not None:
            adj = self.sampler.ipw_base_weights(
                self.sample_seed, np.asarray(self.weights),
                np.asarray(self.group_ids), self.schedule.num_edges)
            self._hot_agg_weights = (
                self._slayout.pad_weights(adj) if self._slayout is not None
                else jnp.asarray(adj, jnp.float32))
        else:
            self._hot_agg_weights = self._hot_weights
        self._cloud_round = self._build_cloud_round()
        if mode == "async":
            self._depart_cycle, self._merge = self._build_async_ops()
        self._weighted_ops_cache = None
        # Row buckets of the departure waves; a mesh keeps the whole
        # buffer (a gather across its row shards would add collectives).
        self._wave_ladder = (
            (int(self._hot_gids.shape[0]),) if mesh is not None
            else _wave_ladder(np.asarray(self._hot_gids)))
        self._wave_exec = {}             # twin -> {bucket: compiled wave}
        self.wave_rows_trained = 0       # bucket rows the waves train
        self.wave_rows_kept = 0          # ... and the rows they commit
        self.wave_bucket_runs = {}       # bucket rows -> waves
        if fault_model is not None or sampler is not None:
            self._weighted_ops()    # build eagerly for fault/sampled runs
        # Weight-averaged train loss over ALL UEs (one vmap'd loss over the
        # hot rows; mesh padding rows carry zero weight).
        self._train_loss = jax.jit(
            lambda gp, batches, w: jnp.sum(
                (w / jnp.sum(w)) *
                jax.vmap(lambda bb: loss_fn(gp, bb)[0])(batches)))

    # ------------------------------------------------------------------

    @property
    def params(self):
        """Stacked UE replicas, unravelled from the flat buffer."""
        if self._slayout is not None:
            return self._slayout.unravel(self._flat)
        return self._layout.unravel(self._flat)

    @params.setter
    def params(self, stacked):
        if self._slayout is not None:
            self._flat = jax.device_put(
                self._slayout.ravel(stacked),
                NamedSharding(self.mesh, self._slayout.spec))
        else:
            self._flat = self._layout.ravel(stacked)

    def _build_cloud_round(self):
        a, b = self.schedule.a, self.schedule.b
        M = self.schedule.num_edges
        loss_fn, lr = self.loss_fn, self.lr
        weights, group_ids = self._hot_weights, self._hot_gids
        solver = self.solver
        dane_mu = self.dane_mu
        mesh = self.mesh
        unravel, ravel = self._row_codec()

        local_gd = clients.gd_local_steps(loss_fn, a, lr)
        local_dane = clients.dane_local_steps(loss_fn, a, lr, mu_prox=dane_mu)
        cycle = _edge_cycle(local_gd, unravel, ravel, b, M, mesh)

        def cloud_round(flat, batches):
            # The whole b-iteration edge loop carries the flat buffer;
            # unravel/ravel around local training are jit-fused reshapes,
            # and each aggregation event is a single dispatch (GD under a
            # mesh: each device's own rows, under shard_map).  DANE's
            # global gradient spans every row, so it keeps the partitioned
            # program.
            if solver == "dane":
                def train(p):
                    g_bar = clients.global_gradient(loss_fn, p, batches,
                                                    weights)
                    return jax.vmap(lambda pp, bb: local_dane(pp, bb, g_bar))(
                        p, batches)

                flat = jax.lax.fori_loop(
                    0, b, _edge_round(train, unravel, ravel, weights,
                                      group_ids, M, mesh), flat)
            else:
                flat = cycle(flat, batches, weights, group_ids)
            with jax.named_scope("hfl.cloud_agg"):
                return aggregate.flat_cloud_aggregate(flat, weights,
                                                      mesh=mesh)

        # Donate the flat buffer so the cloud round updates it in place.
        return jax.jit(cloud_round, donate_argnums=0)

    def _build_async_ops(self):
        """Jitted bodies of the async event replay (mode='async').

        * ``depart_cycle(flat, g, batches, rows)`` — one departure wave
          (``_departure_wave``): re-seed the departing edges' rows from
          the cloud vector ``g``, run their full b-iteration edge cycle
          (Alg. 1 lines 4-9: a local GD steps + eq. 6 edge aggregation, b
          times) and commit them; other rows pass through untouched.  One
          dispatch per wave.  ``rows`` picks the program: an int32 index
          of a few static bucket sizes (``_wave_ladder``) trains only the
          gathered rows, so a wave of one edge costs about one edge's
          share of the sync path's training FLOPs; the (N_hot,) bool mask
          of the N_hot bucket trains the whole buffer and commits the
          masked rows — every wave at max_staleness=0 (all edges) and
          every wave under a mesh, at the sync path's cost.
        * ``merge(g, flat, eff_weights)`` — staleness-weighted cloud merge
          (``flat_staleness_merge``; reduces to eq. 10 at the barrier).
        """
        a, b = self.schedule.a, self.schedule.b
        M = self.schedule.num_edges
        weights = self._hot_weights
        mesh = self.mesh
        w_total = float(jnp.sum(self._hot_weights))
        unravel, ravel = self._row_codec()
        wave = _departure_wave(clients.gd_local_steps(self.loss_fn, a,
                                                      self.lr),
                               unravel, ravel, b, self._hot_gids, M, mesh)

        def depart_cycle(flat, g, batches, rows):
            return wave(flat, g, batches, rows, weights)

        def merge(g, flat, eff_weights):
            with jax.named_scope("hfl.merge"):
                return aggregate.flat_staleness_merge(g, flat, eff_weights,
                                                      w_total, mesh=mesh)

        return (jax.jit(depart_cycle, donate_argnums=0), jax.jit(merge))

    def _build_faulty_ops(self):
        """Fault-aware twins of the hot-loop closures (``fault_model=``).

        Kept SEPARATE from ``_cloud_round`` / ``_depart_cycle`` so the
        fault-free paths stay byte-identical (the parity guarantees of the
        sync/async/stochastic layers never route through this code):

        * ``faulty_cloud_round(flat, batches, w_edge, w_cloud)`` — one
          sync round where the b edge aggregations use the round's
          survivor-renormalized weights and the cloud mean reweights to
          the edges that actually delivered (a dead cohort's zero rows
          carry zero cloud weight — the global model stays the unbiased
          mean of survivors).
        * ``faulty_depart(flat, g, batches, rows, w_edge)`` — the async
          departure wave (``depart_cycle``'s row buckets) with the wave's
          survivor weights; non-departing groups' weights are irrelevant
          (their rows are neither gathered nor committed).

        Both take the weights as RUNTIME arguments: one compilation
        serves every fault pattern.
        """
        a, b = self.schedule.a, self.schedule.b
        M = self.schedule.num_edges
        group_ids = self._hot_gids
        mesh = self.mesh
        unravel, ravel = self._row_codec()
        local_gd = clients.gd_local_steps(self.loss_fn, a, self.lr)
        wave = _departure_wave(local_gd, unravel, ravel, b, group_ids, M,
                               mesh)
        cycle = _edge_cycle(local_gd, unravel, ravel, b, M, mesh)

        def faulty_cloud_round(flat, batches, w_edge, w_cloud):
            flat = cycle(flat, batches, w_edge, group_ids)
            with jax.named_scope("hfl.cloud_agg"):
                return aggregate.flat_cloud_aggregate(flat, w_cloud,
                                                      mesh=mesh)

        def faulty_depart(flat, g, batches, rows, w_edge):
            return wave(flat, g, batches, rows, w_edge)

        return (jax.jit(faulty_cloud_round, donate_argnums=0),
                jax.jit(faulty_depart, donate_argnums=0))

    def _row_codec(self):
        """``(unravel, ravel)`` between the flat rows and stacked pytrees."""
        if self._slayout is not None:
            return self._slayout.unravel_padded, self._slayout.ravel_padded
        return self._layout.unravel, self._layout.ravel

    def _fault_survivor_matrix(self, fc):
        """``fc.survivors`` mapped onto the HOT row layout."""
        return self.hot_survivor_rows(fc.survivors)

    def hot_survivor_rows(self, survivors) -> np.ndarray:
        """Map ``(C, N)`` bool per-UE survivor masks (original UE order,
        e.g. ``faults.FaultyCycles.survivors`` rows) onto the HOT row
        layout: (C, N_hot) bool.  Padding rows are row-0 copies, but they
        carry zero weight everywhere it matters.  Public so an external
        driver (the always-on service) can compose per-cycle fault
        survivors with its own shed/sampling masks on hot rows."""
        surv = np.asarray(survivors)
        if self._slayout is not None:
            surv = np.asarray(self._slayout.pad_rows(
                jnp.asarray(surv.T))).T
        return surv

    def _participation_matrix(self, num_rounds: int) -> np.ndarray:
        """(num_rounds, N) bool cohort masks on the ORIGINAL row order —
        one batched keyed draw (``sampler.sample_rounds``); this is what
        the CLOCK consumes (delay models index original UEs)."""
        return self.sampler.sample_rounds(
            self.sample_seed, np.asarray(self.weights),
            np.asarray(self.group_ids), self.schedule.num_edges, num_rounds)

    def _participation_hot(self, part: np.ndarray) -> np.ndarray:
        """Map (R, N) masks onto the HOT row layout.  Uses ``pad_mask``
        (pad rows -> False), NOT ``pad_rows`` (row-0 copies) — a pad row
        must never look sampled."""
        if self._slayout is None:
            return part
        return np.asarray(self._slayout.pad_mask(part.T)).T

    def _fault_round_weights(self, ue_ok, base=None):
        """(w_edge, w_cloud) for one round/wave from the hot-row survivor
        mask: survivor-renormalized edge weights + cloud weights zeroing
        edges with no surviving mass.  ``base`` overrides the base
        measure (the service passes per-cycle IPW weights); the default
        is the run-static ``_hot_agg_weights`` (== ``_hot_weights``
        unless a non-uniform sampler is active)."""
        M = self.schedule.num_edges
        if base is None:
            base = self._hot_agg_weights
        base = jnp.asarray(base, jnp.float32)
        w_edge = aggregate.survivor_weights(
            base, jnp.asarray(ue_ok), self._hot_gids, M)
        mass = jax.ops.segment_sum(
            base * jnp.asarray(ue_ok, jnp.float32),
            self._hot_gids, num_segments=M)
        w_cloud = jnp.asarray(self._hot_weights) * (mass > 0)[self._hot_gids]
        return w_edge, w_cloud

    def global_params(self):
        """The cloud model: weighted mean over UE replicas (eq. 10)."""
        w = self._hot_weights / jnp.sum(self._hot_weights)
        mean = aggregate.weighted_sum(w, self._flat)     # (f_padded,)
        return self._layout.unravel_single(mean[:self._layout.total])

    def _weighted_ops(self):
        """Jitted runtime-weight twins (``_build_faulty_ops``), built on
        first use — fault runs need them, and so does the service's
        overload-shed departure path (without any ``fault_model``)."""
        if self._weighted_ops_cache is None:
            self._weighted_ops_cache = self._build_faulty_ops()
            (self._faulty_cloud_round,
             self._faulty_depart) = self._weighted_ops_cache
        return self._weighted_ops_cache

    def shard_rows(self) -> dict:
        """What the hot layout puts on each device, keyed by device id:
        ``rows`` (real UE rows held), ``pad_rows`` (zero-weight padding
        rows held), ``flat_bytes`` and ``batch_bytes`` (resident bytes of
        the flat buffer's and the batches' shards on it).  One chip holds
        every row and no padding."""
        perm = (self._slayout.perm if self._slayout is not None
                else np.arange(self._flat.shape[0]))
        out = {}
        for s in self._flat.addressable_shards:
            held = perm[s.index[0]]
            real = int(np.sum(held >= 0))
            out[s.device.id] = {"rows": real, "pad_rows": held.size - real,
                                "flat_bytes": int(s.data.nbytes),
                                "batch_bytes": 0}
        for leaf in jax.tree.leaves(self._hot_batches):
            for s in leaf.addressable_shards:
                out[s.device.id]["batch_bytes"] += int(s.data.nbytes)
        return out

    def op_scopes(self) -> dict:
        """``{program: {instruction: scope}}`` for every program this
        simulator has built, keyed as the profiler names a program's runs
        (``jit_cloud_round``, ``jit_depart_cycle``, ...): each program is
        lowered with the live buffers and compiled, and its instructions
        are mapped to their ``hfl.*`` named scope
        (``roofline.hlo_cost.instruction_scopes``).  A wave twin's map is
        the union over the programs of its bucket ladder, which share its
        name.  Instructions in no scope are left out.  Compiles; call it
        outside any timed window."""
        from repro.roofline.hlo_cost import instruction_scopes

        n = int(self._hot_gids.shape[0])
        flat, batches = self._flat, self._hot_batches
        g = self.place_cloud_vector(np.zeros(flat.shape[1], np.float32))
        programs = [(self._cloud_round, (flat, batches))]
        if self.mode == "async":
            eff = jnp.asarray(np.zeros(n), jnp.float32)
            programs.append((self._merge, (g, flat, eff)))
        if self._weighted_ops_cache is not None:
            w_edge, w_cloud = self._fault_round_weights(np.ones(n, bool))
            programs.append((self._faulty_cloud_round,
                             (flat, batches, w_edge, w_cloud)))
        compiled = [fn.lower(*args).compile() for fn, args in programs]
        twins = ([self._depart_cycle] if self.mode == "async" else []) + (
            [self._faulty_depart] if self._weighted_ops_cache else [])
        for twin in twins:
            compiled += self._wave_programs(twin).values()
        out = {}
        for prog in compiled:
            hlo = prog.as_text()
            name = hlo.split(None, 2)[1].rstrip(",")     # "HloModule <name>,"
            out.setdefault(name, {}).update(instruction_scopes(hlo))
        return out

    def _wave_programs(self, twin) -> dict:
        """``{bucket rows: program}`` of the wave twin ``twin``.  The
        twin's first call compiles every bucket of the ladder, so no later
        wave compiles; the N_hot bucket is the twin with the (N_hot,) bool
        mask.  Under a mesh the one bucket is the jitted twin itself."""
        progs = self._wave_exec.get(twin)
        if progs is not None:
            return progs
        n, f = self._flat.shape
        if self.mesh is not None:
            progs = {n: twin}
        else:
            def spec(x):
                return jax.ShapeDtypeStruct(x.shape, x.dtype)
            head = (spec(self._flat), jax.ShapeDtypeStruct((f,), jnp.float32),
                    jax.tree.map(spec, self._hot_batches))
            tail = (() if twin is self._depart_cycle
                    else (jax.ShapeDtypeStruct((n,), jnp.float32),))
            progs = {}
            for size in self._wave_ladder:
                rows = (jax.ShapeDtypeStruct((n,), jnp.bool_) if size == n
                        else jax.ShapeDtypeStruct((size,), jnp.int32))
                progs[size] = twin.lower(*head, rows, *tail).compile()
        self._wave_exec[twin] = progs
        return progs

    # ------------------------------------------------------------------
    # Public replay hooks (mode='async') — the event-replay primitives
    # `_run_async` is built from, exposed so an external driver (the
    # always-on service, repro.launch.service) can advance the SAME model
    # state one event at a time, checkpoint it, and resume.
    # ------------------------------------------------------------------

    def cloud_vector(self):
        """(F_hot,) f32 cloud model vector: the weighted mean of the
        current flat buffer (sharded to the column spec under a mesh)."""
        w_np = np.asarray(self._hot_weights)
        g = aggregate.weighted_sum(
            jnp.asarray(w_np / w_np.sum(), jnp.float32), self._flat)
        return self.place_cloud_vector(g)

    def place_cloud_vector(self, g):
        """Device-place a cloud vector consistently with the hot layout."""
        g = jnp.asarray(g, jnp.float32)
        if self.mesh is not None:
            g = jax.device_put(
                g, NamedSharding(self.mesh, self._slayout.col_spec))
        return g

    def replay_departure(self, g, mask, ue_ok=None, agg_weights=None) -> None:
        """One departure wave: re-seed the masked rows from ``g``, run
        their b-iteration edge cycle and commit them into the flat buffer.

        ``mask`` is an (N_hot,) bool over hot rows (the departing
        cohorts).  With ``ue_ok`` (an (N_hot,) bool of per-UE
        participation — fault survivors, or the service's overload shed)
        the wave aggregates under mass-preserving survivor-renormalized
        weights (``aggregate.survivor_weights``); rows of excluded UEs
        still train but carry zero weight, keeping eq. 6 the unbiased
        mean of the participants.  ``agg_weights`` overrides the base
        measure of that renormalization (per-cycle IPW weights from the
        service's sampler).

        The wave trains the smallest bucket of ``_wave_ladder`` that holds
        the masked rows, gathered; the N_hot bucket (a full-fleet wave, a
        mask that is not a union of whole cohorts, or any mesh run) trains
        every row and commits the masked ones.  ``wave_rows_trained``
        counts the bucket rows, ``wave_rows_kept`` the committed rows and
        ``wave_bucket_runs`` the waves per bucket, over this simulator's
        life.
        """
        if self.mode != "async":
            raise RuntimeError("replay_departure requires mode='async'")
        mask = np.asarray(mask, bool)
        idx = np.flatnonzero(mask)
        n = mask.size
        gids = np.asarray(self._hot_gids)
        whole = idx.size == np.isin(gids, gids[idx]).sum()
        bucket = next(s for s in self._wave_ladder
                      if s >= idx.size and (whole or s == n))
        self.wave_rows_trained += bucket
        self.wave_rows_kept += idx.size
        self.wave_bucket_runs[bucket] = self.wave_bucket_runs.get(bucket,
                                                                  0) + 1
        if bucket == n:
            rows = jnp.asarray(mask)
        else:
            rows = np.full(bucket, n, np.int32)
            rows[:idx.size] = idx
        if ue_ok is not None:
            w_edge, _ = self._fault_round_weights(np.asarray(ue_ok),
                                                  base=agg_weights)
            _, twin = self._weighted_ops()
            self._flat = self._wave_programs(twin)[bucket](
                self._flat, g, self._hot_batches, rows, w_edge)
        else:
            self._flat = self._wave_programs(self._depart_cycle)[bucket](
                self._flat, g, self._hot_batches, rows)

    def replay_merge(self, g, decay: np.ndarray):
        """Staleness-weighted cloud merge of the arrived edges.

        ``decay`` is (M,) float64 per-edge effective decay
        (``staleness_decay ** lag`` for arrived edges, 0 elsewhere);
        returns the updated cloud vector (one psum under a mesh).
        """
        if self.mode != "async":
            raise RuntimeError("replay_merge requires mode='async'")
        gids = np.asarray(self._hot_gids)
        eff = jnp.asarray(np.asarray(self._hot_weights) *
                          np.asarray(decay)[gids], jnp.float32)
        return self._merge(g, self._flat, eff)

    def edge_mean_row(self, m: int):
        """(F_hot,) f32 — edge ``m``'s model right after its cycle's
        eq. 6 aggregation (every cohort row holds the edge mean, so one
        member row IS the edge contribution a cloud merge consumes)."""
        idx = int(np.flatnonzero(np.asarray(self._hot_gids) == int(m))[0])
        return self._flat[idx]

    def edge_mass(self, m: int) -> float:
        """Total aggregation weight of edge ``m``'s cohort (float64)."""
        w = np.asarray(self._hot_weights, np.float64)
        return float(w[np.asarray(self._hot_gids) == int(m)].sum())

    def hot_rows(self, idx) -> np.ndarray:
        """Host copy of the given hot flat-buffer rows: (len(idx), F_hot)
        f32.  The streaming merge path (``repro.launch.service``) pulls
        one cohort CHUNK at a time through this, so the control plane
        never materializes more than a chunk of the buffer at once
        (``flat_state()`` is the all-rows checkpoint path)."""
        idx = np.asarray(idx, np.int64)
        return np.asarray(jax.device_get(self._flat[jnp.asarray(idx)]),
                          np.float32)

    def global_from_vector(self, g):
        """Unravel a cloud vector into the global parameter pytree."""
        return self._layout.unravel_single(
            jnp.asarray(g)[:self._layout.total])

    def flat_state(self) -> np.ndarray:
        """Host copy of the hot flat buffer (checkpoint payload)."""
        return np.asarray(jax.device_get(self._flat))

    def set_flat_state(self, flat: np.ndarray) -> None:
        """Restore the hot flat buffer from a host array (resume path)."""
        flat = jnp.asarray(flat, jnp.float32)
        if flat.shape != self._flat.shape:
            raise ValueError(f"flat buffer shape {flat.shape} does not "
                             f"match this simulator's hot layout "
                             f"{self._flat.shape} — resume with the same "
                             f"schedule/mesh the checkpoint was taken on")
        if self._slayout is not None:
            flat = jax.device_put(
                flat, NamedSharding(self.mesh, self._slayout.spec))
        self._flat = flat

    # ------------------------------------------------------------------

    def _evaluate(self, log: _EvalLog, t: float, cloud_params,
                  test_batch: dict) -> None:
        """One evaluation point, inside the host span ``hfl.eval``: the
        cloud model (``cloud_params()``) on the test batch and its
        weighted train loss over every UE, read to the host in that
        order (accuracy, test loss, train loss) and logged at clock
        ``t``."""
        with jax.profiler.TraceAnnotation("hfl.eval"):
            gp = cloud_params()
            loss, mets = self.loss_fn(gp, test_batch)
            trl = self._train_loss(gp, self._hot_batches, self._hot_weights)
            log.times.append(t)
            log.accs.append(float(mets.get("acc", jnp.nan)))
            log.tlosses.append(float(loss))
            log.trlosses.append(float(trl))

    def run(self, test_batch: dict, rounds: Optional[int] = None,
            eval_every: int = 1, verbose: bool = False) -> SimResult:
        """Execute ``rounds`` cloud rounds (sync) or the equivalent async
        delivery quota (``rounds * M_active`` edge merges, mode='async';
        ``eval_every`` then counts cloud-update events)."""
        if self.mode == "async":
            return self._run_async(test_batch, rounds, eval_every, verbose)
        sched = self.schedule
        rounds = rounds or sched.rounds
        if self.fault_model is not None:
            return self._run_sync_faulty(test_batch, rounds, eval_every,
                                         verbose)
        if self.sampler is not None:
            return self._run_sync_sampled(test_batch, rounds, eval_every,
                                          verbose)
        if self.delay_model is not None:
            # One batched draw for the whole run: round r costs the max
            # over edges of that round's cycle draw (stochastic eq. 34).
            draws = self.delay_model.cycle_times(
                self.delay_seed, sched.problem, sched.assoc, sched.a,
                sched.b, rounds)
            round_times = np.asarray(draws).max(axis=1)
        else:
            round_times = np.full(rounds, sched.cloud_round_time)  # eq. (34)
        log = _EvalLog()
        clock = 0.0
        test_batch = jax.tree.map(jnp.asarray, test_batch)
        for r in range(rounds):
            with jax.profiler.TraceAnnotation("hfl.round"):
                self._flat = self._cloud_round(self._flat, self._hot_batches)
                clock += float(round_times[r])
                if (r + 1) % eval_every == 0 or r == rounds - 1:
                    self._evaluate(log, clock, self.global_params, test_batch)
                    if verbose:
                        print(f"round {r+1:3d}/{rounds}  t={clock:9.2f}s  "
                              f"{log.last()}")
        return log.result(sched, self.global_params())

    def _run_sync_sampled(self, test_batch: dict, rounds: int,
                          eval_every: int, verbose: bool) -> SimResult:
        """Synchronous rounds under partial participation (``sampler=``).

        One batched keyed draw yields every round's cohort.  Round ``r``

        * COSTS the masked stochastic eq. 34: each edge's tau is the
          member max over round ``r``'s PARTICIPANTS (the delay engine's
          ``participation=`` threading; ``DeterministicDelays`` when no
          ``delay_model`` was given), so shrinking the cohort shortens
          the barrier;
        * AGGREGATES only the cohort, under per-edge mass-preserving
          reweighting (``_fault_round_weights`` — the same
          ``survivor_weights`` renormalization fault rounds use), so the
          cloud trajectory stays an unbiased estimate of the
          full-participation one.
        """
        sched = self.schedule
        part = self._participation_matrix(rounds)
        part_hot = self._participation_hot(part)
        if sched.problem is not None:
            dm = self.delay_model or stochastic.DeterministicDelays()
            draws = dm.cycle_times(self.delay_seed, sched.problem,
                                   sched.assoc, sched.a, sched.b, rounds,
                                   participation=part)
            round_times = np.asarray(draws).max(axis=1)
        else:
            # No problem attached: the constant eq. 34 bound is all we
            # have (full-fleet pacing — conservative).
            round_times = np.full(rounds, sched.cloud_round_time)

        log = _EvalLog()
        clock = 0.0
        test_batch = jax.tree.map(jnp.asarray, test_batch)
        for r in range(rounds):
            with jax.profiler.TraceAnnotation("hfl.round"):
                w_edge, w_cloud = self._fault_round_weights(part_hot[r])
                self._flat = self._faulty_cloud_round(
                    self._flat, self._hot_batches, w_edge, w_cloud)
                clock += float(round_times[r])
                if (r + 1) % eval_every == 0 or r == rounds - 1:
                    self._evaluate(log, clock, self.global_params, test_batch)
                    if verbose:
                        print(f"round {r+1:3d}/{rounds}  t={clock:9.2f}s  "
                              f"{log.last()}  cohort={int(part[r].sum())}")
        return log.result(sched, self.global_params())

    def _run_sync_faulty(self, test_batch: dict, rounds: int,
                         eval_every: int, verbose: bool) -> SimResult:
        """Synchronous rounds under an injected fault process.

        One keyed batched draw (``faults.faulty_cycle_stats``) prices the
        whole run; round ``r`` then

        * COSTS the policy's makespan — wait-for-all pays every straggler
          (comeback waits, unbounded retries, outage stalls) so the round
          is ``max_m`` of the stalled cycle times; deadline policies cut
          at ``D_m`` and skip edges inside an outage window;
        * AGGREGATES only round ``r``'s survivors: edge means use
          survivor-renormalized weights, the cloud mean zeroes edges with
          no delivered mass (down, or fully-dropped cohort).
        """
        sched = self.schedule
        policy = self.fault_policy
        fc = faults.faulty_cycle_stats(
            self.fault_model, policy, self.fault_seed, sched.problem,
            sched.assoc, sched.a, sched.b, rounds,
            delay_model=self.delay_model)
        ct = np.asarray(fc.cycle_times)
        down = np.asarray(fc.down)
        if policy.name == faults.WAIT_FOR_ALL:
            round_times = (ct + np.asarray(fc.stall)).max(axis=1)
        else:
            round_times = np.where(down, 0.0, ct).max(axis=1)
        surv = self._fault_survivor_matrix(fc)
        if self.sampler is not None:
            # Faults x sampling: AND the masks, renormalize ONCE inside
            # `_fault_round_weights` — no double discount.  The clock
            # keeps the policy's full-fleet pricing (deadlines are set
            # before the cohort is known).
            surv = surv & self._participation_hot(
                self._participation_matrix(rounds))
        gids = np.asarray(self._hot_gids)

        log = _EvalLog()
        clock = 0.0
        test_batch = jax.tree.map(jnp.asarray, test_batch)
        for r in range(rounds):
            with jax.profiler.TraceAnnotation("hfl.round"):
                ue_ok = surv[r] & ~down[r][gids]
                if ue_ok.any():
                    w_edge, w_cloud = self._fault_round_weights(ue_ok)
                    self._flat = self._faulty_cloud_round(
                        self._flat, self._hot_batches, w_edge, w_cloud)
                # else: nothing delivered — the round is wasted wall-clock,
                # the model stays put (no division by a zero weight mass).
                clock += float(round_times[r])
                if (r + 1) % eval_every == 0 or r == rounds - 1:
                    self._evaluate(log, clock, self.global_params, test_batch)
                    if verbose:
                        print(f"round {r+1:3d}/{rounds}  t={clock:9.2f}s  "
                              f"{log.last()}  survivors={int(ue_ok.sum())}")
        return log.result(sched, self.global_params())

    def _run_async(self, test_batch: dict, rounds: Optional[int],
                   eval_every: int, verbose: bool) -> SimResult:
        """Replay the event-driven async timeline (see module docstring).

        The clock comes from ``core.delay.async_completion`` (per-edge
        cycles ``b tau_m + t_mc``, SSP-gated); the model state is advanced
        by replaying its trace: departure waves re-seed + cycle the
        departing edges' rows in place, every cloud update applies one
        staleness-weighted merge and is an eval point (``eval_every``
        counts updates; at ``max_staleness=0`` updates == sync rounds).
        """
        sched = self.schedule
        if sched.problem is None:
            raise ValueError("mode='async' needs schedule.problem to derive "
                             "per-edge cycle times (eqs. 8/33)")
        rounds = rounds or sched.rounds
        part = part_hot = None
        if self.sampler is not None:
            # One cohort per CYCLE, pre-drawn for the longest trace the
            # gate allows (cycles beyond that clamp to the last row, the
            # same clamp the fault matrix uses).
            part = self._participation_matrix(rounds + self.max_staleness)
            part_hot = self._participation_hot(part)
        if self.fault_model is not None:
            # Fault pricing stays full-fleet (the policy cannot know the
            # cohort when it sets deadlines/retries) — only the MODEL
            # masks compose below.
            stats = delay.faulty_async_completion(
                sched.problem, sched.assoc, sched.a, sched.b, rounds=rounds,
                max_staleness=self.max_staleness,
                fault_model=self.fault_model, policy=self.fault_policy,
                delay_model=self.delay_model, key=self.fault_seed)
            surv = self._fault_survivor_matrix(stats["cycle_stats"])
            if part_hot is not None:
                surv = _combine_masks(surv, part_hot)
        else:
            stats = delay.async_completion(
                sched.problem, sched.assoc, sched.a, sched.b, rounds=rounds,
                max_staleness=self.max_staleness,
                delay_model=self.delay_model, key=self.delay_seed,
                participation=part)
            # The sampled cohort rides the existing survivor machinery:
            # departures stamp the cycle's mask, merges gate on delivered
            # mass, replay reweights via `survivor_weights`.
            surv = part_hot
        tl = stats["timeline"]
        active = np.asarray(stats["active_edges"])
        gids = np.asarray(self._hot_gids)
        weights_np = np.asarray(self._hot_weights)
        test_batch = jax.tree.map(jnp.asarray, test_batch)

        # Cloud model vector: weighted mean of the current buffer (== every
        # row right after construction or a previous run).
        g = self.cloud_vector()

        num_updates = len(tl.updates)
        pending = np.zeros(gids.shape[0], dtype=bool)
        # Per-hot-row survivor flags of each row's LAST departed cycle
        # (fault runs): departures stamp them, the flush renormalizes the
        # wave's edge weights to them, merges zero out dead cohorts.
        pending_ok = np.ones(gids.shape[0], dtype=bool)
        last_cycle = np.zeros(sched.num_edges, dtype=np.int64)
        log = _EvalLog()
        updates_seen = 0
        for kind, ev in tl.trace:
            if kind == "depart":
                cohort = gids == int(active[ev.edge])
                pending |= cohort
                if surv is not None:
                    row = min(ev.cycle - 1, surv.shape[0] - 1)
                    pending_ok[cohort] = surv[row, cohort]
                    last_cycle[int(active[ev.edge])] = row
                continue
            if kind in ("fail", "repair"):
                continue         # clock annotations only (cycle voided in
                                 # the trace: its delivery never appears)
            if pending.any():
                # jnp.asarray may alias the numpy buffer (zero-copy on CPU)
                # and dispatch is async, so hand over the buffer and start a
                # fresh one instead of mutating it in place.
                ue_ok = (np.where(pending, pending_ok, True)
                         if surv is not None else None)
                self.replay_departure(g, pending, ue_ok=ue_ok)
                pending = np.zeros_like(pending)
            decay = np.zeros(sched.num_edges)
            for e, _, s in ev.merges:
                m_full = int(active[e])
                ok = 1.0
                if surv is not None:
                    cohort = gids == m_full
                    mass = (weights_np[cohort] *
                            surv[last_cycle[m_full], cohort]).sum()
                    ok = float(mass > 0)  # dead cohort: zero rows, no merge
                decay[m_full] = ok * self.staleness_decay ** s
            g = self.replay_merge(g, decay)
            updates_seen += 1
            if updates_seen % eval_every == 0 or updates_seen == num_updates:
                self._evaluate(log, ev.t, lambda: self.global_from_vector(g),
                               test_batch)
                if verbose:
                    print(f"update {updates_seen:4d}/{num_updates}  "
                          f"t={ev.t:9.2f}s  {log.last()}")
        # Leave the buffer consistent (all rows = cloud model) so
        # ``global_params``/repeated runs see the merged state.
        self._flat = jnp.zeros_like(self._flat) + g[None, :]
        return log.result(sched, self.global_params(), timeline=tl)
