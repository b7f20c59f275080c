"""Bring-up smoke: the paper's §V-A HFL deployment end to end on a TPU.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # the sharded mesh path only

One process drives the chip; everything is made from ``--seed`` and
nothing is read from outside the checkout.  Phases (one chip):

* device  — refuse to run unless JAX's first device is a TPU;
* plan    — ``HFLProblem(num_edges=5, num_ues=100)`` and ``plan()``;
* sync    — ``HFLSimulator`` with the published LeNet (44,426 params)
  on synthetic MNIST for the plan's R cloud rounds: compile seconds
  apart from round seconds, finite losses, and the Pallas aggregation
  (``tpu_custom_call``) present in the compiled cloud round;
* ref     — round 1 of that run against a plain float32 reference
  (a per-UE loop of ``jax.grad`` steps, eq. 6 and eq. 10 in numpy), and
  every aggregation kernel against its ``kernels/ref.py`` oracle on the
  real buffer;
* async   — the same federation with ``mode="async"`` for R*M merges;
* service — ``HFLService`` over the LeNet async simulator: checkpoints,
  ``restore_latest()`` into a fresh service, parity with the
  uninterrupted run (in-process, no kill).

``--chips 4`` runs one sync cloud round on a 2x2 ('data', 'model') mesh
(``ShardedFlatLayout``, collective-free edge aggregation, one-psum cloud
merge) and the same round on one device, and prints their parity and
the bytes in use on every device.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``; a failed
phase exits non-zero without it.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))

NUM_EDGES, NUM_UES = 5, 100       # the paper's §V-A deployment
LR = 0.05                          # examples/paper_experiments.py LeNet
MAX_STALENESS = 2
SERVICE_EVENTS = 6                 # cloud events per service run
SERVICE_CKPT_EVERY = 2

# Tolerances, each with its reason.  A round's error is measured as
# ||got - want|| / ||want - init||: the L2 distance between two cloud
# models after one round, as a share of that round's update.
#  KERNEL_RTOL: each kernel output is a weighted mean of <= N = 100 fp32
#   rows summed in another order than the oracle's; the reassociation
#   bound is about N * 2**-24 ~ 6e-6 of max|x|.  A bf16 pass would be
#   ~2**-9 ~ 2e-3, so 2e-5 separates the two.
#  ROUND_TOL_FP32: round 1 of the simulator at "highest" precision
#   against the reference.  LeNet's max-pooling makes the 136 GD steps
#   per UE discontinuous in their inputs: in a CPU run at full width
#   (N=4, a=17, b=8) a 1e-7 relative change of the init moved the round
#   by 1.7e-3 of its update, while a+1 local steps moved it by 1.4e-2 and
#   a reversed edge map by 4.3e-2.
#  ROUND_TOL_DEFAULT: the same round at the TPU's default precision (one
#   bf16 pass per f32 matmul and conv, ~2**-9 per op, at every step): a
#   bound on the drift, a tenth of the round's update.
#  MESH_TOL: the 2x2 mesh runs the same default-precision round with its
#   rows in shards and one psum in eq. 10; the reassociation meets the
#   same discontinuities, so it is bounded like the default round.
#  SERVICE_ATOL: the resumed service re-executes the same programs from
#   the checkpointed state (float32 re-execution tolerance).
KERNEL_RTOL = 2e-5
ROUND_TOL_FP32 = 1e-2
ROUND_TOL_DEFAULT = 1e-1
MESH_TOL = 1e-1
SERVICE_ATOL = 1e-6


class SmokeFailure(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def require_tpu(chips: int):
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found; JAX's first device is "
                 f"{d.platform} ({d.device_kind}), {len(devs)} device(s). "
                 f"Nothing was run.")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"found {len(devs)}. Nothing was run.")
    return devs


# ---------------------------------------------------------------------------
# The federation: plan, data, model
# ---------------------------------------------------------------------------


class Federation:
    """The §V-A problem, its plan, and the LeNet/synthetic-MNIST data."""

    def __init__(self, seed: int, *, num_edges: int = NUM_EDGES,
                 num_ues: int = NUM_UES, model_cfg=None):
        import jax
        import numpy as np

        from repro.configs import lenet_mnist
        from repro.core import schedule
        from repro.core.problem import HFLProblem
        from repro.data import partition, synthetic
        from repro.models import lenet

        self.seed = seed
        self.model_cfg = model_cfg or lenet_mnist.CONFIG
        self.problem = HFLProblem(num_edges=num_edges, num_ues=num_ues,
                                  seed=seed)
        self.schedule = schedule.plan(self.problem)
        n_train = int(self.problem.samples.sum())
        train, self.test = synthetic.synthetic_mnist(seed=seed,
                                                     n_train=n_train)
        rng = np.random.default_rng(seed)
        parts = partition.size_partition(rng, n_train,
                                         self.problem.samples.astype(int))
        self.ue_data = [{k: train[k][ix] for k in train} for ix in parts]
        self.init = lenet.lenet_init(jax.random.PRNGKey(seed),
                                     self.model_cfg)
        self.loss_fn = lenet.lenet_loss
        self.num_params = sum(int(x.size) for x in jax.tree.leaves(self.init))

    def simulator(self, **kw):
        from repro.fl.sim import HFLSimulator
        return HFLSimulator(self.schedule, self.loss_fn, self.init,
                            self.ue_data, lr=LR, seed=self.seed, **kw)


def phase_plan(fed: Federation) -> dict:
    import numpy as np
    s = fed.schedule
    per_edge = np.bincount(s.assoc.argmax(1), minlength=s.num_edges)
    out = dict(N=s.num_ues, M=s.num_edges, a=s.a, b=s.b, R=s.rounds,
               ues_per_edge=per_edge.tolist(),
               sum_D=float(fed.problem.samples.sum()),
               params=fed.num_params, model=fed.model_cfg.name)
    log(f"plan: {out}")
    check(out["N"] == fed.problem.num_ues and out["M"] ==
          fed.problem.num_edges, "plan does not cover the deployment")
    return out


# ---------------------------------------------------------------------------
# Plain float32 reference of one sync cloud round (Alg. 1, eqs. 6 and 10)
# ---------------------------------------------------------------------------


def reference_round(fed: Federation, batches, weights, group_ids,
                    capture=None):
    """One cloud round written out plainly: per UE, ``a`` steps of full-
    batch GD with ``jax.grad``; per edge round, the eq. 6 weighted mean
    of each edge's UEs; at the end, the eq. 10 weighted mean.  Means are
    accumulated in float64 on the host and rounded to float32.  Runs at
    ``highest`` matmul precision.  ``capture`` receives the (N, F) host
    buffer of the first edge round's local models (distinct rows)."""
    import jax
    import jax.flatten_util
    import numpy as np

    a, b, lr = fed.schedule.a, fed.schedule.b, LR
    loss_fn = fed.loss_fn
    w = np.asarray(weights, np.float64)
    gids = np.asarray(group_ids)
    n = gids.shape[0]
    _, unravel = jax.flatten_util.ravel_pytree(fed.init)

    with jax.default_matmul_precision("highest"):
        @jax.jit
        def local(p, batch):
            def step(_, q):
                g = jax.grad(lambda z: loss_fn(z, batch)[0])(q)
                return jax.tree.map(lambda x, gg: x - lr * gg, q, g)
            return jax.lax.fori_loop(0, a, step, p)

        ue_batches = [jax.tree.map(lambda l: l[i], batches)
                      for i in range(n)]
        vec0 = np.asarray(jax.flatten_util.ravel_pytree(fed.init)[0],
                          np.float64)
        rows = np.broadcast_to(vec0, (n, vec0.size)).copy()
        for r in range(b):
            trained = []
            for i in range(n):
                p = local(unravel(rows[i].astype(np.float32)),
                          ue_batches[i])
                trained.append(np.asarray(
                    jax.flatten_util.ravel_pytree(p)[0], np.float64))
            rows = np.stack(trained)
            if r == 0 and capture is not None:
                capture.append(rows.astype(np.float32))
            for m in np.unique(gids):                          # eq. 6
                mem = gids == m
                rows[mem] = (w[mem, None] * rows[mem]).sum(0) / w[mem].sum()
        cloud = (w[:, None] * rows).sum(0) / w.sum()           # eq. 10
    return unravel(cloud.astype(np.float32))


def round_err(got, want, init):
    """(max abs err, ||got - want|| / ||want - init||) over all leaves:
    the second is the error as a share of the round's update."""
    import jax
    import numpy as np

    def flat(tree):
        return np.concatenate([np.ravel(np.asarray(x, np.float64))
                               for x in jax.tree.leaves(tree)])
    g, w, i = flat(got), flat(want), flat(init)
    return (float(np.abs(g - w).max()),
            float(np.linalg.norm(g - w) / np.linalg.norm(w - i)))


def kernel_checks(buf, weights, group_ids, num_groups: int) -> dict:
    """Every aggregation kernel on the real (N, F) buffer against its
    ``kernels/ref.py`` oracle."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    x = jnp.asarray(buf, jnp.float32)
    w = jnp.asarray(weights, jnp.float32)
    g = jnp.asarray(group_ids, jnp.int32)
    n, f = x.shape
    scale = float(np.abs(buf).max())
    blk_seg = ops.pick_agg_blk_f(n, num_groups, f)
    blk_one = ops.pick_agg_blk_f(n, 1, f)
    cases = {
        "hier_segment_aggregate": (
            ops.hier_segment_aggregate(x, w, g, num_groups=num_groups,
                                       blk_f=blk_seg),
            ref.hier_segment_aggregate_ref(x, w, g, num_groups)),
        "hier_segment_accumulate": (
            ops.hier_segment_accumulate(x, w, g, num_groups=num_groups,
                                        blk_f=blk_seg),
            ref.hier_segment_sum_ref(x, w, g, num_groups)),
        "hier_cloud_aggregate": (
            ops.hier_cloud_aggregate(x, w, blk_f=blk_one),
            ref.hier_bcast_aggregate_ref(x, w)),
        "hier_aggregate": (
            ops.hier_aggregate(x, w, blk_f=blk_one),
            ref.hier_aggregate_ref(x, w)),
    }
    out = {}
    for name, (got, want) in cases.items():
        got = np.asarray(got, np.float64)
        want = np.asarray(want, np.float64)
        # the segment sums scale with the edge's weight mass
        s = scale * (float(np.abs(np.asarray(w)).sum())
                     if name == "hier_segment_accumulate" else 1.0)
        err = float(np.abs(got - want).max())
        out[name] = dict(max_abs=err, max_rel=err / s)
        log(f"kernel {name}: max_abs={err!r} max_rel={err / s!r} "
            f"(tol rel {KERNEL_RTOL})")
        check(np.all(np.isfinite(got)), f"{name}: non-finite output")
        check(err / s <= KERNEL_RTOL, f"{name}: rel err {err / s} > "
              f"{KERNEL_RTOL}")
    return out


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def phase_sync(fed: Federation) -> dict:
    """The plan's R sync cloud rounds at the default precision."""
    import jax
    import numpy as np

    sim = fed.simulator()
    t0 = time.perf_counter()
    compiled = sim._cloud_round.lower(sim._flat, sim._hot_batches).compile()
    compile_s = time.perf_counter() - t0
    hlo = compiled.as_text()
    n_kernels = hlo.count("tpu_custom_call")
    log(f"sync: cloud round compiled in {compile_s!r} s; "
        f"tpu_custom_call sites in its HLO: {n_kernels}")
    check(n_kernels > 0, "the compiled cloud round holds no Pallas "
          "kernel (tpu_custom_call)")

    round_s, losses = [], []
    params = None
    for r in range(fed.schedule.rounds):
        t0 = time.perf_counter()
        res = sim.run(fed.test, rounds=1)
        jax.block_until_ready(sim._flat)
        round_s.append(time.perf_counter() - t0)
        loss, acc = float(res.test_loss[-1]), float(res.test_acc[-1])
        losses.append(dict(test_loss=loss, test_acc=acc,
                           train_loss=float(res.train_loss[-1])))
        log(f"sync: round {r + 1}/{fed.schedule.rounds} "
            f"wall={round_s[-1]!r} s test_loss={loss!r} test_acc={acc!r}")
        check(math.isfinite(loss) and math.isfinite(acc),
              f"round {r + 1}: non-finite loss/acc")
        if r == 0:
            params = res.final_params
    log(f"sync: first round {round_s[0]!r} s (the first call also "
        f"compiles the evaluation), steady rounds {round_s[1:]!r} s; "
        f"each round is one cloud round plus its evaluation")
    return dict(compile_s=compile_s, round_s=round_s, losses=losses,
                n_kernel_sites=n_kernels, round1_params=params, sim=sim)


def phase_ref(fed: Federation, sync: dict) -> dict:
    """Round 1 against the float32 reference, at the default precision
    (the run above) and at "highest" (a fresh simulator); the kernels
    against their oracles on the reference's first local models."""
    import jax
    import numpy as np

    sim = sync["sim"]
    capture = []
    t0 = time.perf_counter()
    want = reference_round(fed, sim.batches, sim.weights, sim.group_ids,
                           capture=capture)
    ref_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        fp32 = fed.simulator().run(fed.test, rounds=1).final_params
    fp32_s = time.perf_counter() - t0
    out = dict(ref_s=ref_s, fp32_round_s=fp32_s)
    for name, got, tol in (("highest", fp32, ROUND_TOL_FP32),
                           ("default", sync["round1_params"],
                            ROUND_TOL_DEFAULT)):
        abs_err, rel_err = round_err(got, want, fed.init)
        out[name] = dict(max_abs=abs_err, rel_update=rel_err)
        log(f"ref: sync round 1 at {name} precision vs float32 reference: "
            f"max_abs={abs_err!r} rel_to_update={rel_err!r} (tol {tol})")
        check(rel_err <= tol, f"sync round 1 ({name}) off the reference "
              f"by {rel_err} of its update > {tol}")
    log(f"ref: reference round {ref_s!r} s, highest-precision simulator "
        f"round {fp32_s!r} s (compiles included)")
    out["kernels"] = kernel_checks(
        capture[0], np.asarray(sim.weights), np.asarray(sim.group_ids),
        fed.schedule.num_edges)
    return out


def phase_async(fed: Federation) -> dict:
    sim = fed.simulator(mode="async", max_staleness=MAX_STALENESS)
    t0 = time.perf_counter()
    res = sim.run(fed.test, rounds=fed.schedule.rounds)
    wall = time.perf_counter() - t0
    merges = len(res.timeline.updates)
    loss, acc = float(res.test_loss[-1]), float(res.test_acc[-1])
    log(f"async: {merges} cloud updates ({fed.schedule.rounds} x "
        f"{fed.schedule.num_edges} merges), wall={wall!r} s "
        f"(compiles included), test_loss={loss!r} test_acc={acc!r}, "
        f"simulated makespan={float(res.times[-1])!r} s")
    check(all(math.isfinite(float(x)) for x in res.test_loss),
          "async: non-finite loss")
    return dict(updates=merges, wall_s=wall, test_loss=loss, test_acc=acc)


def phase_service(fed: Federation) -> dict:
    import numpy as np

    from repro.launch.service import HFLService, ServiceConfig

    def merges(svc):
        return [(round(r["t"], 9), r["edge"], r["cycle"])
                for r in svc.trace if r["kind"] == "merge"]

    with tempfile.TemporaryDirectory(prefix="hfl_ckpt_") as ckpt:
        cfg = ServiceConfig(max_staleness=MAX_STALENESS, ckpt_dir=ckpt,
                            ckpt_every=SERVICE_CKPT_EVERY)
        t0 = time.perf_counter()
        svc = HFLService(fed.simulator(mode="async",
                                       max_staleness=MAX_STALENESS), cfg)
        svc.run(SERVICE_EVENTS // 2)
        resumed = HFLService(fed.simulator(mode="async",
                                           max_staleness=MAX_STALENESS), cfg)
        path = resumed.restore_latest()
        check(path is not None, "service: no checkpoint to restore")
        resumed.run(SERVICE_EVENTS)
        svc.run(SERVICE_EVENTS)          # the uninterrupted run, continued
        wall = time.perf_counter() - t0
        err = float(np.abs(resumed.g - svc.g).max())
        same = merges(resumed) == merges(svc)
        s = svc.summary()
        log(f"service: {SERVICE_EVENTS} events, ckpt every "
            f"{SERVICE_CKPT_EVERY}, restored {os.path.basename(path)}; "
            f"model_err={err!r} merge_trace_match={same} "
            f"merges={s['applied']} wall={wall!r} s (compiles included)")
        check(same, "service: resumed merge trace differs")
        check(err <= SERVICE_ATOL, f"service: model_err {err} > "
              f"{SERVICE_ATOL}")
        check(np.all(np.isfinite(svc.g)), "service: non-finite model")
    return dict(model_err=err, merges=s["applied"], wall_s=wall)


def phase_mesh(fed: Federation, devices) -> dict:
    """One sync cloud round on a 2x2 ('data', 'model') mesh against the
    same round on one device."""
    import jax

    from repro.launch.mesh import make_agg_mesh

    mesh = make_agg_mesh(2, 2)
    t0 = time.perf_counter()
    sharded = fed.simulator(mesh=mesh)
    res_m = sharded.run(fed.test, rounds=1)
    jax.block_until_ready(sharded._flat)
    mesh_s = time.perf_counter() - t0
    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in devices[:4]]
    sl = sharded._slayout
    log(f"mesh: 2x2 round wall={mesh_s!r} s (compile included), "
        f"test_loss={float(res_m.test_loss[-1])!r}; flat slab per device "
        f"{sl.per_device_bytes()} B ({sl.n_padded} rows x {sl.f_padded} "
        f"cols padded)")
    log(f"mesh: bytes_in_use per device {in_use}")
    del sharded

    t0 = time.perf_counter()
    single = fed.simulator()
    res_s = single.run(fed.test, rounds=1)
    single_s = time.perf_counter() - t0
    abs_err, rel_err = round_err(res_m.final_params, res_s.final_params,
                                 fed.init)
    log(f"mesh: single-device round wall={single_s!r} s; sharded vs "
        f"single max_abs={abs_err!r} rel_to_update={rel_err!r} "
        f"(tol {MESH_TOL})")
    check(math.isfinite(float(res_m.test_loss[-1])), "mesh: non-finite loss")
    check(rel_err <= MESH_TOL, f"mesh: sharded round off the single-"
          f"device round by {rel_err} of its update > {MESH_TOL}")
    return dict(abs_err=abs_err, rel_err=rel_err, bytes_in_use=in_use)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded mesh path and its "
                         "single-device comparison")
    args = ap.parse_args(argv)

    devices = require_tpu(args.chips)
    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    import jax
    d = devices[0]
    log(f"device: {d.platform} {d.device_kind} x{len(devices)}, "
        f"jax {jax.__version__}, compile cache {enable_compile_cache()}")

    t_start = time.perf_counter()
    fed = Federation(args.seed)
    phase_plan(fed)
    if args.chips == 4:
        phases = [("mesh", lambda: phase_mesh(fed, devices))]
    else:
        state = {}
        phases = [
            ("sync", lambda: state.setdefault("sync", phase_sync(fed))),
            ("ref", lambda: phase_ref(fed, state["sync"])),
            ("async", lambda: phase_async(fed)),
            ("service", lambda: phase_service(fed)),
        ]
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn()
        except Exception:
            traceback.print_exc()
            log(f"FAIL in phase {name}")
            failed.append(name)
        log(f"phase {name} ended after {time.perf_counter() - t0!r} s")
        if name == "ref":
            state.pop("sync", None)      # free the sync simulator
    if failed:
        log(f"failed phases: {failed}")
        return 1
    log(f"all phases passed in {time.perf_counter() - t_start!r} s")
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
