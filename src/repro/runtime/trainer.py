"""Fault-tolerant trainer: LO|FA|MO watchdogs + checkpoint/restart +
elastic re-mesh + straggler detection.

Two communication modes:

  * ``comm="gspmd"`` — params/optimizer sharded by parallel.sharding specs,
    XLA inserts the collectives (production default; this is what the
    dry-run lowers);
  * ``comm="apex"``  — the paper-faithful path: the step runs inside
    shard_map over the DP axis, gradients are synchronised by the explicit
    bidirectional ring reduce-scatter / all-gather of core/collectives
    (first-neighbour torus RDMA, dual-DMA double buffering) with shard-local
    ZeRO-1 moments.  Model must fit per device (DP-pure).

Fault tolerance loop (per §4 of the paper):

  host watchdog ticks each step -> LofamoSim (the fabric model) diffuses
  any injected/host fault to neighbours -> the trainer's master view flags
  the rank -> trainer restores the last verified checkpoint onto the
  surviving mesh (elastic re-mesh: any device subset that still forms a
  torus) and replays the data stream from the checkpointed position.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint import CheckpointStore
from repro.core import collectives as C
from repro.core import fabric, hw
from repro.core.lofamo import LofamoSim
from repro.core.rdma import RdmaEndpoint
from repro.core.topology import Torus
from repro.data import SyntheticTokens, make_batch_arrays
from repro.models import api
from repro.models.common import ArchCfg
from repro.optim import AdamWConfig, adamw_init, adamw_update
from repro.optim.adamw import apex_zero1_update
from repro.parallel import sharding


@dataclasses.dataclass
class TrainerConfig:
    ckpt_dir: str = "/tmp/apex_ckpt"
    ckpt_every: int = 50
    keep_last: int = 3
    opt: AdamWConfig = dataclasses.field(default_factory=AdamWConfig)
    batch: int = 8
    seq_len: int = 128
    # microbatch gradient accumulation: the global batch is split into
    # `grad_accum` sequential microbatches whose grads accumulate in fp32
    # before one optimizer step — on TPU the DP gradient reduction of
    # microbatch i overlaps the compute of i+1 (XLA async collectives),
    # and activation memory drops by the same factor
    grad_accum: int = 1
    remat: bool = True
    comm: str = "gspmd"            # or "apex"
    dp_axis: str = "data"
    # link-fault policy ("remesh" is the node-fault-only default: a dead
    # link loses no state, so it is logged and routing is left to the
    # runtime fabric); "reroute" (apex comm only) = rewrite the collective
    # schedules around the dead link and keep training — no restart, no
    # lost steps, just a higher predicted hop cost.  Node faults always
    # checkpoint-restart on an elastically re-meshed machine.
    fault_mode: str = "remesh"
    # overlap engine (apex comm only): bucket the gradient reduce-scatter
    # (fabric.plan_buckets) and issue each bucket's schedule inside the
    # backward pass via the fabric bucket grad hook, so the ppermute
    # rounds overlap the remaining backward compute — the schedule-level
    # analogue of the §2.1 dual-DMA prefetchable command queue.  Numerics
    # are identical to the sequential step (fp32 params: bitwise).
    overlap: bool = False
    # bucket size target (MB of fp32 grads).  The default (None) loads
    # the fabric autotuner's searched value from ``best_configs.json``
    # ("train" workload entry — see ``fabric.autotune``) and falls back
    # to the hand-tuned 4 MB when no artifact is pinned; passing any
    # explicit number always wins (the escape hatch).
    bucket_mb: float | None = None
    # fabric time-model backend for predicted_comm_s / the overlap
    # estimate: "analytic" (closed-form, the fast default) or "sim" (the
    # event-driven link-level FabricSim replay — same number on healthy
    # single-flow schedules, honest contention pricing under detours)
    cost_backend: str = "analytic"
    # sim-backend fidelity tier: "packet" (the bitwise oracle), "fluid"
    # (flow-level rate allocation — the fast path for big tori) or
    # "hybrid" (fluid with packet escalation of contended links).  The
    # analytic backend ignores it.
    cost_fidelity: str = "packet"
    wd_period: float = 0.5          # LO|FA|MO watchdog period (seconds)
    straggler_factor: float = 3.0   # step slower than this x median -> flag
    seed: int = 0
    # LO|FA|MO fabric shape override: the fault model may cover the full
    # cluster even when this process drives fewer devices (default: the
    # mesh's own torus twin)
    torus_dims: tuple | None = None

    def __post_init__(self) -> None:
        if self.bucket_mb is None:
            from repro.core.fabric import autotune
            self.bucket_mb = float(
                autotune.tuned_knob("train", "bucket_mb", 4.0))


class Trainer:
    def __init__(self, cfg: ArchCfg, tcfg: TrainerConfig,
                 mesh: Mesh | None = None,
                 telemetry: "object | None" = None) -> None:
        self.cfg = cfg
        self.tcfg = tcfg
        self.mesh = mesh
        # optional fabric Telemetry hub: step spans, fault-epoch events
        # and the RDMA twin's counters (None = zero telemetry code runs)
        self.telemetry = telemetry
        self.model = api.get_model(cfg)
        self.store = CheckpointStore(tcfg.ckpt_dir, keep_last=tcfg.keep_last)
        self.data = SyntheticTokens(cfg, tcfg.batch, tcfg.seq_len,
                                    seed=tcfg.seed)
        self.metrics_log: list[dict] = []
        self.events: list[str] = []
        self._step_times: list[float] = []
        # LO|FA|MO fabric model over the mesh's torus twin
        if tcfg.torus_dims is not None:
            dims = tuple(tcfg.torus_dims)
        elif mesh is not None:
            dims = tuple(mesh.shape[a] for a in mesh.axis_names)
        else:
            dims = (1,)
        self.torus = Torus(dims)
        self.lofamo = LofamoSim(self.torus, wd_period=tcfg.wd_period)
        # RDMA endpoint twin: its command-queue depth feeds the overlap
        # model (prefetchable queue = issue gaps hidden between buckets)
        self.rdma = RdmaEndpoint(self.torus, rank=0, telemetry=telemetry)
        self._handled_faults: set[int] = set()
        self._handled_links: set[tuple[int, int]] = set()
        self._fault_map = fabric.FaultMap()
        self.predicted_comm_s: float | None = None
        self.bucket_plan: fabric.BucketPlan | None = None
        self.overlap_estimate: fabric.OverlapEstimate | None = None
        self._overlap_baseline: dict | None = None
        self._build()

    # ------------------------------------------------------------------ build
    def _build(self) -> None:
        cfg, tcfg = self.cfg, self.tcfg
        key = jax.random.key(tcfg.seed)
        if self.mesh is None or tcfg.comm == "single":
            self.params = self.model.init(key)
            self.opt_state = adamw_init(self.params)
            self._make_single_step()
            return
        if tcfg.comm == "apex":
            self._build_apex(key)
        else:
            self._build_gspmd(key)

    def _loss_and_grads(self):
        """(params, batch) -> (loss, grads); microbatched when grad_accum>1
        (fp32 accumulation, one optimizer step per global batch)."""
        model, remat, accum = self.model, self.tcfg.remat, self.tcfg.grad_accum

        def single(params, batch):
            return jax.value_and_grad(
                lambda p: model.train_loss(p, batch, remat=remat))(params)

        if accum <= 1:
            return single

        def accumulated(params, batch):
            micro = jax.tree.map(
                lambda x: x.reshape((accum, x.shape[0] // accum)
                                    + x.shape[1:]), batch)
            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)

            def body(carry, mb):
                loss_acc, g_acc = carry
                loss, g = single(params, mb)
                g_acc = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), g_acc, g)
                return (loss_acc + loss, g_acc), None

            (loss, grads), _ = jax.lax.scan(
                body, (jnp.zeros((), jnp.float32), zeros), micro)
            inv = 1.0 / accum
            return loss * inv, jax.tree.map(lambda g: g * inv, grads)

        return accumulated

    def _make_single_step(self):
        opt = self.tcfg.opt
        loss_and_grads = self._loss_and_grads()

        @jax.jit
        def step_fn(params, opt_state, batch):
            loss, grads = loss_and_grads(params, batch)
            params, opt_state, metrics = adamw_update(opt, grads, opt_state,
                                                      params)
            return params, opt_state, {"loss": loss, **metrics}

        self._step_fn = step_fn
        self.batch_shardings = None

    def _build_gspmd(self, key) -> None:
        cfg, tcfg, mesh = self.cfg, self.tcfg, self.mesh
        shapes = api.param_shapes(cfg)
        pspecs = sharding.param_specs(cfg, shapes, mesh)
        self.param_shardings = sharding.named(mesh, pspecs)
        params = jax.jit(self.model.init,
                         out_shardings=self.param_shardings)(key)
        ostate_shapes = jax.eval_shape(adamw_init, shapes)
        ospecs = {"m": sharding.zero1_specs(cfg, shapes, mesh),
                  "v": sharding.zero1_specs(cfg, shapes, mesh),
                  "step": P()}
        self.opt_shardings = sharding.named(mesh, ospecs)
        opt_state = jax.jit(adamw_init,
                            out_shardings=self.opt_shardings)(params)
        batch_shapes = jax.eval_shape(
            lambda: jax.tree.map(
                jnp.zeros_like,
                make_batch_arrays(self.data.next_batch(), cfg)))
        self.data.step -= 1  # the eval_shape batch was a peek
        bspecs = sharding.batch_specs(cfg, batch_shapes, mesh)
        self.batch_shardings = sharding.named(mesh, bspecs)
        opt = tcfg.opt
        loss_and_grads = self._loss_and_grads()

        @jax.jit
        def step_fn(params, opt_state, batch):
            loss, grads = loss_and_grads(params, batch)
            params, opt_state, metrics = adamw_update(opt, grads, opt_state,
                                                      params)
            return params, opt_state, {"loss": loss, **metrics}

        self._step_fn = step_fn
        self.params, self.opt_state = params, opt_state

    # ------------------------------------------------------- apex (fabric)
    def _apex_schedules(self) -> dict:
        """Lower the apex step's collective schedules against the fabric
        torus, rewritten around the currently known fault map."""
        axis = self.tcfg.dp_axis
        dp = self.mesh.shape[axis]
        torus = self.torus if self.torus.dims == (dp,) else Torus((dp,))
        scheds = {
            "rs": fabric.lower_reduce_scatter(torus, (axis,), mean=True),
            "ag": fabric.lower_all_gather(torus, (axis,)),
            "loss": fabric.lower_all_reduce(torus, (axis,), mean=True),
        }
        if self._fault_map:
            scheds = {k: fabric.rewrite(s, self._fault_map)
                      for k, s in scheds.items()}
        return scheds

    def _predict_comm_s(self, scheds) -> float:
        """Predicted per-step gradient-sync time: every leaf's fp32 grad
        reduce-scatter plus updated-param all-gather, priced on the same
        schedules the step executes (fabric cost model)."""
        axis = self.tcfg.dp_axis
        dp = self.mesh.shape[axis]
        backend = self.tcfg.cost_backend
        # trainer collectives carry the COLLECTIVE traffic class.  Both
        # default backends price a quiet fabric where the tag is inert
        # (analytic ignores it; backend="sim" builds a single-class sim);
        # it matters when a caller prices these schedules on a QoS sim —
        # fabric.estimate(..., backend="sim", qos=QosPolicy()) or a
        # shared ServingCluster timeline — where the flows then ride the
        # COLLECTIVE virtual channel
        cls = fabric.TrafficClass.COLLECTIVE
        fid = self.tcfg.cost_fidelity
        total = fabric.estimate(scheds["loss"], 4, backend=backend,
                                fidelity=fid, cls=cls).total_s
        for p in jax.tree.leaves(self.params):
            chunk_bytes = -(-p.size // dp) * p.dtype.itemsize
            total += fabric.estimate(scheds["rs"], 4 * p.size,
                                     backend=backend, fidelity=fid,
                                     cls=cls).total_s
            total += fabric.estimate(scheds["ag"], chunk_bytes,
                                     backend=backend, fidelity=fid,
                                     cls=cls).total_s
        return total

    def _bwd_compute_model_s(self) -> float:
        """Modelled per-rank backward-compute seconds — the overlap model's
        compute trace (backward ~ 2x forward = 4 * P * T FLOPs, priced at a
        conservative 40% MFU on the target chip)."""
        dp = self.mesh.shape[self.tcfg.dp_axis]
        tokens = self.tcfg.batch * self.tcfg.seq_len / max(dp, 1)
        flops = 4.0 * self.n_params * tokens
        return flops / (hw.TPU_V5E.peak_flops_bf16 * 0.4)

    def _make_apex_step(self) -> None:
        """(Re)build the jitted apex step from the current schedules.

        With ``overlap=True`` the gradient reduce-scatter runs bucket by
        bucket *inside* the backward pass (fabric bucket grad hook) and the
        ZeRO-1 update consumes the pre-reduced shards; a sequential twin of
        the step is also built as the measured-overlap baseline."""
        tcfg, mesh = self.tcfg, self.mesh
        axis = tcfg.dp_axis
        model, opt, remat = self.model, tcfg.opt, tcfg.remat
        scheds = self._apex_schedules()
        self.apex_schedules = scheds
        self.predicted_comm_s = self._predict_comm_s(scheds)
        overlap = tcfg.overlap
        self._overlap_baseline = None
        if overlap:
            bucket_bytes = max(int(tcfg.bucket_mb * (1 << 20)), 1)
            self.bucket_plan = fabric.plan_buckets(self.params, bucket_bytes)
            self.overlap_estimate = fabric.estimate_overlapped(
                scheds["rs"], self.bucket_plan, self._bwd_compute_model_s(),
                queue_depth=self.rdma.queue_depth,
                backend=self.tcfg.cost_backend,
                fidelity=self.tcfg.cost_fidelity,
                cls=fabric.TrafficClass.COLLECTIVE)
        else:
            self.bucket_plan = None
            self.overlap_estimate = None

        def make_per_shard(bucketed: bool):
            hook = (fabric.make_bucket_grad_hook(self.bucket_plan,
                                                 scheds["rs"])
                    if bucketed else (lambda p: p))

            def per_shard(params, m, v, step, batch):
                loss, grads = jax.value_and_grad(
                    lambda p: model.train_loss(hook(p), batch,
                                               remat=remat))(params)
                # mean loss across DP ranks over the torus ring
                loss = C.ring_all_reduce(loss[None], axis,
                                         schedule=scheds["loss"])[0]
                state = {"m": m, "v": v, "step": step}
                params, state = apex_zero1_update(opt, grads, state, params,
                                                  axis_name=axis,
                                                  rs_schedule=scheds["rs"],
                                                  ag_schedule=scheds["ag"],
                                                  pre_reduced=bucketed)
                return params, state["m"], state["v"], state["step"], loss

            return per_shard

        in_specs = (P(), P(axis), P(axis), P(), P(axis))
        out_specs = (P(), P(axis), P(axis), P(), P())
        # check_vma off: outputs ARE replicated (post all-gather), but the
        # ppermute chain hides that from the varying-axes checker.
        self._apex_step = jax.jit(jax.shard_map(
            make_per_shard(overlap), mesh=mesh, in_specs=in_specs,
            out_specs=out_specs, check_vma=False))
        self._apex_step_seq = None
        self._apex_compute_fn = None
        if overlap:
            self._apex_step_seq = jax.jit(jax.shard_map(
                make_per_shard(False), mesh=mesh, in_specs=in_specs,
                out_specs=out_specs, check_vma=False))

            def grads_only(params, batch):
                loss, grads = jax.value_and_grad(
                    lambda p: model.train_loss(p, batch,
                                               remat=remat))(params)
                # the grads must be consumed in the output or XLA dead-code
                # eliminates the whole backward pass and this "compute
                # baseline" times the forward only
                keep = sum(jnp.sum(g.astype(jnp.float32))
                           for g in jax.tree.leaves(grads))
                return jnp.stack([loss, keep])[None]

            self._apex_compute_fn = jax.jit(jax.shard_map(
                grads_only, mesh=mesh, in_specs=(P(), P(axis)),
                out_specs=P(axis), check_vma=False))

        def step_fn(params, opt_state, batch):
            params, m, v, step, loss = self._apex_step(
                params, opt_state["m"], opt_state["v"], opt_state["step"],
                batch)
            return params, {"m": m, "v": v, "step": step}, {"loss": loss}

        self._step_fn = step_fn

    def _measure_overlap_baseline(self, batch) -> dict:
        """One-off calibration for measured overlap efficiency: wall-time
        the sequential (barrier) apex step and the compute-only backward on
        the live batch shapes (second run each, past jit compilation).
        Also warms the overlapped step itself, so the step times compared
        against these baselines never include its compile."""
        args = (self.params, self.opt_state["m"], self.opt_state["v"],
                self.opt_state["step"], batch)

        def timed(fn, *a):
            jax.block_until_ready(fn(*a))
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*a))
            return time.perf_counter() - t0

        seq_s = timed(self._apex_step_seq, *args)
        compute_s = timed(self._apex_compute_fn, self.params, batch)
        jax.block_until_ready(self._apex_step(*args))   # warm, discard
        return {"seq_s": seq_s, "compute_s": compute_s}

    def _build_apex(self, key) -> None:
        """Paper-faithful DP: shard_map + explicit torus ring collectives,
        every collective lowered through the fabric's CollectiveSchedule."""
        axis = self.tcfg.dp_axis
        dp = self.mesh.shape[axis]
        self.params = self.model.init(key)   # replicated
        self._make_apex_step()
        # global moment buffers: (dp * chunk,) per leaf
        m = jax.tree.map(
            lambda p: jnp.zeros((dp * (-(-p.size // dp)),), jnp.float32),
            self.params)
        self.opt_state = {"m": m, "v": jax.tree.map(jnp.copy, m),
                          "step": jnp.zeros((), jnp.int32)}
        self.batch_shardings = None
        self._batch_spec = P(axis)

    @property
    def n_params(self) -> int:
        return sum(int(np.prod(x.shape))
                   for x in jax.tree.leaves(self.params))

    def _place_tree(self, tree):
        """Re-place a restored host tree onto the current mesh shardings."""
        if getattr(self, "param_shardings", None) is not None \
                and self.tcfg.comm == "gspmd" and self.mesh is not None:
            return {"params": jax.device_put(tree["params"],
                                             self.param_shardings),
                    "opt": jax.device_put(tree["opt"], self.opt_shardings)}
        return jax.tree.map(jnp.asarray, tree)

    def resume(self) -> None:
        """Restore the latest checkpoint (raises FileNotFoundError if none)."""
        template = {"params": self.params, "opt": self.opt_state}
        tree, extra = self.store.restore_latest(
            jax.tree.map(np.asarray, template))
        placed = self._place_tree(tree)
        self.params, self.opt_state = placed["params"], placed["opt"]
        self.data = SyntheticTokens.from_state(
            self.cfg, self.tcfg.batch, self.tcfg.seq_len, extra["data"])
        self.events.append(f"resumed from checkpoint @ step {self.data.step}")

    # ------------------------------------------------------------------- loop
    def _place_batch(self, np_batch):
        batch = make_batch_arrays(np_batch, self.cfg, self.batch_shardings)
        if self.tcfg.comm == "apex" and self.mesh is not None:
            batch = {k: jax.device_put(
                v, NamedSharding(self.mesh, P(self.tcfg.dp_axis)))
                for k, v in batch.items()}
        return batch

    def train_step(self) -> dict:
        t0 = time.perf_counter()
        # models with explicit shard_map paths (ep_a2a MoE, manual_sp)
        # resolve the mesh through the registry at trace time
        sharding.set_runtime_mesh(self.mesh)
        np_batch = self.data.next_batch()
        batch = self._place_batch(np_batch)
        if self.tcfg.comm == "apex" and self.tcfg.overlap \
                and self._overlap_baseline is None \
                and self._apex_step_seq is not None:
            self._overlap_baseline = self._measure_overlap_baseline(batch)
            t0 = time.perf_counter()  # calibration is not step time
        self.params, self.opt_state, metrics = self._step_fn(
            self.params, self.opt_state, batch)
        jax.block_until_ready(metrics["loss"])
        dt = time.perf_counter() - t0
        self._step_times.append(dt)
        metrics = {k: float(v) for k, v in metrics.items()}
        metrics["step_time_s"] = dt
        metrics["step"] = self.data.step
        if self.predicted_comm_s is not None:
            # fabric cost model vs wall clock: the schedule's predicted
            # gradient-sync time for this step (APEnet+ NetModel pricing)
            metrics["predicted_comm_s"] = self.predicted_comm_s
        if self.overlap_estimate is not None:
            # overlap engine: predicted overlap efficiency (fraction of
            # fabric time hidden behind backward compute, from the
            # bucketed timeline model) vs the measured one (wall clock of
            # the overlapped step against the sequential-step and
            # compute-only calibration baselines)
            est = self.overlap_estimate
            metrics["overlap_eff_pred"] = est.efficiency
            metrics["overlap_pred_reduction"] = est.reduction
            metrics["overlap_pred_total_s"] = est.total_s
            if self._overlap_baseline is not None:
                base = self._overlap_baseline
                comm_meas = max(base["seq_s"] - base["compute_s"], 1e-9)
                eff = (base["seq_s"] - dt) / comm_meas
                metrics["overlap_eff_measured"] = float(
                    np.clip(eff, 0.0, 1.0))
                metrics["seq_step_s"] = base["seq_s"]
        # straggler detection: this step vs the running median
        if len(self._step_times) >= 5:
            med = float(np.median(self._step_times[-20:]))
            if dt > self.tcfg.straggler_factor * med:
                metrics["straggler"] = True
                self.events.append(
                    f"straggler step={self.data.step} {dt:.3f}s vs median "
                    f"{med:.3f}s — would re-issue on hot spare")
        self.metrics_log.append(metrics)
        if self.telemetry is not None:
            self.telemetry.add("trainer.steps")
            self.telemetry.add("trainer.step_time_s", dt)
            # trainer spans ride a logical clock (cumulative step time):
            # the trainer has no fabric sim frontier to stamp against
            self.telemetry.event(
                ("trainer",), f"step{self.data.step}",
                sum(self._step_times[:-1]), dt,
                loss=metrics.get("loss", 0.0), step=self.data.step)
        return metrics

    def checkpoint(self) -> None:
        tree = {"params": self.params, "opt": self.opt_state}
        self.store.save_async(self.data.step, tree,
                              extra={"data": self.data.state(),
                                     "arch": self.cfg.name})
        self.events.append(f"checkpoint @ step {self.data.step}")

    def train(self, steps: int, *, fault_hook: Callable[[int], None] | None
              = None) -> list[dict]:
        out = []
        for i in range(steps):
            if fault_hook:
                fault_hook(i)
            # LO|FA|MO: one watchdog tick per step (the diagnostic traffic
            # rides the fabric; zero cost on the data path)
            self.lofamo.step()
            failed = self.lofamo.detected_at_master() - self._handled_faults
            if failed:
                self._recover(failed)
                self._handled_faults |= failed
            links = (self.lofamo.detected_links_at_master()
                     - self._handled_links)
            if links:
                self._handle_link_faults(links)
                self._handled_links |= links
            out.append(self.train_step())
            if self.tcfg.ckpt_every and \
                    self.data.step % self.tcfg.ckpt_every == 0:
                self.checkpoint()
        self.store.wait()
        return out

    # -------------------------------------------------------------- recovery
    def _handle_link_faults(self, links: set[tuple[int, int]]) -> None:
        """A torus link died but both endpoints live.  Under
        ``fault_mode="reroute"`` (apex comm) the collective schedules are
        rewritten around the dead link — same numerics, no restart, only a
        higher predicted hop cost; otherwise we just log the awareness."""
        self.events.append(
            f"LO|FA|MO: master aware of dead link(s) {sorted(links)}")
        if self.telemetry is not None:
            self.telemetry.add("fabric.fault_epochs")
            self.telemetry.event(
                ("trainer",), "link_fault", sum(self._step_times),
                links=sorted(links))
        if self.tcfg.fault_mode != "reroute" or self.tcfg.comm != "apex" \
                or self.mesh is None:
            return
        dp = self.mesh.shape[self.tcfg.dp_axis]
        if self.torus.dims != (dp,):
            # LofamoSim link pairs are ranks of self.torus; the apex
            # schedules are lowered on the dp ring — without a 1:1 match
            # the pair would be misread in the other rank space
            self.events.append(
                f"reroute unsupported: fault torus {self.torus.dims} is not "
                f"the dp ring ({dp},); routing left to the runtime fabric")
            return
        before = self.predicted_comm_s
        self._fault_map = fabric.FaultMap.normalized(
            self._fault_map.dead_nodes,
            set(self._fault_map.dead_links) | links)
        try:
            self._make_apex_step()
        except fabric.UnroutableError as e:
            self.events.append(f"reroute impossible ({e}); keeping schedule")
            return
        hops = max(s.max_hops for s in self.apex_schedules.values())
        self.events.append(
            f"rerouted collectives around {sorted(links)}: detour "
            f"max_hops={hops}, predicted grad-sync "
            f"{(before or 0) * 1e3:.2f} -> {self.predicted_comm_s * 1e3:.2f} ms"
            " (training continues, no restart)")

    def _recover(self, failed: set[int]) -> None:
        """Checkpoint-restart on the surviving mesh (elastic re-mesh)."""
        self.events.append(f"LO|FA|MO: master aware of faults {sorted(failed)}"
                           f" (Ta ~ {1.8 * self.tcfg.wd_period:.2f}s)")
        self.store.wait()
        survivors = [d for i, d in enumerate(self.mesh.devices.flat)
                     if i not in failed] if self.mesh is not None else []
        if self.mesh is not None and survivors \
                and len(self.mesh.axis_names) == 1:
            # largest power-of-two prefix that still forms a ring
            n = 1
            while n * 2 <= len(survivors):
                n *= 2
            from repro.launch.mesh import make_mesh
            new_mesh = make_mesh((n,), self.mesh.axis_names,
                                 devices=survivors[:n])
            self.events.append(
                f"elastic re-mesh: {self.mesh.devices.size} -> {n} devices")
            self.mesh = new_mesh
            self.torus = Torus(tuple(new_mesh.shape[a]
                                     for a in new_mesh.axis_names))
            self.lofamo = LofamoSim(self.torus,
                                    wd_period=self.tcfg.wd_period)
            # fresh fabric: the surviving devices' links are all healthy
            self._fault_map = fabric.FaultMap()
            self._handled_links = set()
        # restore model+opt+data from the last verified checkpoint
        template = {"params": self.params, "opt": self.opt_state}
        try:
            tree, extra = self.store.restore_latest(
                jax.tree.map(np.asarray, template))
        except FileNotFoundError:
            self.events.append("no checkpoint yet: restarting from init")
            self._build()
            return
        self._build()  # rebuild step fn / shardings for the new mesh
        placed = self._place_tree(tree)
        self.params, self.opt_state = placed["params"], placed["opt"]
        self.data = SyntheticTokens.from_state(
            self.cfg, self.tcfg.batch, self.tcfg.seq_len, extra["data"])
        self.events.append(
            f"restored step {self.data.step}; data stream replayed")
