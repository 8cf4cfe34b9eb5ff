"""EdgeSession — one engine owning pool, plan, cache, and train steps.

The paper's orchestrator (Alg. 1 plans a device pool; epoch 1 runs
hybrid DP×PP; cached epochs drop to pure DP) as a programmable object
instead of a CLI script. An :class:`EdgeSession` takes a validated
:class:`~repro.runtime.spec.RunSpec` and owns the whole run lifecycle:

* **device pool** — forcing the host device count *before* the first
  JAX backend initialisation stays a documented pre-backend hook:
  ``open()`` resolves the pool size (plan file, ``pool``, dp×stages)
  and calls :func:`repro.compat.force_host_device_count` before any
  backend-touching import runs. Construct the session (and its spec)
  before initialising a JAX backend, or bring your own devices.
* **plan** — resolves ``spec.plan`` (``"auto"`` runs Alg. 1 and sweeps
  the micro count; a path replays a saved plan; ``None`` pins the mesh
  to dp×stages and keeps the planner as an offline report), derives the
  executable :class:`~repro.core.planner.StagePartition`, and builds
  the mesh via :mod:`repro.core.mesh`.
* **cache** — opens the (optionally persistent) activation cache with
  the shared :func:`~repro.core.activation_cache.manifest_for` identity
  and runs each fully-resident epoch through a
  :class:`~repro.core.activation_cache.CachePrefetcher` (used as a
  context manager — an exception mid-epoch joins the worker thread).
* **steps** — compiles the four step variants (``pac_train_step``,
  ``pipeline_pac_train_step``, ``pac_cached_train_step``,
  ``dp_cached_train_step``) behind one :meth:`step` dispatch, including
  the lazily-built cached step (its sharding/shard_map wrapper needs
  the first cached batch's tree structure).

Typical use (the 10-line quickstart)::

    from repro.runtime import RunSpec, EdgeSession

    spec = RunSpec(arch="internlm2-1.8b", reduced=True, epochs=3)
    reports = EdgeSession(spec).run()          # list of EpochReport

or step-by-step::

    with EdgeSession(spec) as s:
        for report in EpochRunner(s).epochs():
            ...
        s.finish()            # checkpoint + cache manifest

Observability attaches as hooks (:class:`~repro.runtime.runner.RunHooks`)
instead of prints; pass ``log=print`` for the CLI's informational lines.
"""

from __future__ import annotations

import contextlib
import functools
from dataclasses import dataclass

import numpy as np

from repro import compat
from repro.runtime.spec import RunSpec, RunSpecError


@dataclass
class StepEvent:
    """One training step, as seen by hooks and the runner."""

    epoch: int
    index: int
    loss: float
    cache_hit: bool
    mode: str          # "full" | "cached" | "hybrid dp2xpp2" | ...
    wall_s: float


class EdgeSession:
    """The run engine. ``open()``/``close()`` (or ``with``) bracket the
    heavyweight state; :meth:`step` is the single dispatch the epoch
    loop calls; :meth:`finish` writes the run's durable outputs
    (checkpoint, cache manifest)."""

    def __init__(self, spec: RunSpec, *, log=None):
        spec.validate()
        self.spec = spec
        self._log = log if log is not None else (lambda *a: None)
        self._opened = False
        self._finished = False
        self._prefetch = None
        self._saved_plan = None
        # populated by open():
        self.cfg = None
        self.plan = None
        self.partition = None
        self.mesh = None
        self.backbone = None      # the (possibly quantized) frozen tree
        self.adapter = None
        self.opt = None
        self.corpus = None
        self.pipe = None
        self.cache = None
        self.warm = False
        self.meta = None
        self.n_micro = None
        self.exec_dp = spec.dp
        self.exec_stages = spec.stages
        self.distributed = False

    # -- lifecycle ----------------------------------------------------------

    def __enter__(self) -> "EdgeSession":
        return self.open()

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    def _resolve_pool(self) -> int:
        """Pre-backend: size the device pool (and force fake host devices
        on CPU) before JAX locks the device count. Pure Python — a saved
        plan is loaded as JSON only."""
        spec = self.spec
        pool = spec.pool or max(spec.total_devices, 4)
        if spec.plan_mode and spec.plan != "auto":
            from repro.core.planner import Plan

            self._saved_plan = Plan.load(spec.plan)
            if spec.pool is not None and spec.pool < self._saved_plan.n_stages:
                raise RunSpecError(
                    f"pool {spec.pool} is smaller than the saved plan's "
                    f"{self._saved_plan.n_stages} stages; pass pool >= "
                    f"{self._saved_plan.n_stages} or replan with plan='auto'")
            # size the replay pool from the plan's own stage count before
            # the device-count knob locks
            pool = max(pool, self._saved_plan.n_stages)
        if spec.plan_mode:
            # the plan decides dp×stages later, but the fake-device count
            # must precede the first backend initialisation — force the
            # whole pool (the mesh uses its first dp·stages devices)
            compat.force_host_device_count(pool)
        elif spec.total_devices > 1:
            compat.force_host_device_count(spec.total_devices)
        return pool

    def _build_plan(self, pool: int, planner_mb: int, n_micro: int, max_stages):
        """One construction site for both the executed plan and the
        offline report: analytic period-granular costs through Alg. 1."""
        from repro.core.planner import (
            HybridParallelismPlanner, JETSON_NANO_H, period_costs)

        spec = self.spec
        return HybridParallelismPlanner(
            period_costs(self.cfg, "pac", seq_len=spec.seq,
                         quant_bits=spec.quant),
            [JETSON_NANO_H] * pool, planner_mb, n_micro,
        ).plan(max_stages=max_stages)

    def open(self) -> "EdgeSession":
        if self._opened:
            return self
        spec = self.spec
        pool = self._resolve_pool()

        import jax

        from repro.core import steps
        from repro.core.activation_cache import (
            ActivationCache,
            manifest_for,
            open_persistent,
        )
        from repro.core.init_methods import pruning_init
        from repro.core.parallel_adapters import init_adapter
        from repro.core.quantization import quantize_tree, tree_storage_bytes
        from repro.data import DataPipeline, SyntheticPersonalCorpus
        from repro.core.mesh import make_edge_mesh, make_plan_mesh
        from repro.models import backbone as bb
        from repro.optim import adamw_init

        log = self._log
        cfg = self.cfg = spec.arch_config()
        log(f"arch={cfg.name} params≈{cfg.param_count()/1e6:.1f}M "
            f"active≈{cfg.active_param_count()/1e6:.1f}M")

        # ---- plan resolution: the Plan is the runtime contract ----------
        partition = None
        exec_dp, exec_stages = spec.dp, spec.stages
        total = spec.total_devices
        n_micro = spec.default_micro()
        if spec.plan_mode:
            n_micro = spec.micro or (
                self._saved_plan.micro_batches if self._saved_plan else None)
            if n_micro is not None and spec.batch % n_micro:
                raise RunSpecError(
                    f"batch {spec.batch} must be divisible by the plan's "
                    f"{n_micro} micro-batches (override with micro=)")
            if spec.plan == "auto":
                smax = min(pool, cfg.n_periods)
                if n_micro is None:
                    # the plan selects the micro count too: σ-optimal
                    # latency over the batch's divisors
                    cands = [m for m in range(1, spec.batch + 1)
                             if spec.batch % m == 0]
                    n_micro, plan = min(
                        ((m, self._build_plan(pool, spec.batch // m, m, smax))
                         for m in cands),
                        key=lambda t: t[1].minibatch_latency)
                else:
                    plan = self._build_plan(pool, spec.batch // n_micro,
                                            n_micro, smax)
            else:
                plan = self._saved_plan
            mb = spec.batch // n_micro
            partition = plan.stage_partition()
            if partition.n_periods != cfg.n_periods:
                raise RunSpecError(
                    f"plan partitions {partition.n_periods} periods but "
                    f"{cfg.name} has {cfg.n_periods} — replan for this arch")
            exec_stages = partition.n_stages
            # widest replica count the pool and the batch layout support
            exec_dp = max(1, pool // exec_stages)
            while exec_dp > 1 and (spec.batch // n_micro) % exec_dp:
                exec_dp -= 1
            log("plan: " + plan.describe())
            for s, split in enumerate(partition.samples_per_device):
                if sum(split) != mb:
                    log(f"note: stage {s} was planned for {sum(split)} "
                        f"samples per micro-batch, executing {mb}")
            total = exec_dp * exec_stages
            self.plan = plan
        distributed = total > 1
        if distributed:
            if partition is None and cfg.n_periods % exec_stages:
                raise RunSpecError(
                    f"stages {exec_stages} must divide n_periods={cfg.n_periods}")
            # fail fast on an impossible batch layout, before any compute
            DataPipeline.dp_microbatches(
                {"tokens": np.zeros((spec.batch, spec.seq), np.int32)},
                n_micro, exec_dp)
        self.partition = partition
        self.n_micro = n_micro
        self.exec_dp, self.exec_stages = exec_dp, exec_stages
        self.distributed = distributed

        # ---- model: backbone (frozen, maybe quantized) + adapter --------
        bp = bb.init_backbone(jax.random.PRNGKey(spec.seed), cfg)
        if spec.quant:
            # one jitted program: eagerly, each op would hold a whole f32
            # temporary of the largest stacked leaf beside the f32 tree
            # (7 GiB at internlm2-1.8b widths)
            bq = jax.jit(functools.partial(quantize_tree, bits=spec.quant))(bp)
            log(f"backbone quantized INT{spec.quant}: "
                f"{tree_storage_bytes(bp)/2**20:.1f} MB → "
                f"{tree_storage_bytes(bq)/2**20:.1f} MB")
        else:
            bq = bp
        self.backbone = bq
        if spec.init == "pruning":
            self.adapter = pruning_init(
                jax.random.PRNGKey(spec.seed + 1), bp, cfg, r=spec.r)
        else:
            self.adapter = init_adapter(
                jax.random.PRNGKey(spec.seed + 1), cfg, r=spec.r)
        n_train = sum(x.size for x in jax.tree.leaves(self.adapter))
        log(f"trainable (adapter) params: {n_train/1e6:.2f}M "
            f"({n_train/cfg.param_count():.2%} of backbone)")
        self.opt = adamw_init(self.adapter)

        if not spec.plan_mode:
            # offline planning report (paper Step 3-4): the plan is
            # computed for the executed micro-batch count at period
            # granularity; the stage count is pinned to the mesh shape
            # and the planner's σ-optimum is reported against it.
            # (plan= makes this plan the execution contract instead.)
            plan = self._build_plan(pool, spec.batch, n_micro,
                                    exec_stages if distributed else None)
            log("edge-pool plan: " + plan.describe().splitlines()[0])
            if distributed and plan.n_stages != exec_stages:
                log(f"note: planner's σ-optimal stage count is "
                    f"{plan.n_stages}; executing --stages {exec_stages} "
                    f"(pass --plan auto to execute the σ-optimum)")
            self.plan = plan
        if spec.save_plan:
            log(f"plan saved: {self.plan.save(spec.save_plan)}")

        # ---- mesh -------------------------------------------------------
        if distributed:
            if spec.plan_mode:
                self.mesh = make_plan_mesh(partition, dp=exec_dp)
                ragged = "" if partition.is_uniform else (
                    f", ragged periods {partition.periods_per_stage}")
                log(f"mesh: plan-driven dp={exec_dp}×pp={exec_stages} on "
                    f"{total} devices, {n_micro} micro-batches{ragged}")
            else:
                self.mesh = make_edge_mesh(exec_dp, exec_stages)
                log(f"mesh: hybrid dp={exec_dp}×pp={exec_stages} on "
                    f"{total} devices, {n_micro} micro-batches")
            # resident on every mesh device: left on the default device,
            # the frozen tree would be copied out again on every step
            rep = jax.sharding.NamedSharding(
                self.mesh, jax.sharding.PartitionSpec())
            self.backbone, self.adapter, self.opt = jax.device_put(
                (self.backbone, self.adapter, self.opt), rep)

        # ---- data + activation cache ------------------------------------
        n_seq = spec.steps_per_epoch * spec.batch
        self.corpus = SyntheticPersonalCorpus(
            cfg.vocab, spec.seq + 1, n_seq, seed=spec.seed)
        self.pipe = DataPipeline(
            self.corpus, global_batch=spec.batch, shuffle=True, seed=spec.seed)
        cache_budget = spec.cache_budget_mb << 20
        if spec.cache_dir and spec.use_cache:
            self.meta = manifest_for(
                cfg, reduced=spec.reduced, seq_len=spec.seq,
                quant_bits=spec.quant, backbone=bq,
                corpus_tokens=self.corpus.tokens)
            self.cache, self.warm = open_persistent(
                spec.cache_dir, self.meta, budget_bytes=cache_budget,
                compress=spec.cache_compress)
            if self.warm:
                log(f"activation cache: warm manifest at {spec.cache_dir} "
                    f"({len(self.cache)} seqs, {spec.cache_compress}) — "
                    f"cached epochs skip the backbone forward entirely")
        else:
            self.cache = ActivationCache(
                budget_bytes=cache_budget, compress=spec.cache_compress)

        # ---- the four step variants behind one dispatch -----------------
        use_pallas = spec.kernels == "pallas"
        self._use_pallas = use_pallas
        self._steps_mod = steps
        # Under pallas, epoch-1 taps are quantized at the tap site into
        # the cache's storage form (no f32 HBM round-trip); put_batch
        # then adopts them without recompressing.
        tap_policy = spec.cache_compress if use_pallas else "f32"
        if distributed:
            # epoch-1: staged backbone forward over `stage` + dp AllReduce
            self._step1 = jax.jit(functools.partial(
                steps.pipeline_pac_train_step, cfg=cfg, mesh=self.mesh,
                n_micro=n_micro, r=spec.r, lr=spec.lr, partition=partition,
                kernel_impl=spec.kernels, tap_policy=tap_policy))
            # built on first cached batch (needs its tree structure)
            self._stepN = None
        else:
            self._step1 = jax.jit(functools.partial(
                steps.pac_train_step, cfg=cfg, r=spec.r, lr=spec.lr,
                kernel_impl=spec.kernels, tap_policy=tap_policy))
            # donate (adapter, opt) — the cached step returns them
            # updated, so the old buffers are reused in place every step
            self._stepN = jax.jit(
                functools.partial(steps.pac_cached_train_step, cfg=cfg,
                                  r=spec.r, lr=spec.lr,
                                  kernel_impl=spec.kernels),
                donate_argnums=(1, 2))
        self._opened = True
        return self

    def close(self) -> None:
        """Release per-run state: join any live prefetcher and (for a
        non-persistent cache) drop the entries + spill files. Does NOT
        write outputs — that is :meth:`finish`, which only a completed
        run should call."""
        if self._prefetch is not None:  # defensive: epoch_scope owns it
            self._prefetch.close()
            self._prefetch = None
        if self.cache is not None and not (self.spec.cache_dir and self.spec.use_cache):
            self.cache.clear()
        self._opened = False

    # -- the step dispatch ---------------------------------------------------

    @contextlib.contextmanager
    def epoch_scope(self, epoch: int):
        """Bracket one epoch's prefetcher lifecycle. When the whole
        epoch is cache-resident this arms a
        :class:`~repro.core.activation_cache.CachePrefetcher` (a
        background thread decompresses/loads batch k+1 — and starts its
        host→device copy — while step k runs) *as a context manager*,
        so an exception mid-epoch joins the worker thread and drains
        its queue instead of leaking a daemon holding device buffers.
        Yields True iff the epoch trains straight from the cache."""
        pf = None
        if self.spec.use_cache:
            from repro.core.activation_cache import CachePrefetcher

            order = self.pipe.epoch_order(epoch)
            if order and self.cache.covers(np.concatenate(order), with_final=True):
                pf = CachePrefetcher(
                    self.cache, order, to_device=not self.distributed,
                    dtype=None, compressed=self._use_pallas)
        if pf is None:
            yield False
            return
        with pf:
            self._prefetch = pf
            try:
                yield True
            finally:
                self._prefetch = None

    def _next_hit(self, ids):
        if self._prefetch is not None:
            return next(self._prefetch)
        if not self.spec.use_cache:
            return None
        return self.cache.get_batch(ids, with_final=True, dtype=None,
                                    compressed=self._use_pallas)

    def _build_cached_step(self, cached):
        """Epoch≥2 distributed: *pure* DP over the mesh. Lazy — the
        sharding (GSPMD) / shard_map (Pallas) wrapper needs the cached
        batch's concrete tree structure."""
        import jax

        from repro.core.mesh import cached_batch_axes, cached_step_shardings

        spec, steps = self.spec, self._steps_mod
        if self._use_pallas:
            # GSPMD cannot repartition pallas_call — the DP twin
            # shard_maps the fused step over the pool
            return jax.jit(
                functools.partial(
                    steps.dp_cached_train_step, cfg=self.cfg,
                    mesh=self.mesh, r=spec.r, lr=spec.lr,
                    kernel_impl="pallas",
                    batch_axes=cached_batch_axes(cached, self.mesh)),
                donate_argnums=(1, 2))
        return jax.jit(
            functools.partial(steps.pac_cached_train_step, cfg=self.cfg,
                              r=spec.r, lr=spec.lr),
            in_shardings=cached_step_shardings(
                self.backbone, self.adapter, self.opt, cached, self.mesh),
            donate_argnums=(1, 2))

    def step(self, batch: dict, *, epoch: int = 0, index: int = 0) -> StepEvent:
        """Run one training step: cache lookup (or prefetcher pull) →
        forward step on miss / cached step on hit → cache fill. Mutates
        the session's adapter/opt state and returns a :class:`StepEvent`.

        ``batch`` is one :meth:`DataPipeline.epoch` item (``seq_ids``
        is consumed here).

        Host spans (``jax.profiler.TraceAnnotation``, seen only while a
        profiler runs): ``pac.step`` around the whole step, holding
        ``pac.step.lookup``, ``pac.step.dispatch`` (the jitted step's
        call), the cache's ``pac.cache.put_batch`` on a miss, and
        ``pac.step.sync`` (``float(loss)``)."""
        import time

        from jax.profiler import TraceAnnotation

        if not self._opened:
            raise RuntimeError("EdgeSession.step() before open() — use "
                               "`with EdgeSession(spec) as s:` or s.open()")
        t0 = time.perf_counter()
        with TraceAnnotation("pac.step"):
            ids = batch.pop("seq_ids")
            with TraceAnnotation("pac.step.lookup"):
                hit = self._next_hit(ids)
            if hit is None:
                with TraceAnnotation("pac.step.dispatch"):
                    loss, self.adapter, self.opt, (b0, taps, bf) = self._step1(
                        self.backbone, self.adapter, self.opt, batch)
                if self.spec.use_cache:
                    # orig_last: storage-form (pallas) taps are padded to the
                    # quant block on the last axis; d_model is the true width
                    self.cache.put_batch(ids, b0, taps, bf,
                                         orig_last=self.cfg.d_model)
                cache_hit = False
            else:
                with TraceAnnotation("pac.step.dispatch"):
                    cached = self._cached_inputs(hit, batch)
                    loss, self.adapter, self.opt = self._cached_step(cached)(
                        self.backbone, self.adapter, self.opt, cached)
                cache_hit = True
            with TraceAnnotation("pac.step.sync"):
                loss = float(loss)
        return StepEvent(
            epoch=epoch, index=index, loss=loss, cache_hit=cache_hit,
            mode=self.mode(cache_hit), wall_s=time.perf_counter() - t0)

    @staticmethod
    def _cached_inputs(hit, batch: dict) -> dict:
        import jax
        import jax.numpy as jnp

        b0, taps, bf = (jax.tree.map(jnp.asarray, h) for h in hit)
        return {"b0": b0, "taps": taps, "b_final": bf,
                "labels": batch["labels"]}

    def _cached_step(self, cached: dict):
        if self._stepN is None:
            self._stepN = self._build_cached_step(cached)
        return self._stepN

    def lower_step(self, batch: dict):
        """Lower, without running, the step :meth:`step` would run for
        ``batch`` (one :meth:`DataPipeline.epoch` item): the cached step
        when the cache holds the whole batch, else the epoch-1 step. The
        result's ``as_text()`` is the program the device gets — e.g.
        whether the Pallas kernels are there as ``tpu_custom_call``s or
        were interpreted — and ``compile()`` times its compilation."""
        if not self._opened:
            raise RuntimeError("lower_step() needs an open()ed session")
        batch = dict(batch)
        ids = batch.pop("seq_ids")
        hit = None
        if self.spec.use_cache:
            hit = self.cache.get_batch(ids, with_final=True, dtype=None,
                                       compressed=self._use_pallas)
        if hit is None:
            return self._step1.lower(self.backbone, self.adapter, self.opt,
                                     batch)
        cached = self._cached_inputs(hit, batch)
        return self._cached_step(cached).lower(
            self.backbone, self.adapter, self.opt, cached)

    def mode(self, cache_hit: bool) -> str:
        """The run-mode label the trainer has always reported."""
        if cache_hit:
            return "cached pure-dp" if self.distributed else "cached"
        if self.distributed:
            kind = "plan-driven" if self.spec.plan_mode else "hybrid"
            return f"{kind} dp{self.exec_dp}xpp{self.exec_stages}"
        return "full"

    # -- fleet seams: preemption snapshots + elastic resharding ---------------

    def snapshot(self, extra: dict = None) -> dict:
        """The job's preemptible state: adapter + optimizer (the backbone
        is frozen and the activation cache is reproducible/persistent, so
        neither belongs in a snapshot). ``extra`` lets a caller ride its
        own cursor (epoch/step index) along. The tree round-trips through
        :func:`repro.checkpoint.save_checkpoint` bit-exactly — the
        preempt-then-resume test pins that."""
        if not self._opened:
            raise RuntimeError("snapshot() needs an open()ed session")
        snap = {"adapter": self.adapter, "opt": self.opt,
                "config": self.cfg.name}
        if extra:
            snap["extra"] = dict(extra)
        return snap

    def restore(self, snap: dict) -> dict:
        """Adopt a :meth:`snapshot`. Returns the snapshot's ``extra``."""
        if not self._opened:
            raise RuntimeError("restore() needs an open()ed session")
        if snap.get("config") != self.cfg.name:
            raise RunSpecError(
                f"snapshot is for arch {snap.get('config')!r}, "
                f"session runs {self.cfg.name!r}")
        self.adapter = snap["adapter"]
        self.opt = snap["opt"]
        return snap.get("extra", {})

    def save_snapshot(self, path: str, extra: dict = None) -> str:
        """Checkpointed preemption: :meth:`snapshot` to disk (msgpack,
        atomic) so a preempted job survives its process."""
        from repro.checkpoint import save_checkpoint

        save_checkpoint(path, self.snapshot(extra))
        return path

    def restore_snapshot(self, path: str) -> dict:
        from repro.checkpoint import load_checkpoint

        return self.restore(load_checkpoint(path))

    def reshard(self, dp: int, devices=None) -> None:
        """Elastic DP for a *distributed* session's cached epochs: rebuild
        the (dp, stage) mesh at a new replica width over ``devices``
        (default: the session's current device pool) and drop the
        lazily-compiled cached step so the next cached batch recompiles
        against the new layout. Legal between steps of a cached epoch —
        pure-DP state is just (adapter, opt), both device-agnostic.
        Single-device fleet jobs reshard through
        :class:`repro.fleet.ElasticDpRunner` instead (chunk-level,
        bit-identical numerics); this seam serves mesh-resident runs,
        where shard_map reduction order may shift float sums at the last
        bit. The epoch-1 step keeps the old mesh — reshard only once the
        cache covers the epoch."""
        if not self._opened:
            raise RuntimeError("reshard() needs an open()ed session")
        if not self.distributed:
            raise RunSpecError(
                "reshard() applies to multi-device sessions; single-device "
                "jobs reshard via repro.fleet.ElasticDpRunner")
        from repro.core.mesh import make_edge_mesh

        dp = int(dp)
        if dp < 1:
            raise RunSpecError(f"dp must be >= 1, got {dp}")
        self.mesh = make_edge_mesh(dp, self.exec_stages, devices)
        self.exec_dp = dp
        self._stepN = None   # rebuilt for the new mesh on the next hit

    # -- outputs --------------------------------------------------------------

    def finish(self) -> None:
        """Write the run's durable outputs: the adapter checkpoint
        (``spec.ckpt``) and — for a persistent cache — the manifest that
        lets the next run resume warm with zero backbone forwards."""
        if self._finished:
            return
        spec, log = self.spec, self._log
        if spec.ckpt:
            from repro.checkpoint import save_checkpoint

            n = save_checkpoint(
                spec.ckpt, {"adapter": self.adapter, "config": self.cfg.name})
            log(f"checkpoint: {spec.ckpt} ({n/2**20:.1f} MB)")
        if self.meta is not None:
            path = self.cache.save_manifest(self.meta)
            log(f"cache manifest: {path} ({len(self.cache)} seqs, "
                f"{spec.cache_compress})")
        self._finished = True

    def serving_engine(self, adapters=None, **kw):
        """Hand the session's artifacts to the serving layer: a
        :class:`~repro.serve.ServeEngine` over this session's (quantized)
        frozen backbone and, by default, the adapter it just trained
        (registered as ``"local"``). Pass ``adapters={name: tree, ...}``
        to serve a different bank — e.g. side networks pulled from peer
        devices' checkpoints. Engine knobs (``kv_policy``, ``page_size``,
        ``max_len``, ``max_batch``, ...) pass through; ``r`` and
        ``kernel_impl`` default to the run's spec."""
        from repro.serve import ServeEngine

        if self.backbone is None:
            raise RunSpecError("serving_engine() needs an open()ed session")
        if adapters is None:
            adapters = {"local": self.adapter}
        kw.setdefault("r", self.spec.r)
        kw.setdefault("kernel_impl", self.spec.kernels)
        return ServeEngine(self.backbone, self.cfg, adapters, **kw)

    def run(self, hooks=()) -> list:
        """The whole lifecycle in one call: open → every epoch through
        an :class:`~repro.runtime.runner.EpochRunner` → finish → close.
        Returns the list of :class:`~repro.runtime.runner.EpochReport`."""
        from repro.runtime.runner import EpochRunner

        with self:
            reports = EpochRunner(self, hooks=hooks).run()
            self.finish()
        return reports
