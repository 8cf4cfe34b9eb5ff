"""RunSpec — the typed, serializable description of one fine-tuning run.

The trainer CLI's flags, the examples' constant blocks and the on-chip
benchmark's wiring all collapse into this one dataclass:
a :class:`RunSpec` is the single source of truth an
:class:`~repro.runtime.session.EdgeSession` executes. It is pure Python
(safe to build, validate, and JSON-round-trip before any JAX backend
initialisation — the session relies on that to size the device pool
first), and every field mirrors one trainer flag (docs/CLI.md).

    spec = RunSpec(arch="internlm2-1.8b", reduced=True, dp=2, stages=2)
    spec.validate()                # layout errors before any compute
    RunSpec.from_json(spec.to_json()) == spec
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass
from typing import Optional

INIT_METHODS = ("pruning", "random")
KERNEL_IMPLS = ("ref", "pallas")
QUANT_BITS = (4, 8)


class RunSpecError(ValueError):
    """An invalid or inconsistent RunSpec (bad field value, impossible
    batch/mesh layout, plan/arch mismatch)."""


@dataclass(frozen=True)
class RunSpec:
    """One run of the paper's workflow (Fig. 4), as data.

    Defaults match the trainer CLI's defaults exactly; ``use_cache``
    inverts the CLI's ``--no-cache``. ``plan`` is ``None`` (CLI-pinned
    dp×stages), ``"auto"`` (Alg. 1 selects stages/boundaries/micro), or
    a path to a JSON saved with ``save_plan`` (replay).
    """

    # model / workload
    arch: str = "internlm2-1.8b"
    reduced: bool = False
    epochs: int = 3
    steps_per_epoch: int = 8
    batch: int = 4
    seq: int = 32
    seed: int = 0
    # adapter + backbone treatment
    r: int = 8
    init: str = "pruning"
    quant: Optional[int] = None
    lr: float = 3e-3
    # activation cache
    use_cache: bool = True
    cache_dir: Optional[str] = None
    cache_compress: str = "f32"
    cache_budget_mb: int = 4096
    # parallelism / planning
    dp: int = 1
    stages: int = 1
    micro: Optional[int] = None
    plan: Optional[str] = None
    pool: Optional[int] = None
    save_plan: Optional[str] = None
    # compute path for BOTH the epoch-1 frozen forward (OpSet dispatch:
    # quantized matmuls, Pallas flash attention, storage-form taps) and
    # the cached-epoch step ("ref" = dense jnp oracle)
    kernels: str = "ref"
    # outputs
    ckpt: Optional[str] = None

    # -- derived ------------------------------------------------------------

    @property
    def plan_mode(self) -> bool:
        return self.plan is not None

    @property
    def total_devices(self) -> int:
        """CLI-pinned mesh size (the plan may override dp×stages)."""
        return self.dp * self.stages

    def arch_config(self):
        """The effective :class:`~repro.configs.base.ArchConfig`
        (``reduced`` applied). Pure Python — no JAX state touched."""
        from repro.configs import get_arch

        cfg = get_arch(self.arch)
        return cfg.reduced() if self.reduced else cfg

    def default_micro(self) -> Optional[int]:
        """The micro-batch count when the spec pins one statically:
        ``micro`` if set, else the stage count when distributed, else the
        4-micro planning-report default. ``None`` in plan mode with no
        override (the plan supplies or sweeps it)."""
        if self.micro is not None:
            return self.micro
        if self.plan_mode:
            return None
        return self.stages if self.total_devices > 1 else 4

    # -- validation ---------------------------------------------------------

    def validate(self) -> "RunSpec":
        """Raise :class:`RunSpecError` on any statically-checkable
        inconsistency: enum fields, batch divisibility, mesh layout,
        period/stage compatibility. Plan-file-dependent checks (pool ≥
        saved plan's stages, plan/arch period match) run when the
        session resolves the plan. Returns self for chaining."""
        def bad(msg):
            raise RunSpecError(msg)

        for name in ("epochs", "steps_per_epoch", "batch", "seq", "r",
                     "dp", "stages", "cache_budget_mb"):
            if getattr(self, name) < 1:
                bad(f"{name} must be >= 1, got {getattr(self, name)}")
        if self.init not in INIT_METHODS:
            bad(f"init must be one of {INIT_METHODS}, got {self.init!r}")
        if self.kernels not in KERNEL_IMPLS:
            bad(f"kernels must be one of {KERNEL_IMPLS}, got {self.kernels!r}")
        if self.quant is not None and self.quant not in QUANT_BITS:
            bad(f"quant must be one of {QUANT_BITS} or None, got {self.quant!r}")
        from repro.core.activation_cache import COMPRESS_POLICIES

        if self.cache_compress not in COMPRESS_POLICIES:
            bad(f"cache_compress must be one of {COMPRESS_POLICIES}, "
                f"got {self.cache_compress!r}")
        if self.micro is not None:
            if self.micro < 1:
                bad(f"micro must be >= 1, got {self.micro}")
            if self.batch % self.micro:
                bad(f"batch {self.batch} must be divisible by micro={self.micro}")
        if self.pool is not None and self.pool < 1:
            bad(f"pool must be >= 1, got {self.pool}")
        if self.plan_mode and self.plan != "auto":
            # a saved plan is pure JSON (no JAX state) — load it here so
            # pool-vs-stages inconsistencies surface before any compute
            from repro.core.planner import Plan

            try:
                saved = Plan.load(self.plan)
            except (OSError, ValueError, KeyError) as e:
                bad(f"cannot load plan file {self.plan!r}: {e}")
            if self.pool is not None and self.pool < saved.n_stages:
                bad(f"pool {self.pool} is smaller than the saved plan's "
                    f"{saved.n_stages} stages; pass pool >= "
                    f"{saved.n_stages} or replan with plan='auto'")
        if not self.plan_mode and self.total_devices > 1:
            n_micro = self.default_micro()
            if self.batch % n_micro:
                bad(f"batch {self.batch} must be divisible by the "
                    f"{n_micro} micro-batches")
            if (self.batch // n_micro) % self.dp:
                bad(f"micro-batch size {self.batch // n_micro} must be "
                    f"divisible by dp={self.dp}")
            cfg = self.arch_config()
            if cfg.n_periods % self.stages:
                bad(f"stages {self.stages} must divide n_periods="
                    f"{cfg.n_periods} of {cfg.name} (or use plan='auto' "
                    f"for uneven boundaries)")
        return self

    def replace(self, **changes) -> "RunSpec":
        """A modified copy, re-validated — how the fleet CLI stamps
        per-job seeds, cache dirs, and checkpoint paths onto one base
        spec (``RunSpec`` is frozen)."""
        return dataclasses.replace(self, **changes).validate()

    # -- (de)serialisation --------------------------------------------------

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "RunSpec":
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = sorted(set(d) - known)
        if unknown:
            raise RunSpecError(f"unknown RunSpec field(s): {unknown}")
        return cls(**d)

    def to_json(self, indent: Optional[int] = 1) -> str:
        return json.dumps(self.to_dict(), indent=indent, sort_keys=True)

    @classmethod
    def from_json(cls, s: str) -> "RunSpec":
        return cls.from_dict(json.loads(s))

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            f.write(self.to_json())
        return path

    @classmethod
    def load(cls, path: str) -> "RunSpec":
        with open(path) as f:
            return cls.from_json(f.read())

    @classmethod
    def from_args(cls, ns) -> "RunSpec":
        """Build from the trainer CLI's parsed ``argparse`` namespace —
        the flags are a thin veneer over this constructor (docs/CLI.md)."""
        return cls(
            arch=ns.arch, reduced=ns.reduced, epochs=ns.epochs,
            steps_per_epoch=ns.steps_per_epoch, batch=ns.batch, seq=ns.seq,
            seed=ns.seed, r=ns.r, init=ns.init, quant=ns.quant, lr=ns.lr,
            use_cache=not ns.no_cache, cache_dir=ns.cache_dir,
            cache_compress=ns.cache_compress,
            cache_budget_mb=ns.cache_budget_mb, dp=ns.dp, stages=ns.stages,
            micro=ns.micro, plan=ns.plan, pool=ns.pool,
            save_plan=ns.save_plan,
            kernels=ns.kernels, ckpt=ns.ckpt,
        )
