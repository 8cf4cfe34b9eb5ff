"""The runtime layer: RunSpec + EdgeSession + EpochRunner.

Acceptance contract of the session refactor:

* :class:`RunSpec` is typed, validated, and JSON-round-trippable — the
  trainer flags are a veneer over it (checked literally: the CLI and an
  equivalent RunSpec produce byte-identical output modulo timings);
* :class:`EdgeSession` is *golden-equivalent* to the pre-refactor
  trainer loop: its losses match a hand-composed
  ``pac_train_step``/``pac_cached_train_step`` (and pipeline/sharded)
  loop bit-for-bit, on the single-device, hybrid dp2×pp2, and Pallas
  cached paths;
* :class:`EpochRunner` streams typed records (StepEvent*, EpochReport)
  and fires hooks in order.

Multi-device tests run in subprocesses (the device count locks at
backend init; this process keeps the single real device).
"""

import os
import re
import subprocess
import sys
import textwrap
import types

import pytest

from repro.runtime import RunSpec, RunSpecError

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_sub(code: str) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# ---------------------------------------------------------------------------
# RunSpec: serialisation
# ---------------------------------------------------------------------------


def test_runspec_json_round_trip():
    spec = RunSpec(arch="t5-base-pac", reduced=True, epochs=5, batch=8,
                   quant=8, micro=2, dp=2, stages=2, cache_compress="int8",
                   kernels="pallas", plan=None, lr=1e-4)
    assert RunSpec.from_json(spec.to_json()) == spec
    assert RunSpec.from_dict(spec.to_dict()) == spec
    # defaults survive too
    assert RunSpec.from_json(RunSpec().to_json()) == RunSpec()


def test_runspec_save_load(tmp_path):
    spec = RunSpec(reduced=True, epochs=2, cache_dir=str(tmp_path / "c"))
    path = spec.save(str(tmp_path / "run.json"))
    assert RunSpec.load(path) == spec


def test_runspec_rejects_unknown_fields():
    with pytest.raises(RunSpecError, match="unknown RunSpec field"):
        RunSpec.from_dict({"epochs": 2, "batch_size": 4, "archh": "x"})


def test_runspec_from_args_inverts_no_cache():
    ns = types.SimpleNamespace(
        arch="internlm2-1.8b", reduced=True, epochs=2, steps_per_epoch=4,
        batch=4, seq=16, seed=1, r=8, init="pruning", quant=None, lr=3e-3,
        no_cache=True, cache_dir=None, cache_compress="f32",
        cache_budget_mb=64, dp=1, stages=1, micro=None, plan=None,
        pool=None, save_plan=None, kernels="ref", ckpt=None)
    spec = RunSpec.from_args(ns)
    assert spec.use_cache is False and spec.seed == 1 and spec.reduced
    ns.no_cache = False
    assert RunSpec.from_args(ns).use_cache is True


# ---------------------------------------------------------------------------
# RunSpec: validation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kw, match", [
    (dict(epochs=0), "epochs"),
    (dict(batch=-1), "batch"),
    (dict(init="magic"), "init"),
    (dict(kernels="cuda"), "kernels"),
    (dict(quant=3), "quant"),
    (dict(cache_compress="zip"), "cache_compress"),
    (dict(pool=0), "pool"),
    (dict(batch=4, micro=3), "divisible"),
    (dict(dp=2, stages=1, batch=4, micro=4), "micro-batch size"),
    # reduced internlm2 has 2 periods: 3 stages can't split them evenly
    (dict(reduced=True, dp=1, stages=3, batch=6, micro=3), "stages"),
])
def test_runspec_validation_errors(kw, match):
    with pytest.raises(RunSpecError, match=match):
        RunSpec(**kw).validate()


def test_runspec_validate_is_chainable_and_accepts_defaults():
    spec = RunSpec()
    assert spec.validate() is spec
    RunSpec(reduced=True, dp=2, stages=2, batch=4, micro=2).validate()


def test_runspec_validates_saved_plan_pool(tmp_path):
    from repro.core.planner import JETSON_NANO_H, Plan, Stage

    plan = Plan(
        stages=[
            Stage(0, 0, (JETSON_NANO_H,), (4,), 0.1),
            Stage(1, 1, (JETSON_NANO_H,), (4,), 0.1),
        ],
        n_stages=2, micro_batches=2,
        latency_begin=0.0, latency_exec=0.2, latency_end=0.0)
    path = plan.save(str(tmp_path / "plan.json"))
    with pytest.raises(RunSpecError, match="smaller than the saved plan"):
        RunSpec(plan=path, pool=1).validate()
    RunSpec(plan=path, pool=2).validate()  # big enough pool is fine
    RunSpec(plan=path).validate()          # pool=None: session sizes it
    with pytest.raises(RunSpecError, match="cannot load plan file"):
        RunSpec(plan=str(tmp_path / "missing.json")).validate()


# ---------------------------------------------------------------------------
# golden equivalence: session == directly-composed steps
# ---------------------------------------------------------------------------


def _reference_losses(spec, *, kernel_impl="ref", compressed=False):
    """The single-device trainer loop, composed by hand from the
    primitive steps exactly as the session composes them (since the
    OpSet dispatch, ``kernel_impl`` governs epoch 1 too: the pallas
    epoch-1 step emits taps in the cache's storage form) — the oracle
    the session must match bit-for-bit."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.core import steps
    from repro.core.activation_cache import ActivationCache
    from repro.core.init_methods import pruning_init
    from repro.data import DataPipeline, SyntheticPersonalCorpus
    from repro.models import backbone as bb
    from repro.optim import adamw_init

    cfg = spec.arch_config()
    bp = bb.init_backbone(jax.random.PRNGKey(spec.seed), cfg)
    ap = pruning_init(jax.random.PRNGKey(spec.seed + 1), bp, cfg, r=spec.r)
    opt = adamw_init(ap)
    corpus = SyntheticPersonalCorpus(
        cfg.vocab, spec.seq + 1, spec.steps_per_epoch * spec.batch,
        seed=spec.seed)
    pipe = DataPipeline(corpus, global_batch=spec.batch, shuffle=True,
                        seed=spec.seed)
    cache = ActivationCache(budget_bytes=spec.cache_budget_mb << 20,
                            compress=spec.cache_compress)
    tap_policy = spec.cache_compress if kernel_impl == "pallas" else "f32"
    step1 = jax.jit(functools.partial(
        steps.pac_train_step, cfg=cfg, r=spec.r, lr=spec.lr,
        kernel_impl=kernel_impl, tap_policy=tap_policy))
    stepN = jax.jit(functools.partial(
        steps.pac_cached_train_step, cfg=cfg, r=spec.r, lr=spec.lr,
        kernel_impl=kernel_impl), donate_argnums=(1, 2))
    out = []
    for epoch in range(spec.epochs):
        losses = []
        for batch in pipe.epoch(epoch):
            ids = batch.pop("seq_ids")
            if cache.covers(ids, with_final=True):
                hit = cache.get_batch(ids, with_final=True, dtype=None,
                                      compressed=compressed)
                b0, taps, bf = (jax.tree.map(jnp.asarray, h) for h in hit)
                loss, ap, opt = stepN(bp, ap, opt, {
                    "b0": b0, "taps": taps, "b_final": bf,
                    "labels": batch["labels"]})
            else:
                loss, ap, opt, (b0, taps, bf) = step1(bp, ap, opt, batch)
                cache.put_batch(ids, b0, taps, bf, orig_last=cfg.d_model)
            losses.append(float(loss))
        out.append(losses)
    return out


def test_session_matches_composed_steps_single_device():
    """EdgeSession's per-step losses == the hand-composed trainer loop,
    bit-for-bit (same seeds, same data order, same jitted steps)."""
    from repro.runtime import EdgeSession

    spec = RunSpec(arch="internlm2-1.8b", reduced=True, epochs=3,
                   steps_per_epoch=2, batch=2, seq=16, r=4, lr=1e-3)
    reports = EdgeSession(spec).run()
    assert [r.losses for r in reports] == _reference_losses(spec)
    assert [r.used_cache for r in reports] == [False, True, True]
    assert [r.mode for r in reports] == ["full", "cached", "cached"]


def test_session_matches_composed_steps_pallas_interpret():
    """Same golden check on the Pallas cached path: int8 entries reach
    the step in storage form and the fused interpret-mode kernels must
    reproduce the hand-composed loop exactly."""
    from repro.runtime import EdgeSession

    spec = RunSpec(arch="internlm2-1.8b", reduced=True, epochs=2,
                   steps_per_epoch=2, batch=2, seq=16, r=4, lr=1e-3,
                   cache_compress="int8", kernels="pallas")
    reports = EdgeSession(spec).run()
    want = _reference_losses(spec, kernel_impl="pallas", compressed=True)
    assert [r.losses for r in reports] == want
    assert reports[1].used_cache and reports[1].mode == "cached"


def test_cached_epoch_same_from_prefetcher_as_from_get_batch():
    """A cached epoch fed by the prefetcher (its batches joined on the
    device) gives the losses and adapter, bit for bit, that the same
    epoch gives fed by the cache's host-joined ``get_batch``."""
    import contextlib

    import jax
    import numpy as np

    from repro.runtime import EdgeSession

    spec = RunSpec(arch="internlm2-1.8b", reduced=True, epochs=2,
                   steps_per_epoch=2, batch=2, seq=16, r=4, lr=1e-3,
                   cache_compress="int8", kernels="pallas")

    def cached_epoch(prefetch: bool):
        with EdgeSession(spec) as s:
            for batch in s.pipe.epoch(0):
                s.step(batch)
            scope = s.epoch_scope(1) if prefetch else contextlib.nullcontext(False)
            with scope as from_prefetcher:
                assert from_prefetcher == prefetch
                events = [s.step(batch, epoch=1) for batch in s.pipe.epoch(1)]
            assert all(e.cache_hit for e in events)
            return [e.loss for e in events], jax.device_get(s.adapter)

    losses, adapter = cached_epoch(prefetch=True)
    want_losses, want_adapter = cached_epoch(prefetch=False)
    assert losses == want_losses
    for a, b in zip(jax.tree.leaves(adapter), jax.tree.leaves(want_adapter)):
        np.testing.assert_array_equal(a, b)


_GOLDEN_DP = textwrap.dedent(
    """
    import functools
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.runtime import EdgeSession, RunSpec

    spec = RunSpec(arch="internlm2-1.8b", reduced=True, epochs=2,
                   steps_per_epoch=2, batch=4, seq=16, r=4, lr=1e-3,
                   dp=2, stages=2, micro=2)
    got = [r.losses for r in EdgeSession(spec).run()]

    from repro.core import steps
    from repro.core.activation_cache import ActivationCache
    from repro.core.init_methods import pruning_init
    from repro.data import DataPipeline, SyntheticPersonalCorpus
    from repro.core import mesh as shard
    from repro.core.mesh import make_edge_mesh
    from repro.models import backbone as bb
    from repro.optim import adamw_init

    cfg = spec.arch_config()
    mesh = make_edge_mesh(2, 2)
    bp = bb.init_backbone(jax.random.PRNGKey(0), cfg)
    ap = pruning_init(jax.random.PRNGKey(1), bp, cfg, r=4)
    opt = adamw_init(ap)
    corpus = SyntheticPersonalCorpus(cfg.vocab, spec.seq + 1,
                                     spec.steps_per_epoch * spec.batch, seed=0)
    pipe = DataPipeline(corpus, global_batch=spec.batch, shuffle=True, seed=0)
    cache = ActivationCache(budget_bytes=spec.cache_budget_mb << 20)
    step1 = jax.jit(functools.partial(
        steps.pipeline_pac_train_step, cfg=cfg, mesh=mesh, n_micro=2,
        r=4, lr=1e-3, partition=None))
    stepN = None
    want = []
    for epoch in range(spec.epochs):
        losses = []
        for batch in pipe.epoch(epoch):
            ids = batch.pop("seq_ids")
            if cache.covers(ids, with_final=True):
                hit = cache.get_batch(ids, with_final=True, dtype=None)
                b0, taps, bf = (jax.tree.map(jnp.asarray, h) for h in hit)
                cached = {"b0": b0, "taps": taps, "b_final": bf,
                          "labels": batch["labels"]}
                if stepN is None:
                    stepN = jax.jit(
                        functools.partial(steps.pac_cached_train_step,
                                          cfg=cfg, r=4, lr=1e-3),
                        in_shardings=shard.cached_step_shardings(
                            bp, ap, opt, cached, mesh),
                        donate_argnums=(1, 2))
                loss, ap, opt = stepN(bp, ap, opt, cached)
            else:
                loss, ap, opt, (b0, taps, bf) = step1(bp, ap, opt, batch)
                cache.put_batch(ids, b0, taps, bf)
            losses.append(float(loss))
        want.append(losses)
    assert got == want, (got, want)
    print("GOLDEN_DP_OK")
    """
)


def test_session_matches_composed_steps_dp2xpp2():
    """Distributed golden: the session's hybrid epoch-1 + cached pure-DP
    losses == the hand-composed pipeline/sharded loop, bit-for-bit.
    (Subprocess: the session forces 4 fake host devices pre-backend and
    the reference loop reuses them.)"""
    assert "GOLDEN_DP_OK" in _run_sub(_GOLDEN_DP)


_VENEER_SPEC = ("RunSpec(arch='internlm2-1.8b', reduced=True, epochs=2, "
                "steps_per_epoch=2, batch=4, seq=16, plan='auto', pool=4, "
                "micro=2)")

_VENEER_API = textwrap.dedent(
    f"""
    from repro.runtime import ConsoleHook, EdgeSession, RunSpec
    EdgeSession({_VENEER_SPEC}, log=print).run(hooks=(ConsoleHook(),))
    """
)

_VENEER_FLAGS = ["--arch", "internlm2-1.8b", "--reduced", "--epochs", "2",
                 "--steps-per-epoch", "2", "--batch", "4", "--seq", "16",
                 "--plan", "auto", "--pool", "4", "--micro", "2"]


def test_cli_is_a_veneer_over_the_session():
    """The trainer CLI and the equivalent RunSpec produce byte-identical
    stdout (timings masked) — flags are a veneer, there is no CLI-only
    logic left. Exercised on the plan-driven path (Alg. 1 auto)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    cli = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", *_VENEER_FLAGS],
        capture_output=True, text=True, env=env, timeout=600)
    assert cli.returncode == 0, cli.stderr[-3000:]
    api = _run_sub(_VENEER_API)
    mask = lambda s: re.sub(r"time=[0-9.]+s", "time=*", s)
    assert mask(api) == mask(cli.stdout)
    assert "mesh: plan-driven dp=" in cli.stdout  # the path we meant to hit


# ---------------------------------------------------------------------------
# runner + hooks
# ---------------------------------------------------------------------------


def test_runner_streams_typed_records_and_fires_hooks_in_order():
    from repro.runtime import (
        EdgeSession,
        EpochReport,
        EpochRunner,
        RunHooks,
        StepEvent,
    )

    calls = []

    class Recorder(RunHooks):
        def on_epoch_start(self, session, epoch):
            calls.append(("start", epoch))

        def on_step(self, session, event):
            calls.append(("step", event.epoch, event.index))

        def on_epoch_end(self, session, report):
            calls.append(("end", report.epoch, report.steps))

    spec = RunSpec(arch="internlm2-1.8b", reduced=True, epochs=2,
                   steps_per_epoch=2, batch=2, seq=16, r=4)
    with EdgeSession(spec) as s:
        records = list(EpochRunner(s, hooks=[Recorder()]).events())
    events = [r for r in records if isinstance(r, StepEvent)]
    reports = [r for r in records if isinstance(r, EpochReport)]
    assert len(events) == 4 and len(reports) == 2
    # each epoch: its StepEvents, then its EpochReport (the final record)
    assert [type(r).__name__ for r in records] == [
        "StepEvent", "StepEvent", "EpochReport"] * 2
    assert calls == [("start", 0), ("step", 0, 0), ("step", 0, 1),
                     ("end", 0, 2),
                     ("start", 1), ("step", 1, 0), ("step", 1, 1),
                     ("end", 1, 2)]
    assert [e.cache_hit for e in events] == [False, False, True, True]
    assert [e.mode for e in events] == ["full", "full", "cached", "cached"]
    assert all(e.wall_s > 0 for e in events)
    assert reports[0].mean_loss == pytest.approx(
        sum(reports[0].losses) / len(reports[0].losses))


def test_console_hook_prints_the_classic_epoch_line():
    from repro.runtime import ConsoleHook, EdgeSession

    lines = []
    spec = RunSpec(arch="internlm2-1.8b", reduced=True, epochs=1,
                   steps_per_epoch=1, batch=2, seq=16, r=4)
    EdgeSession(spec).run(hooks=(ConsoleHook(print_fn=lines.append),))
    assert len(lines) == 1
    assert re.fullmatch(
        r"epoch 0: loss=\d+\.\d{4} time=\d+\.\ds \(full\) "
        r"cache\[2 seqs, \d+ MB, f32\]", lines[0]), lines[0]


def test_step_before_open_raises():
    from repro.runtime import EdgeSession

    s = EdgeSession(RunSpec(reduced=True))
    with pytest.raises(RuntimeError, match="open"):
        s.step({"tokens": None, "labels": None, "seq_ids": []})
