"""Plan-driven execution: the planner's Plan as the runtime contract.

Acceptance contract of the plan→execution path (ISSUE 4):

* a *uniform* Plan executed via its StagePartition produces **bit-
  identical** loss/adapter-grads to the --dp/--stages path (the partition
  dispatches to exactly the same code);
* a *ragged* Plan (uneven periods per stage) matches the single-device
  reference loss/grads/taps within fp32 tolerance, and its layer-ordered
  taps round-trip through the ActivationCache so epoch ≥2 runs zero
  backbone forwards;
* Plan JSON round-trips losslessly (save once, replay on the pool);
* the trainer CLI executes ``--plan auto`` end to end and replays a
  ``--save-plan`` file to the same losses.

Multi-device tests run in subprocesses with
``--xla_force_host_platform_device_count`` (this process keeps the
single real device).
"""

import os
import subprocess
import sys
import textwrap

import pytest

from repro.core.planner import (
    HybridParallelismPlanner,
    JETSON_NANO_H,
    JETSON_NANO_L,
    JETSON_TX2_H,
    JETSON_TX2_L,
    Plan,
    StagePartition,
    aggregate_periods,
    model_layer_costs,
    period_costs,
)
from repro.configs import get_arch, list_archs


def _run_sub(code: str) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env, timeout=600
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# ---------------------------------------------------------------------------
# StagePartition: the executable artifact
# ---------------------------------------------------------------------------


def test_stage_partition_shape_and_masks():
    p = StagePartition(boundaries=(0, 2, 6, 10),
                       samples_per_device=((4,), (4,), (2, 2)), n_micro=2)
    assert p.n_stages == 3 and p.n_periods == 10
    assert p.periods_per_stage == (2, 4, 4) and p.max_periods == 4
    assert not p.is_uniform
    assert p.masks() == (
        (True, True, False, False),
        (True, True, True, True),
        (True, True, True, True),
    )
    u = StagePartition(boundaries=(0, 5, 10), samples_per_device=((4,), (4,)), n_micro=2)
    assert u.is_uniform and u.masks() == ((True,) * 5, (True,) * 5)


def test_stage_partition_rejects_bad_boundaries():
    with pytest.raises(ValueError):
        StagePartition(boundaries=(1, 3), samples_per_device=((1,),), n_micro=1)
    with pytest.raises(ValueError):
        StagePartition(boundaries=(0, 3, 2), samples_per_device=((1,), (1,)), n_micro=1)
    with pytest.raises(ValueError):  # splits/stages mismatch
        StagePartition(boundaries=(0, 2, 4), samples_per_device=((1,),), n_micro=1)


def test_plan_partition_from_planner_is_executable():
    cfg = get_arch("internlm2-1.8b").reduced()
    plan = HybridParallelismPlanner(
        period_costs(cfg, "pac", seq_len=32), [JETSON_NANO_H] * 4, 4, 2,
    ).plan()
    part = plan.stage_partition()
    assert part.n_periods == cfg.n_periods
    assert sum(part.periods_per_stage) == cfg.n_periods
    assert part.n_micro == plan.micro_batches


def test_layer_granularity_plan_refuses_off_period_cut():
    """A plan cut inside a period is a report, not a contract — deriving a
    partition from it must fail loudly."""
    cfg = get_arch("t5-base-pac")
    costs = model_layer_costs(cfg, "full", seq_len=64)
    plan = HybridParallelismPlanner(costs, [JETSON_NANO_H] * 4, 2, 4).plan()
    if plan.n_stages == 1:
        pytest.skip("planner chose a single stage; no interior cut to test")
    lpp = len(costs)  # pretend one huge period: every interior cut is illegal
    with pytest.raises(ValueError):
        plan.stage_partition(layers_per_period=lpp)


@pytest.mark.parametrize("arch", list_archs())
def test_aggregate_periods_sums_flops_keeps_boundary_act(arch):
    cfg = get_arch(arch)
    layer = model_layer_costs(cfg, "pac", seq_len=64)
    per = aggregate_periods(layer, cfg.period)
    assert len(per) == cfg.n_periods
    P = cfg.period
    for i, c in enumerate(per):
        assert c.fwd_flops == pytest.approx(
            sum(l.fwd_flops for l in layer[i * P:(i + 1) * P]))
        # inter-stage comm is the boundary activation, not the sum
        assert c.act_bytes == layer[(i + 1) * P - 1].act_bytes
    with pytest.raises(ValueError):
        aggregate_periods(layer, len(layer) + 1)


# ---------------------------------------------------------------------------
# Plan JSON round-trip
# ---------------------------------------------------------------------------


def test_plan_json_roundtrip(tmp_path):
    from repro.core.pipeline import simulate_plan

    cfg = get_arch("internlm2-1.8b").reduced()
    env_b = [JETSON_NANO_H, JETSON_NANO_L, JETSON_TX2_H, JETSON_TX2_L]
    plan = HybridParallelismPlanner(
        period_costs(cfg, "pac", seq_len=32), env_b, 4, 2,
    ).plan()
    path = plan.save(str(tmp_path / "plan.json"))
    back = Plan.load(path)
    assert back.describe() == plan.describe()
    assert back.minibatch_latency == pytest.approx(plan.minibatch_latency)
    assert back.stage_partition() == plan.stage_partition()
    for a, b in zip(plan.stages, back.stages):
        assert (a.fwd_time, a.bwd_time) == pytest.approx((b.fwd_time, b.bwd_time))
        assert a.devices == b.devices
    assert simulate_plan(back)["minibatch_time"] == pytest.approx(
        simulate_plan(plan)["minibatch_time"])


def test_plan_json_rejects_unknown_version():
    with pytest.raises(ValueError):
        Plan.from_json('{"version": 99}')


# ---------------------------------------------------------------------------
# Execution equivalence (subprocess: needs >1 device)
# ---------------------------------------------------------------------------

_UNIFORM_BITWISE = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs import get_arch, list_archs
    from repro.core import steps
    from repro.core.parallel_adapters import init_adapter
    from repro.core.planner import StagePartition
    from repro.core.mesh import make_edge_mesh
    from repro.models import backbone as bb

    cfg = get_arch("internlm2-1.8b").reduced()   # 2 periods
    mesh = make_edge_mesh(2, 2)
    bp = bb.init_backbone(jax.random.PRNGKey(0), cfg)
    ap = init_adapter(jax.random.PRNGKey(1), cfg, r=4)
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(2), (8, 16), 0, cfg.vocab),
        "labels": jax.random.randint(jax.random.PRNGKey(3), (8, 16), 0, cfg.vocab),
    }
    part = StagePartition(boundaries=(0, 1, 2),
                          samples_per_device=((2, 2), (2, 2)), n_micro=2)
    assert part.is_uniform
    l_ref, g_ref, acts_ref = steps.pipeline_pac_loss_and_grads(
        bp, ap, batch, cfg=cfg, mesh=mesh, n_micro=2, r=4)
    l_pl, g_pl, acts_pl = steps.pipeline_pac_loss_and_grads(
        bp, ap, batch, cfg=cfg, mesh=mesh, n_micro=2, r=4, partition=part)
    assert np.array_equal(np.asarray(l_ref), np.asarray(l_pl)), "loss not bit-identical"
    for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_pl)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), "grads not bit-identical"
    for a, b in zip(jax.tree.leaves(acts_ref), jax.tree.leaves(acts_pl)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), "acts not bit-identical"
    print("UNIFORM_BITWISE_OK")
    """
)


def test_uniform_plan_is_bit_identical_to_stages_path():
    """The equivalence bar for uniform plans is exact: same stage function,
    same stacking, same collectives."""
    assert "UNIFORM_BITWISE_OK" in _run_sub(_UNIFORM_BITWISE)


_RAGGED_EQUIV = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=3"
    import dataclasses, functools
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs import get_arch, list_archs
    from repro.core import steps
    from repro.core.activation_cache import ActivationCache
    from repro.core.parallel_adapters import init_adapter
    from repro.core.planner import (
        HybridParallelismPlanner, JETSON_NANO_H, JETSON_NANO_L, JETSON_TX2_H,
        period_costs)
    from repro.core.mesh import make_plan_mesh
    from repro.models import backbone as bb
    from repro.optim import adamw_init

    cfg = get_arch("internlm2-1.8b").reduced()
    cfg = dataclasses.replace(cfg, name="plan5p", n_layers=5 * cfg.period)
    assert cfg.n_periods == 5

    # a real planner-made RAGGED plan: heterogeneous speeds + memory too
    # tight for one device force an uneven 3-stage split of 5 periods
    pc = period_costs(cfg, "pac", seq_len=16)
    need = sum(c.param_bytes + 2 * c.trainable_bytes for c in pc)
    env = [dataclasses.replace(d, memory_bytes=need * f)
           for d, f in ((JETSON_NANO_L, 0.5), (JETSON_TX2_H, 0.5), (JETSON_NANO_H, 0.5))]
    plan = HybridParallelismPlanner(pc, env, 4, 2).plan(max_stages=3)
    part = plan.stage_partition()
    assert part.n_stages == 3, part
    assert not part.is_uniform, f"want a ragged demo plan, got {part.periods_per_stage}"

    mesh = make_plan_mesh(part)   # (dp=1, stage=3)
    bp = bb.init_backbone(jax.random.PRNGKey(0), cfg)
    ap = init_adapter(jax.random.PRNGKey(1), cfg, r=4)
    B, S = 4, 16
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab),
        "labels": jax.random.randint(jax.random.PRNGKey(3), (B, S), 0, cfg.vocab),
    }

    loss_ref, grads_ref = jax.value_and_grad(
        lambda a: steps.pac_loss_fn(a, bp, cfg, batch, r=4))(ap)
    loss_pp, grads_pp, (b0, taps, bf) = steps.pipeline_pac_loss_and_grads(
        bp, ap, batch, cfg=cfg, mesh=mesh, n_micro=part.n_micro, r=4, partition=part)
    assert abs(float(loss_ref) - float(loss_pp)) < 1e-4, (float(loss_ref), float(loss_pp))
    gmax = max(float(jnp.max(jnp.abs(a - b)))
               for a, b in zip(jax.tree.leaves(grads_ref), jax.tree.leaves(grads_pp)))
    assert gmax < 1e-4, f"adapter grad mismatch {gmax}"

    # taps from the uneven boundaries assemble in true layer order
    bf_ref, taps_ref, b0_ref, _ = bb.backbone_forward(
        bp, cfg, batch, collect_taps=True, return_inputs=True)
    assert taps.shape == taps_ref.shape, (taps.shape, taps_ref.shape)
    assert float(jnp.max(jnp.abs(taps - taps_ref))) < 1e-4, "taps mismatch"
    assert float(jnp.max(jnp.abs(bf - bf_ref))) < 1e-4, "b_final mismatch"
    assert float(jnp.max(jnp.abs(b0 - b0_ref))) < 1e-6, "b0 mismatch"
    print("RAGGED_EQUIV_OK")

    # layer-ordered taps feed the cache: epoch>=2 adapter-only step from the
    # cached entries matches the single-device cached step (zero backbone fwd)
    ids = np.arange(B, dtype=np.int32)
    cache = ActivationCache(budget_bytes=1 << 30)
    cache.put_batch(ids, b0, taps, bf)
    hit = cache.get_batch(ids, with_final=True)
    assert hit is not None
    cb0, ctaps, cbf = (jnp.asarray(x) for x in hit)
    cached = {"b0": cb0, "taps": ctaps, "b_final": cbf, "labels": batch["labels"]}
    ref_cached = {"b0": b0_ref, "taps": taps_ref, "b_final": bf_ref,
                  "labels": batch["labels"]}
    opt = adamw_init(ap)
    stepN = functools.partial(steps.pac_cached_train_step, cfg=cfg, r=4)
    l_pipe, ap_pipe, _ = stepN(bp, ap, opt, cached)
    l_1dev, ap_1dev, _ = stepN(bp, ap, opt, ref_cached)
    assert abs(float(l_pipe) - float(l_1dev)) < 1e-4
    d = max(float(jnp.max(jnp.abs(a - b)))
            for a, b in zip(jax.tree.leaves(ap_pipe), jax.tree.leaves(ap_1dev)))
    assert d < 1e-3, f"cached-step adapter mismatch {d}"
    print("RAGGED_CACHE_OK")
    """
)


def test_ragged_plan_matches_single_device_and_feeds_cache():
    """10-periods-over-3-stages style ragged execution: loss/grads/taps ≡
    single device, and the taps round-trip the activation cache."""
    out = _run_sub(_RAGGED_EQUIV)
    assert "RAGGED_EQUIV_OK" in out
    assert "RAGGED_CACHE_OK" in out


# ---------------------------------------------------------------------------
# Trainer CLI: --plan auto end to end, --save-plan / replay
# ---------------------------------------------------------------------------


def _run_train(tmp, *extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # the CLI must force its own device count
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--reduced",
         "--epochs", "2", "--steps-per-epoch", "2", "--batch", "4",
         "--seq", "16", *extra],
        capture_output=True, text=True, env=env, timeout=600, cwd=str(tmp),
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_train_cli_plan_auto(tmp_path):
    """`--plan auto` plans, builds the mesh from the plan, executes epoch 1
    through the pipeline and epoch 2 from the cache."""
    out = _run_train(tmp_path, "--plan", "auto", "--pool", "4", "--micro", "2")
    assert "mesh: plan-driven dp=2×pp=2" in out
    assert "(plan-driven dp2xpp2)" in out
    assert "(cached pure-dp)" in out


def test_train_cli_plan_save_and_replay(tmp_path):
    """--save-plan emits a JSON the trainer replays to identical losses."""
    out1 = _run_train(tmp_path, "--plan", "auto", "--pool", "4",
                      "--micro", "2", "--save-plan", "plan.json")
    assert (tmp_path / "plan.json").exists()
    out2 = _run_train(tmp_path, "--plan", "plan.json", "--pool", "4")
    losses1 = [l for l in out1.splitlines() if l.startswith("epoch ")]
    losses2 = [l for l in out2.splitlines() if l.startswith("epoch ")]
    def strip_time(lines):
        return [l.split(" time=")[0] for l in lines]
    assert strip_time(losses1) == strip_time(losses2)
