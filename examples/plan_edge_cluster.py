"""Planner demo — paper Fig. 17: device grouping across models and pools —
plus plan-driven execution: the winning plan for a heterogeneous pool is
not just printed, it is *run*, end to end, on emulated edge devices.

The survey half is pure planning (Alg. 1 over the paper's Jetson
profiles, full-size models). The execution half plans a CPU-runnable
demo model at period granularity on the heterogeneous Env.B pool, saves
the winning (RAGGED) Plan, and replays it through the runtime layer: a
:class:`~repro.runtime.RunSpec` pointing at the plan file, executed by
an :class:`~repro.runtime.EdgeSession` — which derives the
:class:`StagePartition` (uneven layer boundaries and all), builds the
mesh from it, and trains a few real steps through the 1F1B pipeline.
Modelled vs executed latency are printed side by side.

    PYTHONPATH=src python examples/plan_edge_cluster.py [--quick] [--steps N]
"""

import argparse
import dataclasses
import os
import tempfile

POOL_SIZE = 4  # fake host devices for the execution half


def survey(archs=("t5-base-pac", "bart-large-pac", "t5-large-pac")):
    """The Fig. 17 sweep: hybrid vs pure-DP vs pure-PP across pools."""
    from repro.configs import get_arch
    from repro.core.pipeline import simulate_plan
    from repro.core.planner import (
        HybridParallelismPlanner,
        JETSON_NANO_H,
        JETSON_NANO_L,
        JETSON_TX2_H,
        JETSON_TX2_L,
        model_layer_costs,
        plan_pure_dp,
        plan_pure_pp,
    )

    pools = {
        "Env.A (4x nano-H)": [JETSON_NANO_H] * 4,
        "Env.B (het 4-dev)": [JETSON_NANO_H, JETSON_NANO_L, JETSON_TX2_H, JETSON_TX2_L],
        "8x nano-H": [JETSON_NANO_H] * 8,
    }

    for arch in archs:
        cfg = get_arch(arch)
        costs = model_layer_costs(cfg, "pac", seq_len=128)
        print(f"\n=== {arch} ({cfg.param_count()/1e9:.2f}B params), technique=PAC+ ===")
        for pool_name, devs in pools.items():
            plan = HybridParallelismPlanner(costs, devs, len(devs), 4).plan()
            sim = simulate_plan(plan)
            dp = plan_pure_dp(costs, devs, len(devs), 4)
            pp = plan_pure_pp(costs, devs, len(devs), 4)
            print(f"\n[{pool_name}] HP: {plan.minibatch_latency*1e3:.0f} ms/minibatch, "
                  f"bubble {sim['bubble_fraction']:.1%} | "
                  f"DP: {'OOM' if dp is None else f'{dp.minibatch_latency*1e3:.0f} ms'} | "
                  f"PP: {'OOM' if pp is None else f'{pp.minibatch_latency*1e3:.0f} ms'}")
            print(plan.describe())


PLANNED_MB = 4  # samples per micro-batch, both planned and executed
N_MICRO = 2


def build_demo_plan():
    """The 10-period demo model and its RAGGED Env.B plan (pure Python —
    safe before any JAX backend init)."""
    from repro.configs.base import ArchConfig, LayerSpec, register
    from repro.core.planner import (
        HybridParallelismPlanner,
        JETSON_NANO_H,
        JETSON_NANO_L,
        JETSON_TX2_H,
        JETSON_TX2_L,
        period_costs,
    )

    # registered so a RunSpec can name it (the session replays the plan)
    cfg = register(ArchConfig(
        name="plan-demo-10p", family="dense", n_layers=10, d_model=128,
        n_heads=4, n_kv_heads=4, d_ff=256, vocab=512,
        pattern=(LayerSpec(kind="attn"),), source="plan-execution demo",
    ))
    # Env.B speed ratios with memory budgets scaled to the demo model
    # (~6.8 MB): no single device can host all 10 periods, so Alg. 1 must
    # pipeline — and the heterogeneous speeds make the split RAGGED
    env_b = [
        dataclasses.replace(d, memory_bytes=3 * 2 ** 20)
        for d in (JETSON_NANO_H, JETSON_NANO_L, JETSON_TX2_H, JETSON_TX2_L)
    ]
    plan = HybridParallelismPlanner(
        period_costs(cfg, "pac", seq_len=32), env_b, PLANNED_MB, N_MICRO,
    ).plan(max_stages=3)
    return cfg, plan


def execute_winning_plan(n_steps: int = 3) -> dict:
    """Plan the demo model on Env.B, save the Plan, and *replay* it
    through the runtime layer (RunSpec → EdgeSession) for real.

    Returns {modelled_ms, executed_ms, compile_ms, stages, periods,
    ragged} so the heterogeneous benchmark can reuse this workload."""
    if n_steps < 1:
        raise ValueError(f"n_steps must be >= 1, got {n_steps}")

    from repro.core.pipeline import simulate_plan
    from repro.runtime import EdgeSession, EpochRunner, RunSpec, StepEvent

    cfg, plan = build_demo_plan()
    part = plan.stage_partition()
    sim = simulate_plan(plan)

    print(f"\n=== executing the winning Env.B plan for {cfg.name} "
          f"({cfg.n_periods} periods) ===")
    print(plan.describe())
    print(f"partition: boundaries={part.boundaries} "
          f"periods/stage={part.periods_per_stage} "
          f"{'uniform' if part.is_uniform else 'RAGGED (padded+masked stages)'}")

    # save → replay: the plan file is the contract the session executes
    # (the same round-trip the trainer's --save-plan / --plan do)
    fd, plan_path = tempfile.mkstemp(suffix=".json", prefix="env_b_plan_")
    os.close(fd)
    try:
        plan.save(plan_path)
        # execute the micro-batch size the plan was made for: mb == PLANNED_MB
        spec = RunSpec(
            arch=cfg.name, epochs=1, steps_per_epoch=n_steps + 1,
            batch=PLANNED_MB * N_MICRO, seq=32, r=8, lr=1e-3, init="random",
            plan=plan_path, pool=POOL_SIZE, micro=N_MICRO, use_cache=False,
        )
        times = []
        with EdgeSession(spec) as session:  # forces the fake pool pre-backend
            dp = session.exec_dp
            for rec in EpochRunner(session).events():
                if isinstance(rec, StepEvent):  # step 0 pays compilation
                    times.append(rec.wall_s)
                    print(f"  step {rec.index}: loss={rec.loss:.4f} "
                          f"wall={rec.wall_s*1e3:.0f}ms")
    finally:
        os.unlink(plan_path)
    print(f"modelled (Jetson Env.B): {sim['minibatch_time']*1e3:.1f} ms/minibatch, "
          f"bubble {sim['bubble_fraction']:.1%}")
    print(f"executed (CPU-emulated {dp}x{part.n_stages} mesh): "
          f"{min(times[1:])*1e3:.0f} ms/step best-of-{n_steps} "
          f"(different silicon — the point is the *same plan* drives both)")
    return {
        "modelled_ms": sim["minibatch_time"] * 1e3,
        "executed_ms": min(times[1:]) * 1e3,
        "compile_ms": times[0] * 1e3,
        "stages": part.n_stages,
        "periods": part.periods_per_stage,
        "ragged": not part.is_uniform,
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="skip the full-size survey (CI smoke)")
    ap.add_argument("--steps", type=int, default=3,
                    help="real train steps for the executed plan")
    args = ap.parse_args()

    # the survey is pure planning; the session forces its own device
    # pool before the backend comes up when the execution half runs
    if not args.quick:
        survey()
    execute_winning_plan(args.steps)


if __name__ == "__main__":
    main()
