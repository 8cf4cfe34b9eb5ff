"""The bridge to the program: its configuration, session and weights.

The harness hands the program the benchmark's weights (``bench/weights``)
through the program's own public pieces: the registry's ``ArchConfig``,
``RunSpec`` → ``EdgeSession``, ``quantize_tree`` for the frozen backbone
and ``adamw_init`` for the adapter's optimizer state.
"""

from __future__ import annotations

import gc

import jax
import jax.numpy as jnp

from bench import weights as W

# arch keys of a configuration file and the ArchConfig attribute each is
_ARCH_ATTRS = {"n_layers": "n_layers", "d_model": "d_model", "n_heads": "n_heads",
               "n_kv_heads": "n_kv_heads", "head_dim": "hd", "d_ff": "d_ff",
               "vocab": "vocab", "rope_theta": "rope_theta", "norm_eps": "norm_eps"}


def program_arch(config: dict) -> str:
    """The name the benchmark registers its configuration under in the
    program's registry (never one of the program's own entries)."""
    return "bench." + config["name"]


def arch_config(config: dict):
    """The program's ArchConfig for ``config``, registered from the file
    alone: a dense decoder of attention layers at the file's widths."""
    from repro.configs import ArchConfig, LayerSpec, register

    arch = config["arch"]
    return register(ArchConfig(
        name=program_arch(config), family="dense", n_layers=arch["n_layers"],
        d_model=arch["d_model"], n_heads=arch["n_heads"], n_kv_heads=arch["n_kv_heads"],
        head_dim=arch["head_dim"], d_ff=arch["d_ff"], vocab=arch["vocab"],
        pattern=(LayerSpec(kind="attn"),), rope_theta=arch["rope_theta"],
        norm_eps=arch["norm_eps"], source=config["source"]))


def check_adapter(config: dict, cfg) -> None:
    from repro.core.parallel_adapters import adapter_config

    acfg = adapter_config(cfg, config["run"]["r"])
    got = {k: getattr(acfg, _ARCH_ATTRS[k]) for k in config["adapter"]}
    if got != config["adapter"]:
        raise ValueError(f"side network widths {got} differ from the file's "
                         f"{config['adapter']}")


def run_spec(config: dict, traffic: dict, seed: int, **override):
    from repro.runtime import RunSpec

    run = config["run"]
    fields = dict(
        arch=program_arch(config), quant=run["quant"], kernels=run["kernels"],
        cache_compress=run["cache_compress"], r=run["r"], lr=config["optimizer"]["lr"],
        batch=traffic["batch"], seq=traffic["seq"],
        steps_per_epoch=traffic["rows_per_job"] // traffic["batch"], epochs=1,
        cache_budget_mb=traffic["cache_budget_mb"], seed=seed % 2 ** 31)
    fields.update(override)
    return RunSpec(**fields)


def _same_tree(a, b, what: str) -> None:
    shapes = lambda t: [(x.shape, str(x.dtype)) for x in jax.tree.leaves(t)]
    if jax.tree.structure(a) != jax.tree.structure(b) or shapes(a) != shapes(b):
        raise ValueError(f"the benchmark's {what} does not have the program's layout")


def make_backbone(config: dict, seed: int, bits: int):
    """The benchmark's backbone from the seed, quantised by the program's
    ``quantize_tree`` one layer at a time, then stacked: at most one
    layer's float32 weights are live, so the build's peak stays well
    under the program's own."""
    from repro.core.quantization import quantize_tree

    arch, key = config["arch"], W.seed_key(seed, W.STREAM_BACKBONE)
    n = arch["n_layers"]
    # a (1, ...) slice of a stacked leaf is quantised where the stacked
    # leaf of n layers would be: n * size >= quant_min_size
    min_size = -(-config["quant_min_size"] // n)

    @jax.jit
    def layer(k, i):
        one = jax.tree.map(lambda x: x[None], W.make_layer(k, arch, i))
        return quantize_tree(W.nest(one), bits=bits, min_size=min_size)

    layers = [layer(key, jnp.int32(i)) for i in range(n)]
    blocks = jax.jit(lambda ls: jax.tree.map(lambda *xs: jnp.concatenate(xs), *ls))(layers)
    del layers
    rest = jax.jit(lambda k: quantize_tree(W.make_rest(k, arch), bits=bits,
                                           min_size=config["quant_min_size"]))(key)
    return dict(rest, blocks=[blocks])


def open_session(config: dict, traffic: dict, seed: int, marks=None, **override):
    """An open EdgeSession holding the benchmark's weights (quantised by
    the program) and initial adapter, with fresh optimizer state.
    ``marks``, where given, gets the clock (``harness.now``) at which the
    program's open and the benchmark's weights were done."""
    from bench.harness import now
    from repro.runtime import EdgeSession

    cfg = arch_config(config)
    check_adapter(config, cfg)
    spec = run_spec(config, traffic, seed, **override)
    session = EdgeSession(spec).open()
    if marks is not None:
        marks["program_open"] = now()
    own_backbone, own_adapter = session.backbone, session.adapter
    layout = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), own_backbone)
    session.backbone = session.adapter = session.opt = own_backbone = None
    gc.collect()
    arch = config["arch"]
    session.backbone = make_backbone(config, seed, spec.quant)
    _same_tree(session.backbone, layout, "backbone")
    adapter = jax.jit(lambda k: W.make_adapter(k, arch, config["adapter"]))(
        W.seed_key(seed, W.STREAM_ADAPTER))
    _same_tree(adapter, own_adapter, "adapter")
    del own_adapter
    set_adapter(session, adapter)
    if marks is not None:
        marks["weights"] = now()
    return session


def set_adapter(session, adapter) -> None:
    from repro.optim import adamw_init

    session.adapter = jax.device_put(adapter)
    session.opt = adamw_init(session.adapter)
