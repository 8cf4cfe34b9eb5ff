"""Edge-mesh shardings, data pipeline, checkpoint, optimizer units."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import load_checkpoint, save_checkpoint
from repro.compat import abstract_mesh
from repro.configs import get_arch, list_archs
from repro.core import mesh as mesh_mod
from repro.core.activation_cache import COMPRESS_POLICIES, ActivationCache
from repro.core.parallel_adapters import init_adapter
from repro.core.quantization import QTensor, quantize_tree
from repro.data import DataPipeline, SyntheticPersonalCorpus, glue_like_task
from repro.models import backbone as bb
from repro.optim import adamw_init, adamw_update, clip_by_global_norm, cosine_schedule


def _leaves(tree):
    return jax.tree.leaves(tree, is_leaf=lambda x: isinstance(x, QTensor))


def _batch_entry(axes):
    return tuple(axes) if len(axes) > 1 else axes[0]


@pytest.mark.parametrize("arch", list_archs())
def test_edge_shardings_cover_every_leaf(arch):
    """``cached_step_shardings`` on a (dp 2, stage 2) mesh: every leaf of
    an INT8 backbone (each QTensor's payload and scales), the adapter,
    its AdamW state and a cached batch gets a sharding; parameters are
    replicated; every cached leaf's batch dim lies on the cached axes."""
    cfg = get_arch(arch).reduced()
    key = jax.random.PRNGKey(0)
    bp = jax.eval_shape(lambda: quantize_tree(bb.init_backbone(key, cfg), bits=8))
    ap = jax.eval_shape(lambda: init_adapter(key, cfg, r=4))
    opt = jax.eval_shape(adamw_init, ap)
    B, S, d, n_p = 8, 16, cfg.d_model, cfg.n_periods
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    cached = {"b0": f32(B, S, d), "b_final": f32(B, S, d),
              "taps": {"q": jax.ShapeDtypeStruct((n_p, B, S, d), jnp.int8),
                       "scale": f32(n_p, B, S, 1)},
              "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    mesh = abstract_mesh((2, 2), ("dp", "stage"))
    shardings = mesh_mod.cached_step_shardings(bp, ap, opt, cached, mesh)

    qtensors = [l for l in _leaves(bp) if isinstance(l, QTensor)]
    assert qtensors, "the INT8 backbone must hold quantized leaves"
    for tree, sh in zip((bp, ap, opt), shardings[:3]):
        assert jax.tree.structure(tree) == jax.tree.structure(sh)
        for spec in jax.tree.leaves(sh):
            assert isinstance(spec, NamedSharding) and spec.spec == P()
    # each QTensor's payload and scales are two leaves, each sharded
    assert len(jax.tree.leaves(shardings[0])) == len(_leaves(bp)) + len(qtensors)
    for q in jax.tree.leaves(shardings[0], is_leaf=lambda x: isinstance(x, QTensor)):
        if isinstance(q, QTensor):
            assert isinstance(q.q, NamedSharding) and isinstance(q.scale, NamedSharding)
    axes = mesh_mod.cached_batch_axes(cached, mesh)
    assert axes == ("dp", "stage")
    flat, _ = jax.tree_util.tree_flatten_with_path(shardings[3])
    assert len(flat) == len(jax.tree.leaves(cached)) == 5
    for path, spec in flat:
        bdim = 1 if mesh_mod.path_names(path)[0] == "taps" else 0
        assert spec.spec[bdim] == _batch_entry(axes), (path, spec.spec)
        assert all(e is None for i, e in enumerate(spec.spec) if i != bdim)


# (dp, stage, batch) -> the axes the cached batch must shard over
_LAYOUTS = {
    "dp2-stage1": (2, 1, 8, ("dp", "stage")),
    "dp1-stage2": (1, 2, 8, ("dp", "stage")),
    "dp2-stage2-b8": (2, 2, 8, ("dp", "stage")),
    "dp2-stage2-b6": (2, 2, 6, ("dp",)),
}


@pytest.mark.parametrize("layout", list(_LAYOUTS))
@pytest.mark.parametrize("policy", COMPRESS_POLICIES)
def test_cached_batch_specs(policy, layout):
    """The cached batch as ``ActivationCache.get_batch`` hands it back
    (int8 in storage form) shards its batch dim over the data axes, with
    ``stage`` joining only where the batch divides the whole pool."""
    dp, stages, B, want = _LAYOUTS[layout]
    n_p, S, d = 2, 4, 128
    rng = np.random.default_rng(0)
    cache = ActivationCache(budget_bytes=1 << 24, compress=policy)
    ids = np.arange(B)
    cache.put_batch(ids, rng.standard_normal((B, S, d), np.float32),
                    rng.standard_normal((n_p, B, S, d), np.float32),
                    rng.standard_normal((B, S, d), np.float32))
    b0, taps, bf = cache.get_batch(ids, with_final=True, dtype=None,
                                   compressed=policy == "int8")
    cached = {"b0": b0, "taps": taps, "b_final": bf,
              "labels": np.zeros((B, S), np.int32)}
    assert isinstance(taps, dict) == (policy == "int8")
    mesh = abstract_mesh((dp, stages), ("dp", "stage"))
    axes = mesh_mod.cached_batch_axes(cached, mesh)
    assert axes == want
    specs = mesh_mod.batch_specs(cached, axes)
    flat, _ = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, P))
    leaves = jax.tree.leaves(cached)
    assert len(flat) == len(leaves)
    for (path, spec), leaf in zip(flat, leaves):
        bdim = 1 if mesh_mod.path_names(path)[0] == "taps" else 0
        assert leaf.shape[bdim] == B
        assert spec == P(*[_batch_entry(want) if i == bdim else None
                           for i in range(leaf.ndim)]), (path, spec)


def test_corpus_learnable_structure_and_determinism():
    c1 = SyntheticPersonalCorpus(128, 16, 32, seed=7)
    c2 = SyntheticPersonalCorpus(128, 16, 32, seed=7)
    np.testing.assert_array_equal(c1.tokens, c2.tokens)
    b = c1.batch(np.arange(4))
    assert b["tokens"].shape == (4, 15) and b["labels"].shape == (4, 15)
    # labels are next tokens
    np.testing.assert_array_equal(b["labels"][:, :-1], b["tokens"][:, 1:])


def test_pipeline_epochs_shuffle_and_microbatch():
    corpus = glue_like_task("mrpc", 128, 16, scale=0.01)
    pipe = DataPipeline(corpus, global_batch=8, seed=3)
    e0 = [b["seq_ids"] for b in pipe.epoch(0)]
    e1 = [b["seq_ids"] for b in pipe.epoch(1)]
    assert not all(np.array_equal(a, b) for a, b in zip(e0, e1))
    mb = DataPipeline.microbatches(corpus.batch(np.arange(8)), 4)
    assert mb["tokens"].shape[:2] == (4, 2)


def test_dp_microbatches_layout_and_validation():
    """The hybrid-trainer batch layout: (B,) → (n_micro, mb) with dim 1
    contiguous-chunk shardable over dp ranks, and clear errors (not
    asserts) on indivisible CLI combinations."""
    corpus = glue_like_task("mrpc", 128, 16, scale=0.01)
    batch = corpus.batch(np.arange(8))
    mb = DataPipeline.dp_microbatches(batch, n_micro=2, dp=2)
    assert mb["tokens"].shape[:2] == (2, 4)
    # micro m, dp rank r owns samples [m*mb + r*mb/dp, ...): contiguous
    np.testing.assert_array_equal(mb["seq_ids"][0], batch["seq_ids"][:4])
    np.testing.assert_array_equal(mb["seq_ids"][1], batch["seq_ids"][4:])
    with pytest.raises(ValueError, match="divisible"):
        DataPipeline.dp_microbatches(batch, n_micro=2, dp=3)
    with pytest.raises(ValueError, match=">= 1"):
        DataPipeline.dp_microbatches(batch, n_micro=0, dp=1)


def test_checkpoint_roundtrip_nested(tmp_path):
    tree = {
        "a": jnp.arange(6).reshape(2, 3).astype(jnp.float32),
        "nested": {"b": [jnp.ones((4,)), jnp.zeros((2, 2), jnp.int32)]},
        "q": quantize_tree({"w": jax.random.normal(jax.random.PRNGKey(0), (128, 128))})["w"],
        "scalar": 3,
        "s": "hello",
    }
    p = str(tmp_path / "t.msgpack")
    n = save_checkpoint(p, tree)
    assert n > 0
    back = load_checkpoint(p)
    np.testing.assert_array_equal(np.asarray(back["a"]), np.asarray(tree["a"]))
    assert isinstance(back["q"], QTensor)
    np.testing.assert_array_equal(np.asarray(back["q"].q), np.asarray(tree["q"].q))
    assert back["scalar"] == 3 and back["s"] == "hello"


def test_adamw_descends_quadratic():
    params = {"w": jnp.array([5.0, -3.0])}
    state = adamw_init(params)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}
        params, state = adamw_update(params, grads, state, lr=5e-2, weight_decay=0.0)
    assert float(jnp.max(jnp.abs(params["w"]))) < 0.15


def test_clip_and_schedule():
    g, norm = clip_by_global_norm({"a": jnp.full((4,), 10.0)}, 1.0)
    assert float(jnp.sqrt(jnp.sum(jnp.square(g["a"])))) <= 1.0 + 1e-5
    lrs = [float(cosine_schedule(s, 100, 1.0, warmup_steps=10)) for s in range(100)]
    assert lrs[0] < lrs[9] and lrs[20] > lrs[90]
