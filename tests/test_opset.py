"""ISSUE 7 acceptance: the OpSet dispatch layer.

Golden ref bit-identity (the ``ref`` OpSet IS the historical model
code), a property sweep over ragged shapes × backbone storage forms
({f32, bf16, int8, int4}) asserting pallas-interpret vs ref equivalence
of losses, adapter grads and emitted taps through ``backbone_forward``,
the storage-form tap contract with the activation cache, the staged
(shard_map) pipeline equivalence in a 4-device subprocess, the
prepare_block no-dequant guarantee, and the registry seam.
"""

import functools
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _propcheck import given, settings, strategies as st

from repro.configs import get_arch
from repro.core import steps
from repro.core.activation_cache import ActivationCache
from repro.core.opset import TAP_BLOCK, OpSet, get_opset, register_opset
from repro.core.parallel_adapters import init_adapter
from repro.core.quantization import QTensor, quantize, quantize_tree
from repro.models import backbone as bb
from repro.optim import adamw_init

KEY = jax.random.PRNGKey(0)
CFG = get_arch("internlm2-1.8b").reduced()
STORAGES = ("f32", "bf16", "int8", "int4")
# bf16 halves the mantissa on every weight; the two legs then disagree
# through the attention kernel's different accumulation order
_TOL = {"f32": 2e-4, "bf16": 3e-2, "int8": 2e-4, "int4": 2e-4}


@functools.lru_cache(maxsize=None)
def _backbone(storage: str):
    bp = bb.init_backbone(KEY, CFG)
    if storage == "f32":
        return bp
    if storage == "bf16":
        return jax.tree.map(
            lambda t: t.astype(jnp.bfloat16) if t.dtype == jnp.float32 else t, bp)
    return quantize_tree(bp, bits={"int8": 8, "int4": 4}[storage], min_size=1024)


def _batch(B, S, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    return {
        "tokens": jax.random.randint(ks[0], (B, S), 0, CFG.vocab),
        "labels": jax.random.randint(ks[1], (B, S), 0, CFG.vocab),
    }


def _pallas_loss(ap, bp, batch, tap_policy="f32", r=4):
    """The pallas epoch-1 adapter loss, composed exactly as
    ``pac_train_step(kernel_impl="pallas")`` builds it."""
    from repro.kernels.cached_step import cached_loss_parts

    ops = get_opset("pallas", tap_policy, True)
    b_final, taps, x, positions = bb.backbone_forward(
        bp, CFG, batch, collect_taps=True, return_inputs=True, ops=ops)
    b0_s, bf_s = ops.emit_tap(x), ops.emit_tap(b_final)
    b0_s, taps, bf_s = jax.lax.stop_gradient((b0_s, taps, bf_s))
    cached = {"b0": b0_s, "taps": taps, "b_final": bf_s, "labels": batch["labels"]}
    num, den = cached_loss_parts(
        bp, ap, CFG, cached, positions, r, impl="pallas", interpret=True)
    return num / jnp.maximum(den, 1)


# ---------------------------------------------------------------------------
# Golden: the ref OpSet is bit-identical to the historical defaults
# ---------------------------------------------------------------------------


def test_ref_opset_bit_identical_forward():
    """ops=None (the default) and the explicit ref OpSet produce the exact
    same bits — the refactor did not move the oracle."""
    bp = _backbone("int8")
    batch = _batch(2, 12)
    h0, taps0, x0, _ = bb.backbone_forward(
        bp, CFG, batch, collect_taps=True, return_inputs=True)
    h1, taps1, x1, _ = bb.backbone_forward(
        bp, CFG, batch, collect_taps=True, return_inputs=True,
        ops=get_opset("ref"))
    for a, b in ((h0, h1), (taps0, taps1), (x0, x1)):
        assert jnp.array_equal(a, b), "ref OpSet is not bit-identical"


def test_ref_opset_bit_identical_step():
    """pac_train_step's default and kernel_impl="ref" are the same step:
    identical loss bits, identical updated adapter bits."""
    bp, batch = _backbone("f32"), _batch(2, 12)
    ap = init_adapter(jax.random.PRNGKey(1), CFG, r=4)
    opt = adamw_init(ap)
    l0, ap0, _, acts0 = steps.pac_train_step(bp, ap, opt, batch, cfg=CFG, r=4)
    l1, ap1, _, acts1 = steps.pac_train_step(
        bp, ap, opt, batch, cfg=CFG, r=4, kernel_impl="ref")
    assert jnp.array_equal(l0, l1)
    for a, b in zip(jax.tree.leaves(ap0), jax.tree.leaves(ap1)):
        assert jnp.array_equal(a, b)
    for a, b in zip(jax.tree.leaves(acts0), jax.tree.leaves(acts1)):
        assert jnp.array_equal(a, b)


# ---------------------------------------------------------------------------
# Property sweep: ragged shapes × storage forms, pallas-interpret ≡ ref
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("storage", STORAGES)
@settings(max_examples=3, deadline=None)
@given(B=st.integers(1, 3), S=st.sampled_from([5, 17, 33]))
def test_epoch1_parity_losses_grads_taps(storage, B, S):
    """Loss, adapter grads, and the emitted taps of the pallas-interpret
    epoch-1 forward match the ref oracle on the SAME weights, for every
    backbone storage form and ragged (B, S)."""
    bp, batch = _backbone(storage), _batch(B, S, seed=B * 100 + S)
    ap = init_adapter(jax.random.PRNGKey(1), CFG, r=4)
    tol = _TOL[storage]

    l_ref, g_ref = jax.value_and_grad(steps.pac_loss_fn)(
        ap, bp, CFG, batch, r=4)
    l_pal, g_pal = jax.value_and_grad(_pallas_loss)(ap, bp, batch, r=4)
    assert abs(float(l_ref) - float(l_pal)) < tol, (storage, float(l_ref), float(l_pal))
    for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_pal)):
        scale = max(float(jnp.max(jnp.abs(a))), 1e-3)
        assert float(jnp.max(jnp.abs(a - b))) < tol * max(scale, 1.0), storage

    # taps (f32 tap policy: emit_tap is identity) — the frozen hiddens
    # themselves agree between the two compute paths
    _, taps_ref = bb.backbone_forward(bp, CFG, batch, collect_taps=True)
    _, taps_pal = bb.backbone_forward(
        bp, CFG, batch, collect_taps=True, ops=get_opset("pallas", "f32", True))
    diff = float(jnp.max(jnp.abs(
        taps_ref.astype(jnp.float32) - taps_pal.astype(jnp.float32))))
    ref_mag = max(float(jnp.max(jnp.abs(taps_ref.astype(jnp.float32)))), 1.0)
    assert diff < tol * 10 * ref_mag, (storage, diff, ref_mag)


# ---------------------------------------------------------------------------
# Storage-form taps: quantized at the tap site, adopted by the cache
# ---------------------------------------------------------------------------


def test_int8_taps_are_cache_storage_form():
    """tap_policy="int8" emits {q, scale} == the cache's own compression
    of the same hidden, and put_batch adopts the payload without a second
    quantization round-trip."""
    bp, batch = _backbone("int8"), _batch(2, 12)
    ops = get_opset("pallas", "int8", True)
    b_final, taps, x, _ = bb.backbone_forward(
        bp, CFG, batch, collect_taps=True, return_inputs=True, ops=ops)
    assert isinstance(taps, dict) and set(taps) == {"q", "scale"}
    assert taps["q"].dtype == jnp.int8

    # bit-identical to what the f32-tap path + cache-side compression makes
    _, taps_f32 = bb.backbone_forward(
        bp, CFG, batch, collect_taps=True, ops=get_opset("pallas", "f32", True))
    qt = quantize(taps_f32.astype(jnp.float32), bits=8, block=TAP_BLOCK)
    assert jnp.array_equal(taps["q"], qt.q)
    # the scale reduction fuses into the forward trace — last-ulp only
    np.testing.assert_allclose(
        np.asarray(taps["scale"]), np.asarray(qt.scale), rtol=1e-6)

    # the cache adopts storage-form entries as-is
    cache = ActivationCache(budget_bytes=1 << 30, compress="int8")
    b0_s, bf_s = ops.emit_tap(x), ops.emit_tap(b_final)
    cache.put_batch(list(range(2)), b0_s, taps, bf_s, orig_last=CFG.d_model)
    cb0, ctaps, _ = cache.get_batch(list(range(2)), with_final=True, compressed=True)
    assert np.array_equal(np.asarray(ctaps["q"]), np.asarray(taps["q"]))
    assert np.array_equal(np.asarray(cb0["q"]), np.asarray(b0_s["q"]))

    # a non-int8 cache refuses a quantized payload instead of guessing
    with pytest.raises(ValueError):
        ActivationCache(budget_bytes=1 << 30, compress="f32").put_batch(
            [0, 1], b0_s, taps, bf_s, orig_last=CFG.d_model)


def test_int8_tap_loss_close_to_ref():
    """End-to-end epoch-1 step with storage-form taps: the loss carries
    only the int8 tap quantization error."""
    bp, batch = _backbone("int8"), _batch(2, 12)
    ap = init_adapter(jax.random.PRNGKey(1), CFG, r=4)
    opt = adamw_init(ap)
    l_ref, *_ = steps.pac_train_step(bp, ap, opt, batch, cfg=CFG, r=4)
    l_pal, _, _, (b0, taps, bf) = steps.pac_train_step(
        bp, ap, opt, batch, cfg=CFG, r=4, kernel_impl="pallas",
        tap_policy="int8", interpret=True)
    assert abs(float(l_ref) - float(l_pal)) < 5e-2
    assert isinstance(taps, dict) and taps["q"].dtype == jnp.int8
    assert isinstance(b0, dict) and isinstance(bf, dict)


# ---------------------------------------------------------------------------
# prepare_block: the pallas path never dequantizes the matmul weights
# ---------------------------------------------------------------------------


def test_prepare_block_keeps_matmul_weights_quantized():
    bp = _backbone("int8")
    spec = CFG.pattern[0]
    assert spec.kind == "attn"
    p = jax.tree.map(lambda t: t[0], bp["blocks"][0])  # period 0's block
    out = get_opset("pallas", "f32", True).prepare_block(p, spec)
    for name in ("wq", "wk", "wv", "wo"):
        assert isinstance(out["mixer"][name], QTensor), name
    for name in ("wi", "wg", "wo"):
        assert isinstance(out["ffn"][name], QTensor), name
    # norm gains have no quantized kernel — those ARE dequantized
    for leaf in jax.tree.leaves(out["ln1"]) + jax.tree.leaves(out["ln2"]):
        assert not isinstance(leaf, QTensor)
    # the ref OpSet dequantizes everything (the historical idiom)
    for leaf in jax.tree.leaves(get_opset("ref").prepare_block(p, spec)):
        assert not isinstance(leaf, QTensor)


# ---------------------------------------------------------------------------
# Registry seam
# ---------------------------------------------------------------------------


def test_registry_unknown_opset_raises():
    with pytest.raises(ValueError, match="unknown OpSet"):
        get_opset("not-a-kernel-impl")


def test_registry_extension_point():
    class _Dummy(OpSet):
        name = "dummy-test"

        def __init__(self, tap_policy="f32", interpret=None):
            self.tap_policy = tap_policy

    register_opset("dummy-test", _Dummy)
    assert isinstance(get_opset("dummy-test", "bf16"), _Dummy)
    # instances are cached per (name, tap_policy, interpret)
    assert get_opset("dummy-test", "bf16") is get_opset("dummy-test", "bf16")


def test_models_layer_never_imports_kernels():
    """The seam the CI grep enforces: model code reaches kernels only
    through the OpSet registry (docstring mentions are fine; import
    statements are not)."""
    import re

    pat = re.compile(r"^\s*(from\s+repro\.kernels|import\s+repro\.kernels"
                     r"|from\s+repro\s+import\s+.*\bkernels\b)")
    root = os.path.join(os.path.dirname(__file__), "..", "src", "repro", "models")
    for fn in os.listdir(root):
        if fn.endswith(".py"):
            with open(os.path.join(root, fn)) as f:
                for i, line in enumerate(f, 1):
                    assert not pat.match(line), f"{fn}:{i}: {line.strip()}"


# ---------------------------------------------------------------------------
# Staged pipeline: shard_map epoch-1 on the pallas OpSet (4-dev subprocess)
# ---------------------------------------------------------------------------

_PIPELINE_PARITY = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp
    from repro.configs import get_arch
    from repro.core import steps
    from repro.core.parallel_adapters import init_adapter
    from repro.core.quantization import quantize_tree
    from repro.core.mesh import make_edge_mesh
    from repro.models import backbone as bb

    cfg = get_arch("internlm2-1.8b").reduced()
    mesh = make_edge_mesh(2, 2)
    bp = quantize_tree(bb.init_backbone(jax.random.PRNGKey(0), cfg),
                       bits=8, min_size=1024)
    ap = init_adapter(jax.random.PRNGKey(1), cfg, r=4)
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0, cfg.vocab),
        "labels": jax.random.randint(jax.random.PRNGKey(3), (4, 16), 0, cfg.vocab),
    }

    l_ref, g_ref = jax.value_and_grad(
        lambda a: steps.pac_loss_fn(a, bp, cfg, batch, r=4))(ap)

    # f32 taps: tight parity of the staged pallas forward against ref
    l_pal, g_pal, (b0, taps, bf) = steps.pipeline_pac_loss_and_grads(
        bp, ap, batch, cfg=cfg, mesh=mesh, n_micro=2, r=4,
        kernel_impl="pallas", tap_policy="f32", interpret=True)
    assert abs(float(l_ref) - float(l_pal)) < 1e-3, (float(l_ref), float(l_pal))
    gmax = max(
        float(jnp.max(jnp.abs(a - b)))
        for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_pal)))
    assert gmax < 1e-3, f"adapter grad mismatch {gmax}"
    print("PIPELINE_PALLAS_F32_OK")

    # int8 taps: storage-form pytrees flow through the staged forward
    l_q, g_q, (b0q, tapsq, bfq) = steps.pipeline_pac_loss_and_grads(
        bp, ap, batch, cfg=cfg, mesh=mesh, n_micro=2, r=4,
        kernel_impl="pallas", tap_policy="int8", interpret=True)
    assert isinstance(tapsq, dict) and tapsq["q"].dtype == jnp.int8, tapsq
    assert isinstance(b0q, dict) and isinstance(bfq, dict)
    assert abs(float(l_ref) - float(l_q)) < 5e-2, (float(l_ref), float(l_q))
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree.leaves(g_q))
    print("PIPELINE_PALLAS_INT8_OK")
    """
)


def test_staged_pipeline_pallas_parity():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", _PIPELINE_PARITY],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "PIPELINE_PALLAS_F32_OK" in out.stdout
    assert "PIPELINE_PALLAS_INT8_OK" in out.stdout
