"""The main-path Pallas kernels compile for a TPU v5e at internlm2-1.8b widths.

Every other kernel test runs the Pallas interpreter, which skips
Mosaic's tiling and VMEM checks. Here each kernel is lowered and
compiled by the TPU compiler for a described (not attached) v5e chip,
from shapes alone, and the compiled program must hold the kernel as a
``tpu_custom_call``. Widths are those of configs/internlm2_1_8b.py at
batch 4 × seq 512 (2048 tokens): d=2048, GQA 16/8 heads of 128,
d_ff=8192, vocab 92544, adapter width d/8.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.cached_step import dq_adapter_mix, lmhead_ce
from repro.kernels.flash_attention import flash_attention_tpu
from repro.kernels.paged_attention import paged_attention
from repro.kernels.quant_matmul import quant_matmul

T, D, DA, V, FF = 2048, 2048, 256, 92544, 8192  # tokens, d, d/r, vocab, d_ff


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent compile
    cache off: a TPU executable written here could not be read back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _qmm(bits):
    return lambda x, q, s: quant_matmul(x, q, s, bits=bits)


def _mix(x, w, a):
    return dq_adapter_mix(x, w, a, jnp.float32(0.7), interpret=False)


def _mix_int8(q, s, w, a):
    return _mix({"q": q, "scale": s}, w, a)


def _mix_grad(fwd):
    def f(*args):
        *entry, w, a = args
        return jax.grad(
            lambda w_, a_: jnp.sum(fwd(*entry, w_, a_)), argnums=(0, 1))(w, a)
    return f


def _ce(h, w, lab):
    return lmhead_ce(h, w, lab, interpret=False)


def _ce_grad(h, w, lab):
    return jax.grad(lambda h_: jnp.sum(_ce(h_, w, lab)))(h)


def _paged(q, kp, vp, ks, vs, bt, ln):
    return paged_attention(q, kp, vp, bt, ln, k_scale=ks, v_scale=vs,
                           interpret=False)


f32, i8, bf16, i32 = jnp.float32, jnp.int8, jnp.bfloat16, jnp.int32
_MIX_INT8 = [((T, D), i8), ((T, D // 128), f32), ((D, DA), f32), ((T, DA), f32)]
_MIX_BF16 = [((T, D), bf16), ((D, DA), f32), ((T, DA), f32)]
_CE = [((T, D), f32), ((D, V), f32), ((T,), i32)]
PAGES, PAGE, HKV, HD = 256, 8, 8, 128

CASES = {
    # epoch-1 projections: wq (d→d), wk/wv (d→Hkv·hd), wi (d→d_ff),
    # the MLP's wo (d_ff→d), and one decode row block of 4 tokens
    "quant_matmul_int8_wq": (
        _qmm(8), [((T, D), f32), ((D, D), i8), ((D, D // 128), f32)]),
    "quant_matmul_int8_wk": (
        _qmm(8), [((T, D), f32), ((D, 1024), i8), ((D, 8), f32)]),
    "quant_matmul_int8_wi": (
        _qmm(8), [((T, D), f32), ((D, FF), i8), ((D, FF // 128), f32)]),
    "quant_matmul_int8_wo": (
        _qmm(8), [((T, FF), f32), ((FF, D), i8), ((FF, D // 128), f32)]),
    "quant_matmul_int8_decode": (
        _qmm(8), [((4, D), f32), ((D, FF), i8), ((D, FF // 128), f32)]),
    "quant_matmul_int4_wi": (
        _qmm(4), [((T, D), f32), ((D, FF // 2), i8), ((D, FF // 128), f32)]),
    "flash_attention_s512": (
        lambda q, k, v: flash_attention_tpu(q, k, v, causal=True),
        [((4 * 16, 512, 128), f32)] * 3),
    "dq_adapter_mix_int8": (_mix_int8, _MIX_INT8),
    "dq_adapter_mix_int8_grad": (_mix_grad(_mix_int8), _MIX_INT8),
    "dq_adapter_mix_bf16": (_mix, _MIX_BF16),
    "dq_adapter_mix_bf16_grad": (_mix_grad(_mix), _MIX_BF16),
    "lmhead_ce": (_ce, _CE),
    "lmhead_ce_grad": (_ce_grad, _CE),
    "paged_attention_int8": (_paged, [
        ((4, HKV, 2, HD), f32),
        ((PAGES, PAGE, HKV, HD), i8), ((PAGES, PAGE, HKV, HD), i8),
        ((PAGES, PAGE, HKV), f32), ((PAGES, PAGE, HKV), f32),
        ((4, 64), i32), ((4,), i32)]),
}


# the instruction names the device trace shows for the cached step's
# kernels, whatever autodiff transform they run under
KERNEL_NAMES = {
    "dq_adapter_mix_int8": ["dq_adapter_mix_fwd"],
    "dq_adapter_mix_int8_grad": ["dq_adapter_mix_dw"],
    "dq_adapter_mix_bf16": ["dq_adapter_mix_fwd"],
    "dq_adapter_mix_bf16_grad": ["dq_adapter_mix_dw"],
    "lmhead_ce": ["lmhead_ce_fwd"],
    "lmhead_ce_grad": ["lmhead_ce_fwd", "lmhead_ce_bwd"],
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, name
    customs = [ln.split(" = ", 1)[0].split()[-1] for ln in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    for kernel in KERNEL_NAMES.get(name, []):
        assert any(re.fullmatch(rf"%{kernel}\.\d+", c) for c in customs), (kernel, customs)


def test_cache_split_compiles_for_v5e(one_chip):
    """``ActivationCache.put_batch``'s device split at the capture cell's
    shapes (batch 4 × 1,024 tokens, 24 taps, d 2,048, int8 storage form)
    lowers for the chip: set-up's first put compiles it, not the window."""
    from repro.core.activation_cache import _split_on_device

    B, S, NP = 4, 1024, 24

    def form(*lead):
        return {"q": jax.ShapeDtypeStruct(lead + (S, D), i8, sharding=one_chip),
                "scale": jax.ShapeDtypeStruct(lead + (S, D // 128), f32, sharding=one_chip)}

    parts = (form(B), form(NP, B), form(B))
    _split_on_device.lower(parts).compile()
    seqs = jax.eval_shape(_split_on_device, parts)
    assert len(seqs) == B
    for b0, taps, bf in seqs:
        assert b0["q"].shape == bf["q"].shape == (S, D)
        assert taps["q"].shape == (NP, S, D) and taps["scale"].shape == (NP, S, D // 128)
