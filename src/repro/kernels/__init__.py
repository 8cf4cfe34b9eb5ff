"""Pallas TPU kernels for the paper's compute hot-spots.

Each kernel has a pure-jnp oracle in :mod:`repro.kernels.ref` and is
allclose-pinned to it in ``tests/test_kernels.py`` /
``tests/test_cached_step.py``. Shared conventions:

* **interpret mode** — every kernel takes ``interpret=``; ``True`` runs
  the kernel body through the Pallas interpreter (bit-accurate, not
  fast), which is how the CPU tests run them. ``None`` (the
  ``cached_step``/``paged_attention``/OpSet default) compiles for the TPU
  and interprets only when JAX's default backend is not a TPU — e.g.
  under ``JAX_PLATFORMS=cpu``. ``tests/test_tpu_compile.py`` compiles
  each main-path kernel for a described v5e chip.
* **ragged shapes** — public entry points either pad-and-slice
  non-divisible dims (``adapter_fuse``, everything in ``cached_step``)
  or clamp block sizes and assert divisibility (``quant_matmul``,
  ``flash_attention`` — their callers control the shapes); each
  docstring says which.
* **dtypes** — inputs may be f32/bf16 (plus int8 payloads where
  documented); the MXU accumulates in f32
  (``preferred_element_type``) and outputs cast back at the epilogue.

Modules:

* ``cached_step`` — the epoch≥2 hot path: fused dequant×adapter λ-mix
  + blockwise LM-head cross-entropy, with custom VJPs (this is what
  ``--kernels pallas`` runs).
* ``quant_matmul`` — ``x @ dequant(Wq)`` for INT8/INT4 block-absmax
  weights (paper §IV-D).
* ``adapter_fuse`` — single λ-mix combine for f32 taps.
* ``flash_attention`` — causal/windowed/soft-capped attention.
* ``paged_attention`` — paged-KV decode attention (the serving core).
* ``ref`` — the jnp oracles.
"""
