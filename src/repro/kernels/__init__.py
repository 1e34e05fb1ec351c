"""Pallas TPU kernels for the compute hot-spots of the serving path.

Layout per kernel: ``kernel.py`` (pl.pallas_call + BlockSpec VMEM tiling),
``ops.py`` (jit'd public wrapper), ``ref.py`` (pure-jnp oracle used by the
allclose sweeps in tests/).

Kernels are TPU-TARGETED.  On the CPU (the test suite) they run under the
Pallas interpreter, asked for explicitly with ``interpret=True`` or
``REPRO_PALLAS_INTERPRET=1``; ``tests/test_tpu_compile.py`` compiles them
for a described TPU v5e, and ``chip_smoke.py`` runs them on the chip.
The kernel/XLA switch is ``cfg.attention_impl``.

* flash_attention      — causal/SWA prefill attention, online softmax
* decode_attention     — GQA flash-decode over a (ring-buffer) KV cache,
                         KV-chunk grid + log-sum-exp combine
* paged_decode_attention — flash-decode directly over the device-resident
                         page pool: a scalar-prefetched page table picks
                         each grid step's page, so non-contiguous
                         sequences decode in place (no dense gather)
* shared_prefix_attention — Hydragen-style: one pass over the SHARED prefix
                         KV for the whole batch (B·G-row matmuls feed the
                         MXU) + per-request suffix pass, LSE-combined.
                         This is the kernel-level realization of Halo's
                         KV-cache sharing.
* rglru_scan           — RG-LRU blocked linear-recurrence scan (Griffin)
"""

import os

import jax


def env_interpret(interpret: bool) -> bool:
    """Pallas interpret mode: the caller's flag, or
    REPRO_PALLAS_INTERPRET=1 (how CPU runs of the kernel suite ask for
    it).  The override on a TPU backend is an error — it would swap the
    device kernels for the interpreter without a trace in the results."""
    forced = os.environ.get("REPRO_PALLAS_INTERPRET", "") == "1"
    if forced and jax.default_backend() == "tpu":
        raise RuntimeError(
            "REPRO_PALLAS_INTERPRET=1 on a TPU backend: the kernels would "
            "run under the Pallas interpreter; unset it")
    return interpret or forced
