"""Pallas TPU kernels for the compute hot-spots (validated in interpret mode
on CPU, compiled for a described v5e chip in tests/test_tpu_compile.py, and
checked against their references on the chip by chip_smoke.py):

  flash_attention/  blockwise fused attention (causal, sliding-window, GQA)
  flash_decode/     single-query attention over a padded, kv_valid-masked
                    KV cache (split-KV online softmax — the serving hot path)
  ssd_scan/         Mamba2 SSD chunked scan, fused forward and backward
                    with VMEM-carried state (the TPU training path)
  mtsl_update/      fused per-component-LR update (the paper's eta * g step)

Each has kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd wrapper) and
ref.py (pure-jnp oracle used by tests and by the CPU/dry-run math path).
platform.py picks compiled (TPU) or interpret (CPU) mode.
"""
