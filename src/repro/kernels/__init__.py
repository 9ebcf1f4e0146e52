"""Pallas TPU kernels for the paper's hot spots, with jnp oracles.

Module map
----------
matmul           — tiled MXU matmul (the paper's mma/wgmma analog)
fp8_matmul       — fp8-storage matmul with scale epilogue (QGMMA)
flash_attention  — blockwise online-softmax attention (training/prefill)
paged_attention  — fused paged decode/chunk for serving: walks the
                   per-slot block table *inside* the kernel, DMAs only
                   the valid KV blocks of a head-major pool into VMEM,
                   contracts on the MXU, and reads e4m3 pools with
                   per-row scales (see its docstring for the layout
                   and the tolerance against the gather path)
dpx_kernel       — tropical matmul + Smith-Waterman (DPX analog)
async_pipeline   — double-buffered DMA pipeline (TMA analog)
ops              — jit'd public wrappers: tile autotuning/auto-fit,
                   oversize-tile ValueError guard, interpret default
                   (interpreted on the CPU, compiled on a TPU)
ref              — jnp oracles for the above

Validated on CPU via interpret=True against ref.py and the
models/attention oracles (tests/test_kernels.py, test_paged_kernel.py);
tests/test_tpu_compile.py compiles the paged kernels for a described
TPU v5e with Mosaic.
"""
