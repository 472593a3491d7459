"""Pallas TPU kernels for the compute hot-spots of the system.

Each kernel is a package ``<name>/{kernel.py, ops.py, ref.py}``:

  window_attention   ViTDet window attention over the window-blocked
                     mixed-resolution sequence — the paper's §III hot path.
  flash_attention    global attention (ViTDet global blocks, LM prefill):
                     online-softmax tiling, GQA-aware.
  decode_attention   one-token GQA decode against a long KV cache
                     (flash-decode style blocked reduction over the cache).
  ssd_scan           Mamba-2 SSD: intra-chunk quadratic form + carried
                     inter-chunk state, sequential grid over chunks.
  mixed_res_pool     d x d average-pool patch downsampling (mixed-res
                     packing hot spot, §III-A).
  int8_matmul        int8 x int8 -> int32 blocked GEMM with per-channel
                     dequant epilogue — the quantized weight lane
                     (repro.quant) for QKV/MLP/head projections.

TPU is the TARGET (pl.pallas_call + BlockSpec VMEM tiling, MXU-aligned
block shapes).  Every ``kernel.py`` entry takes ``interpret`` as a
required argument; the ``ops.py`` wrappers pass ``interpret=True`` off
the TPU, where tests/test_kernels.py checks each kernel against its
``ref.py`` pure-jnp oracle across shapes and dtypes, and
tests/test_tpu_compile.py compiles the serving kernels at ViTDet-L
widths for a described v5e.  ``chip_smoke.py`` runs them on the chip.

The jnp model code paths remain the default for dry-run lowering (XLA
cost analysis reads the jnp HLO); ``ops.py`` wrappers are the swap-in
entry points on real TPU hardware (e.g. ``mamba2_forward(...,
use_kernel=True)``).

``dispatch.py`` is the model-facing router: hot paths take a
``backend`` argument ("auto" | "pallas" | "xla") and "auto" selects the
Pallas kernels on TPU while keeping the jnp paths elsewhere (interpret
mode stays a parity-testing tool).  See README.md.
"""
