"""Pallas TPU kernels for the compute hot spots.

Data plane (the paper's local strategies, TPU-adapted — DESIGN.md §3.1):
  segmented_scan — grouped aggregation (Reduce/CoGroup local strategy)
  sorted_probe   — sorted-search join probe (Match local strategy)

Model plane:
  flash_attention — fused causal/windowed GQA attention
  rwkv6_scan      — chunked WKV6 data-dependent-decay recurrence
  linear_scan     — diagonal linear recurrence (RG-LRU)

Each kernel file: pl.pallas_call + explicit BlockSpec VMEM tiling.
`ops.py` holds the jit'd public wrappers; `ref.py` the pure-jnp oracles.
Kernels run interpret=True on non-TPU backends (validated in tests);
compiled mode targets TPU v5e.  The data-plane kernels do not compile for
it yet (`ops.TPU_KERNEL_REFUSALS`), so `use_kernels=True` raises on a TPU.
"""
