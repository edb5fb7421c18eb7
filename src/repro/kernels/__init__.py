"""Pallas TPU kernels for the model plane, and the fused-span executor.

Model plane:
  flash_attention — fused causal/windowed GQA attention
  rwkv6_scan      — chunked WKV6 data-dependent-decay recurrence
  linear_scan     — diagonal linear recurrence (RG-LRU)

Each kernel file: pl.pallas_call + explicit BlockSpec VMEM tiling.
`ops.py` holds the jit'd public wrappers; `ref.py` the pure-jnp oracles
(including those of the data plane's segmented scan, segment reduction
and sorted probe, which `core.scans` and `core.udf.JitSegmentOps` are
tested against).  Kernels run interpret=True on non-TPU backends
(validated in tests); compiled mode targets TPU v5e.

Data plane: every stage body is composed XLA (`core.masked`, `core.scans`);
`megakernel.py` fuses runs of stages into one span body, inlined into the
same XLA program (DESIGN.md §10).
"""
