"""Public wrappers for the Pallas kernels: padding, dtype policy, dispatch.

Every entry point pads inputs to the kernel's block multiples, calls the
pallas kernel (interpret mode automatically on non-TPU backends), and slices
the result back.  `KernelSegmentOps` adapts the segmented-scan kernel to the
SegmentOps interface consumed by KAT UDFs in the masked executor.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import flash_attention as _fa
from . import linear_scan as _ls
from . import rwkv6_scan as _rwkv
from . import segmented_scan as _ss
from . import sorted_probe as _sp
from ..core.udf import SegmentOps


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# what Mosaic answers when the `use_kernels` dataflow kernels are compiled
# for a TPU (tests/test_tpu_compile.py pins both as strict xfails)
TPU_KERNEL_REFUSALS = (
    "sorted_probe: float64 keys are refused ('64-bit types are not "
    "supported'), and with int32 keys its index maps return int64 under x64 "
    "(\"failed to legalize 'func.return' (i64, i32)\"); segmented_scan: the "
    "in-kernel associative_scan fails to lower (an MLIR 'slice' type error)")


def refuse_on_tpu(use_kernels: bool) -> None:
    """Raise when `use_kernels=True` would send the dataflow Pallas kernels
    (`sorted_probe`, `segmented_scan`) to the TPU compiler, which refuses
    both.  Off-TPU they run in interpret mode, as the CPU tests do."""
    if use_kernels and jax.default_backend() == "tpu":
        raise NotImplementedError(
            f"use_kernels=True on a TPU backend: {TPU_KERNEL_REFUSALS}; "
            "serve with use_kernels=False")


def _pad_to(x: jnp.ndarray, mult: int, axis: int, value=0):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value), n


# ---------------------------------------------------------------------------
# Segmented scan / segment reduce
# ---------------------------------------------------------------------------
_IDENT = {"add": 0.0, "max": -np.inf, "min": np.inf}


def segmented_scan(values: jnp.ndarray, flags: jnp.ndarray, op: str = "add",
                   block_n: Optional[int] = None) -> jnp.ndarray:
    """Inclusive segmented scan; values [N] or [N, C]."""
    squeeze = values.ndim == 1
    v = values[:, None] if squeeze else values
    orig_dtype = v.dtype
    v = v.astype(jnp.float32)
    bn = _choose_block(v.shape[0], block_n or _ss.BLOCK_N)
    vp, n = _pad_to(v, bn, 0, value=_IDENT[op] if op != "add" else 0.0)
    fp, _ = _pad_to(flags.astype(bool), bn, 0, value=True)
    out = _ss.segmented_scan(vp, fp, op=op, interpret=_interpret(),
                             block_n=bn)[:n]
    out = out.astype(orig_dtype)
    return out[:, 0] if squeeze else out


def _choose_block(n: int, want: int) -> int:
    b = min(want, n)
    while n % b:
        b //= 2
    return max(b, 1)


def segment_reduce(values: jnp.ndarray, segment_ids: jnp.ndarray,
                   num_segments: int, op: str = "add",
                   valid=None) -> jnp.ndarray:
    """Per-segment reduction over key-sorted rows via scan + boundary gather.

    Rows must be sorted by `segment_ids` (the masked executor guarantees
    this).  Invalid rows contribute the op identity.
    """
    squeeze = values.ndim == 1
    v = values[:, None] if squeeze else values
    v = v.astype(jnp.float32)
    if valid is not None:
        v = jnp.where(valid[:, None], v, _IDENT[op] if op != "add" else 0.0)
    n = v.shape[0]
    sid = segment_ids.astype(jnp.int32)
    flags = jnp.concatenate([jnp.ones(1, bool), sid[1:] != sid[:-1]])
    scanned = segmented_scan(v, flags, op=op)
    is_last = jnp.concatenate([sid[1:] != sid[:-1], jnp.ones(1, bool)])
    ident = jnp.asarray(_IDENT[op] if op != "add" else 0.0, scanned.dtype)
    out = jnp.full((num_segments, v.shape[1]), ident, scanned.dtype)
    rows = jnp.where(is_last, sid, num_segments)  # scatter-drop non-lasts
    out = out.at[rows].set(jnp.where(is_last[:, None], scanned, ident),
                           mode="drop")
    return out[:, 0] if squeeze else out


class KernelSegmentOps(SegmentOps):
    """SegmentOps backed by the Pallas segmented-scan kernel (sorted ids)."""

    def __init__(self, segment_ids, num_segments: int, record_valid=None,
                 is_start=None):
        self.segment_ids = segment_ids.astype(jnp.int32)
        self.num_segments = int(num_segments)
        self.record_valid = record_valid
        # first valid row of each segment, precomputed by the masked executor
        # (required for order-elided inputs, where valid rows have gaps and
        # segment-id transitions no longer locate group starts)
        self.is_start = is_start

    def _reduce(self, values, op):
        out = segment_reduce(jnp.asarray(values), self.segment_ids,
                             self.num_segments, op=op,
                             valid=self.record_valid)
        return out

    def sum(self, values):
        v = jnp.asarray(values)
        out = self._reduce(v, "add")
        if jnp.issubdtype(v.dtype, jnp.integer) or v.dtype == bool:
            return out.astype(jnp.int64)
        return out.astype(v.dtype)

    def max(self, values):
        v = jnp.asarray(values)
        return self._reduce(v, "max").astype(v.dtype)

    def min(self, values):
        v = jnp.asarray(values)
        return self._reduce(v, "min").astype(v.dtype)

    def count(self):
        return self.sum(jnp.ones_like(self.segment_ids))

    def mean(self, values):
        return self.sum(values) / jnp.maximum(self.count(), 1)

    def first(self, values):
        v = jnp.asarray(values)
        sid = self.segment_ids
        if self.is_start is not None:
            is_start = self.is_start
        else:
            is_start = jnp.concatenate([jnp.ones(1, bool),
                                        sid[1:] != sid[:-1]])
            if self.record_valid is not None:
                is_start = is_start & self.record_valid
        rows = jnp.where(is_start, sid, self.num_segments)
        out = jnp.zeros((self.num_segments,), v.dtype)
        return out.at[rows].set(jnp.where(is_start, v, 0), mode="drop")

    def any(self, mask):
        return self.sum(jnp.asarray(mask).astype(jnp.int32)) > 0

    def all(self, mask):
        return self.sum(jnp.asarray(mask).astype(jnp.int32)) == self.count()

    def broadcast(self, per_group):
        return jnp.asarray(per_group)[self.segment_ids]


# ---------------------------------------------------------------------------
# Sorted probe
# ---------------------------------------------------------------------------
def sorted_probe(keys_sorted: jnp.ndarray, queries: jnp.ndarray) -> jnp.ndarray:
    """searchsorted(keys, queries, 'left') via the blocked-compare kernel."""
    kd = keys_sorted.astype(jnp.float64) if keys_sorted.dtype == jnp.int64 \
        else keys_sorted
    bk = _choose_block(max(keys_sorted.shape[0], 1), _sp.BLOCK_K)
    bq = _choose_block(max(queries.shape[0], 1), _sp.BLOCK_Q)
    maxval = (jnp.iinfo(keys_sorted.dtype).max
              if jnp.issubdtype(keys_sorted.dtype, jnp.integer)
              else jnp.finfo(keys_sorted.dtype).max)
    kp, _ = _pad_to(keys_sorted, bk, 0, value=maxval)
    qp, m = _pad_to(queries, bq, 0)
    out = _sp.sorted_probe(kp, qp, interpret=_interpret(),
                           block_q=bq, block_k=bk)
    return out[:m]


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------
def flash_attention(q, k, v, causal: bool = True, window: Optional[int] = None,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None) -> jnp.ndarray:
    """Padded/sliced wrapper around the fused attention kernel."""
    t, s = q.shape[2], k.shape[2]
    bq = _choose_block(t, block_q or _fa.BLOCK_Q)
    bk = _choose_block(s, block_k or _fa.BLOCK_K)
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               scale=scale, interpret=_interpret(),
                               block_q=bq, block_k=bk)


# ---------------------------------------------------------------------------
# RWKV-6 and RG-LRU scans
# ---------------------------------------------------------------------------
def rwkv6(r, k, v, w, u, chunk: Optional[int] = None) -> jnp.ndarray:
    t = r.shape[2]
    c = _choose_block(t, chunk or _rwkv.CHUNK)
    return _rwkv.rwkv6_scan(r, k, v, w, u, interpret=_interpret(), chunk=c)


def linear_scan(a, b, block_t: Optional[int] = None) -> jnp.ndarray:
    """h_t = a_t * h_{t-1} + b_t over axis -2; a,b [..., T, D]."""
    shape = a.shape
    t, d = shape[-2], shape[-1]
    g = int(np.prod(shape[:-2])) if len(shape) > 2 else 1
    a3 = a.reshape(g, t, d)
    b3 = b.reshape(g, t, d)
    bt = _choose_block(t, block_t or _ls.BLOCK_T)
    out = _ls.linear_scan(a3, b3, interpret=_interpret(), block_t=bt)
    return out.reshape(shape)
