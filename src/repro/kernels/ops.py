"""Public wrappers for the model-plane Pallas kernels: block choice and
dispatch (interpret mode automatically on non-TPU backends).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import flash_attention as _fa
from . import linear_scan as _ls
from . import rwkv6_scan as _rwkv


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _choose_block(n: int, want: int) -> int:
    b = min(want, n)
    while n % b:
        b //= 2
    return max(b, 1)


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
