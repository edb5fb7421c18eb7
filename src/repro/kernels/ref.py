"""Pure-jnp oracles (the correctness references): one for every Pallas
kernel, and the data plane's segmented scan, segment reduction and sorted
probe, which `core.scans` and `core.udf.JitSegmentOps` are tested against.

Each function states one contract plainly; tests sweep shapes/dtypes and
assert_allclose against these.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# segmented_scan — segmented inclusive scan over sorted segments
# ---------------------------------------------------------------------------
def segmented_scan(values: jnp.ndarray, flags: jnp.ndarray,
                   op: str = "add") -> jnp.ndarray:
    """Inclusive scan of `values` [N, C] restarting wherever `flags` [N] is
    True.  Classic segmented-scan combine: the left operand is absorbed when
    the right element starts a new segment."""
    if op == "add":
        combine = jnp.add
    elif op == "max":
        combine = jnp.maximum
    elif op == "min":
        combine = jnp.minimum
    else:
        raise ValueError(op)

    f = flags.astype(bool)[:, None]

    def comb(a, b):
        av, af = a
        bv, bf = b
        return jnp.where(bf, bv, combine(av, bv)), af | bf

    out, _ = jax.lax.associative_scan(comb, (values, f), axis=0)
    return out


def segment_reduce(values: jnp.ndarray, segment_ids: jnp.ndarray,
                   num_segments: int, op: str = "add",
                   valid=None) -> jnp.ndarray:
    """Per-segment reduction of key-sorted rows (oracle for the full
    scan+boundary-gather pipeline in ops.py).  values [N] or [N, C]."""
    v = values if values.ndim > 1 else values[:, None]
    if valid is not None:
        ident = _identity(op, v.dtype)
        v = jnp.where(valid[:, None], v, ident)
    if op == "add":
        out = jax.ops.segment_sum(v, segment_ids, num_segments)
    elif op == "max":
        out = jax.ops.segment_max(v, segment_ids, num_segments)
    elif op == "min":
        out = jax.ops.segment_min(v, segment_ids, num_segments)
    else:
        raise ValueError(op)
    return out if values.ndim > 1 else out[:, 0]


def _identity(op: str, dtype):
    if op == "add":
        return jnp.zeros((), dtype)
    big = jnp.finfo(dtype).max if jnp.issubdtype(dtype, jnp.floating) \
        else jnp.iinfo(dtype).max
    small = jnp.finfo(dtype).min if jnp.issubdtype(dtype, jnp.floating) \
        else jnp.iinfo(dtype).min
    return jnp.asarray(small if op == "max" else big, dtype)


# ---------------------------------------------------------------------------
# sorted_probe — vectorized searchsorted (left)
# ---------------------------------------------------------------------------
def sorted_probe(keys_sorted: jnp.ndarray, queries: jnp.ndarray) -> jnp.ndarray:
    return jnp.searchsorted(keys_sorted, queries, side="left").astype(jnp.int32)


# ---------------------------------------------------------------------------
# flash_attention — causal/windowed GQA attention
# ---------------------------------------------------------------------------
def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
              causal: bool = True, window: int | None = None,
              scale: float | None = None) -> jnp.ndarray:
    """q [B,Hq,T,D], k/v [B,Hkv,S,D] (Hq % Hkv == 0).  float32 math."""
    b, hq, t, d = q.shape
    hkv, s = k.shape[1], k.shape[2]
    group = hq // hkv
    qf = q.astype(jnp.float32) * (scale if scale is not None else d ** -0.5)
    kf = jnp.repeat(k.astype(jnp.float32), group, axis=1)
    vf = jnp.repeat(v.astype(jnp.float32), group, axis=1)
    logits = jnp.einsum("bhtd,bhsd->bhts", qf, kf)
    qpos = jnp.arange(t)[:, None] + (s - t)  # q positions within kv timeline
    kpos = jnp.arange(s)[None, :]
    mask = jnp.ones((t, s), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    w = jnp.where(jnp.isnan(w), 0.0, w)  # fully-masked rows
    return jnp.einsum("bhts,bhsd->bhtd", w, vf).astype(q.dtype)


def blocked_attention(q, k, v, causal: bool = True, window=None,
                      scale=None, block: int = 512):
    """Flash-style attention in plain XLA: lax.scan over KV tiles with an
    online-softmax carry — never materializes the [T, S] logits matrix.
    Matches `attention` numerically (tested); used for the memory-fit
    compiles and anywhere the Pallas kernel can't lower (CPU backend)."""
    b, hq, t, d = q.shape
    hkv, s = k.shape[1], k.shape[2]
    if s % block:
        pad = (-s) % block
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        dead = jnp.arange(s + pad) >= s
    else:
        pad = 0
        dead = jnp.zeros(s, bool)
    sp = s + pad
    group = hq // hkv
    qf = q.astype(jnp.float32) * (scale if scale is not None else d ** -0.5)
    q_pos = jnp.arange(t) + (s - t)

    nb = sp // block
    k_tiles = jnp.moveaxis(k.reshape(b, hkv, nb, block, d), 2, 0)
    v_tiles = jnp.moveaxis(v.reshape(b, hkv, nb, block, d), 2, 0)
    dead_tiles = dead.reshape(nb, block)

    def step(carry, tile):
        m_run, l_run, acc = carry
        kt, vt, dd, idx = tile
        kt = jnp.repeat(kt, group, axis=1)       # [b, hq, block, d]
        vt = jnp.repeat(vt, group, axis=1)
        logits = jax.lax.dot_general(
            qf, kt.astype(jnp.float32),
            (((3,), (3,)), ((0, 1), (0, 1))),
            preferred_element_type=jnp.float32)   # [b, hq, t, block]
        k_pos = idx * block + jnp.arange(block)
        mask = ~dd[None, :]
        if causal:
            mask = mask & (k_pos[None, :] <= q_pos[:, None])
        if window is not None:
            mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
        logits = jnp.where(mask[None, None], logits, -1e30)
        m_new = jnp.maximum(m_run, logits.max(-1, keepdims=True))
        p = jnp.exp(logits - m_new)
        p = jnp.where(mask[None, None], p, 0.0)
        alpha = jnp.exp(m_run - m_new)
        l_new = l_run * alpha + p.sum(-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p, vt.astype(jnp.float32),
            (((3,), (2,)), ((0, 1), (0, 1))),
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    init = (jnp.full((b, hq, t, 1), -1e30, jnp.float32),
            jnp.zeros((b, hq, t, 1), jnp.float32),
            jnp.zeros((b, hq, t, v.shape[-1]), jnp.float32))
    (m_f, l_f, acc), _ = jax.lax.scan(
        step, init, (k_tiles, v_tiles, dead_tiles, jnp.arange(nb)))
    return (acc / jnp.maximum(l_f, 1e-30)).astype(q.dtype)


# ---------------------------------------------------------------------------
# rwkv6 — data-dependent-decay linear attention (Finch, eq. WKV)
# ---------------------------------------------------------------------------
def rwkv6(r: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, w: jnp.ndarray,
          u: jnp.ndarray, state: jnp.ndarray | None = None,
          return_state: bool = False):
    """r,k,w [B,H,T,Dk], v [B,H,T,Dv], u [H,Dk]; per-step:
        out_t = r_t @ (S + u^T ⊙ (k_t^T v_t));  S = diag(w_t) S + k_t^T v_t
    """
    b, h, t, dk = r.shape
    dv = v.shape[-1]
    rf, kf, vf, wf = (x.astype(jnp.float32) for x in (r, k, v, w))
    uf = u.astype(jnp.float32)
    if state is None:
        state = jnp.zeros((b, h, dk, dv), jnp.float32)

    def step(S, inp):
        rt, kt, vt, wt = inp  # [b,h,dk],[b,h,dk],[b,h,dv],[b,h,dk]
        kv = kt[..., :, None] * vt[..., None, :]          # [b,h,dk,dv]
        out = jnp.einsum("bhk,bhkv->bhv", rt,
                         S + uf[None, :, :, None] * kv)
        S = wt[..., :, None] * S + kv
        return S, out

    xs = (jnp.moveaxis(rf, 2, 0), jnp.moveaxis(kf, 2, 0),
          jnp.moveaxis(vf, 2, 0), jnp.moveaxis(wf, 2, 0))
    S, outs = jax.lax.scan(step, state, xs)
    out = jnp.moveaxis(outs, 0, 2).astype(r.dtype)
    return (out, S) if return_state else out


def rwkv6_chunked(r, k, v, w, u, chunk: int = 32, state=None,
                  return_state: bool = False):
    """Chunked-matmul WKV6 — mathematically equal to `rwkv6` but expressed as
    dense per-chunk matmuls (GLA-style), the TPU-native formulation:

      intra-chunk:  ((r~ @ k~^T) ⊙ strict-causal) @ v  +  (r·u·k) v   (MXU)
      inter-chunk:  r~ @ S_chunk_start                                 (MXU)
      state:        S ← diag(A_C) S + (k~ ⊙ A_C)^T @ v

    with r~_t = r_t·exp(L_{t-1}), k~_j = k_j·exp(-L_j), L = cumsum(log w).
    Memory for backward is O(T/C·|S| + C²) instead of the naive scan's
    O(T·|S|) — this is what makes rwkv6-3b train_4k fit HBM (DESIGN.md §6).
    """
    b, h, t, dk = r.shape
    dv = v.shape[-1]
    assert t % chunk == 0, (t, chunk)
    nc, c = t // chunk, chunk
    f32 = jnp.float32
    rf, kf, vf, wf = (x.astype(f32).reshape(b, h, nc, c, -1)
                      for x in (r, k, v, w))
    uf = u.astype(f32)

    logw = jnp.log(jnp.maximum(wf, 1e-38))                  # [b,h,nc,c,dk]
    lc = jnp.cumsum(logw, axis=3)                           # inclusive
    lx = lc - logw                                          # exclusive
    r_t = rf * jnp.exp(lx)                                  # r~
    k_t = kf * jnp.exp(-lc)                                 # k~
    a_c = jnp.exp(lc[:, :, :, -1:, :])                      # [b,h,nc,1,dk]

    # per-chunk summaries
    decay = a_c[:, :, :, 0, :]                              # [b,h,nc,dk]
    p = jnp.einsum("bhnck,bhncv->bhnkv", k_t * a_c, vf)     # [b,h,nc,dk,dv]

    # propagate chunk-start states (cheap diagonal recurrence over nc)
    if state is None:
        s0 = jnp.zeros((b, h, dk, dv), f32)
    else:
        s0 = state.astype(f32)

    def comb(x, y):
        ax, sx = x
        ay, sy = y
        return ax * ay, ay[..., None] * sx + sy

    ca, cs = jax.lax.associative_scan(comb, (decay, p), axis=2)
    # state BEFORE chunk n: s0 folded with prefix of chunks < n
    s_incl = ca[..., None] * s0[:, :, None] + cs            # after chunk n
    s_start = jnp.concatenate(
        [jnp.broadcast_to(s0[:, :, None], (b, h, 1, dk, dv)),
         s_incl[:, :, :-1]], axis=2)                        # [b,h,nc,dk,dv]

    inter = jnp.einsum("bhnck,bhnkv->bhncv", r_t, s_start)
    scores = jnp.einsum("bhnck,bhnjk->bhncj", r_t, k_t)     # [b,h,nc,c,c]
    mask = jnp.tril(jnp.ones((c, c), bool), k=-1)
    intra = jnp.einsum("bhncj,bhnjv->bhncv",
                       jnp.where(mask[None, None, None], scores, 0.0), vf)
    diag = jnp.sum(rf * uf[None, :, None, None, :] * kf, axis=-1,
                   keepdims=True) * vf
    out = (inter + intra + diag).reshape(b, h, t, dv).astype(r.dtype)
    if return_state:
        return out, s_incl[:, :, -1]
    return out


def linear_scan_chunked(a, b, h0=None, chunk: int = 128):
    """`linear_scan` with O(T/C·D + C·D·logC) backward memory: outer scan
    carries chunk-boundary states; each chunk's associative scan is wrapped
    in jax.checkpoint so its per-level residuals are recomputed."""
    t, d = a.shape[-2], a.shape[-1]
    if t % chunk or t <= chunk:
        return linear_scan(a, b, h0=h0)
    lead = a.shape[:-2]
    nc = t // chunk
    af = a.astype(jnp.float32).reshape(lead + (nc, chunk, d))
    bf = b.astype(jnp.float32).reshape(lead + (nc, chunk, d))
    af = jnp.moveaxis(af, -3, 0)
    bf = jnp.moveaxis(bf, -3, 0)
    h = jnp.zeros(lead + (d,), jnp.float32) if h0 is None \
        else h0.astype(jnp.float32)

    @jax.checkpoint
    def one_chunk(hc, ab):
        ac, bc = ab

        def comb(x, y):
            ax, bx = x
            ay, by = y
            return ax * ay, ay * bx + by

        ca, cb = jax.lax.associative_scan(comb, (ac, bc), axis=-2)
        out = cb + ca * hc[..., None, :]
        return out[..., -1, :], out

    hN, outs = jax.lax.scan(one_chunk, h, (af, bf))
    out = jnp.moveaxis(outs, 0, -3).reshape(lead + (t, d))
    return out.astype(a.dtype)


# ---------------------------------------------------------------------------
# linear_scan — diagonal linear recurrence h_t = a_t * h_{t-1} + b_t (RG-LRU)
# ---------------------------------------------------------------------------
def linear_scan(a: jnp.ndarray, b: jnp.ndarray,
                h0: jnp.ndarray | None = None) -> jnp.ndarray:
    """a, b [..., T, D] -> h [..., T, D] (f32 math)."""
    af, bf = a.astype(jnp.float32), b.astype(jnp.float32)
    if h0 is not None:
        bf = bf.at[..., 0, :].add(af[..., 0, :] * h0.astype(jnp.float32))

    def comb(x, y):
        ax, bx = x
        ay, by = y
        return ax * ay, ay * bx + by

    _, h = jax.lax.associative_scan(comb, (af, bf), axis=-2)
    return h.astype(a.dtype)
