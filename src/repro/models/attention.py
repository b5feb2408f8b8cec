"""Attention: chunked (flash-style) softmax, GQA variants, MLA, caches.

Key properties:
  * ``chunked_attention`` scans KV in fixed chunks with an online softmax —
    no (Sq, Skv) tensor is ever materialized, which is what lets the 32k
    prefill cells compile inside HBM.
  * sliding-window ('L') layers keep ring-buffer KV caches of size
    ``window`` — decode_32k/long_500k cells only pay window-sized memory
    for local layers.
  * RoPE is applied at absolute positions before caching, so ring-buffer
    entries stay valid.
  * MLA (DeepSeek-V3) caches only the compressed latent (c_kv, k_pe) and
    decodes in the absorbed form (query hits the latent directly).
  * decode attention is a plain masked softmax over the cache: under pjit,
    GSPMD partitions the cache sequence axis (sequence-parallel decode for
    long_500k) and inserts the flash-decoding style partial reductions.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import (apply_rope, cdtype, dense_init, rms_head_norm,
                                 rng_for)
from repro.runtime.telemetry import scope
from repro.sharding import annotate

NEG = -1e30


def _softcap(s, cap: Optional[float]):
    if cap is None:
        return s
    return jnp.tanh(s / cap) * cap


def chunked_attention(q, k, v, *, causal: bool, scale: float,
                      window: Optional[int] = None,
                      softcap: Optional[float] = None,
                      q_offset: int = 0, chunk_kv: int = 1024):
    """Online-softmax attention.

    q (B, Sq, Hq, Dh), k (B, Skv, Hkv, Dh), v (B, Skv, Hkv, Dv)
    → (B, Sq, Hq, Dv).  Hq must be a multiple of Hkv (GQA grouping).
    """
    b, sq, hq, dh = q.shape
    _, skv, hkv, _ = k.shape
    dv = v.shape[-1]
    g = hq // hkv
    chunk_kv = min(chunk_kv, skv)

    qh = q.astype(jnp.float32).reshape(b, sq, hkv, g, dh)
    qh = qh.transpose(0, 2, 3, 1, 4)                     # (B, Hkv, G, Sq, Dh)

    pad = (-skv) % chunk_kv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = (skv + pad) // chunk_kv
    kc = k.reshape(b, nc, chunk_kv, hkv, dh).transpose(1, 0, 3, 2, 4)
    vc = v.reshape(b, nc, chunk_kv, hkv, dv).transpose(1, 0, 3, 2, 4)

    qpos = q_offset + jnp.arange(sq)                     # (Sq,)

    def body(carry, xs):
        m, l, acc = carry
        kb, vb, ci = xs                                  # (B,Hkv,C,Dh/Dv)
        s = jnp.einsum("bhgqd,bhcd->bhgqc", qh, kb.astype(jnp.float32)) * scale
        s = _softcap(s, softcap)
        kpos = ci * chunk_kv + jnp.arange(chunk_kv)      # (C,)
        ok = (kpos < skv)[None, :]
        if causal:
            ok = ok & (qpos[:, None] >= kpos[None, :])
        if window is not None:
            ok = ok & (qpos[:, None] - kpos[None, :] < window)
        s = jnp.where(ok[None, None, None], s, NEG)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhgqc,bhcd->bhgqd", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hkv, g, sq), NEG, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, sq, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                  (kc, vc, jnp.arange(nc)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, hq, dv)
    return out.astype(q.dtype)


def ring_slot_positions(cache_size: int, t):
    """Absolute position stored in each ring slot at time t (next write = t).

    For t <= cache_size slot s holds position s (s < t valid); afterwards the
    live window is [t - W, t) with slot(p) = p % W.  ``t`` may be a scalar
    (→ (W,)) or a per-slot (B,) vector (→ (B, W)) for continuous batching.
    Delegates to the canonical ring math in core.kv_compress.
    """
    from repro.core.kv_compress import ring_positions
    return ring_positions(cache_size, t)


def decode_attention(q, k_cache, v_cache, *, t, scale: float,
                     window: Optional[int] = None,
                     softcap: Optional[float] = None,
                     ring: bool = False, chunk_len=None):
    """One-token (or chunked mixed-mode) attention over a cache.

    Decode form — q (B, Hq, Dh), k_cache (B, Sc, Hkv, Dh), v_cache
    (B, Sc, Hkv, Dv).  ``t`` = current absolute position (the query's
    position; cache entries with position < t participate); scalar or
    per-slot (B,) for continuous batching.  Under pjit the Sc axis may be
    sharded (sequence-parallel long-context decode).

    Mixed chunk form (decode-interleaved prefill) — q (B, L, Hq, Dh) with
    per-slot ``chunk_len`` (B,) valid rows and ``t`` = cache length
    *before* the chunk rows were written: row i queries absolute position
    t + i and sees cache entries with position < t + i + 1 (the chunk's
    own rows are already in the cache, so intra-chunk causality falls out
    of the same mask).  Rows at index >= chunk_len are garbage and must
    be discarded by the caller.  Returns (B, L, Hq, Dv).
    """
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    b, l, hq, dh = q.shape
    _, sc, hkv, _ = k_cache.shape
    g = hq // hkv
    qh = q.astype(jnp.float32).reshape(b, l, hkv, g, dh)

    s = jnp.einsum("blhgd,bshd->bhlgs", qh,
                   k_cache.astype(jnp.float32)) * scale
    s = _softcap(s, softcap)
    tb = jnp.broadcast_to(jnp.asarray(t), (b,))
    if squeeze:
        qpos1 = tb[:, None]                              # (B, 1) = qpos + 1
        tw = tb                                          # writes included
    else:
        cl = jnp.broadcast_to(jnp.asarray(chunk_len, jnp.int32), (b,))
        qpos1 = tb[:, None] + jnp.arange(l)[None, :] + 1
        tw = tb + cl                                     # ring holds t + cl
    pos = ring_slot_positions(sc, tw) if ring else jnp.arange(sc)
    pos = jnp.broadcast_to(pos, (b, sc))
    ok = ((pos >= 0)[:, None, :]
          & (pos[:, None, :] < qpos1[:, :, None]))       # (B, L, Sc)
    if window is not None:
        # query position is qpos1-1; training mask is qpos - kpos < window,
        # i.e. kpos >= qpos1 - window
        ok = ok & (pos[:, None, :] >= qpos1[:, :, None] - window)
    s = jnp.where(ok[:, None, :, None, :], s, NEG)
    m = s.max(-1, keepdims=True)
    p = jnp.exp(s - m)
    lsum = p.sum(-1, keepdims=True)
    out = jnp.einsum("bhlgs,bshd->blhgd", p / jnp.maximum(lsum, 1e-30),
                     v_cache.astype(jnp.float32))
    out = out.reshape(b, l, hq, -1).astype(q.dtype)
    return out[:, 0] if squeeze else out


# ---------------------------------------------------------------------------
# GQA attention layer (self-attention)
# ---------------------------------------------------------------------------


def init_attn(rng, cfg: ModelConfig, name: str = "attn"):
    d, hq, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p = {
        "wq": dense_init(rng_for(rng, name + "/wq"), (d, hq * dh)),
        "wk": dense_init(rng_for(rng, name + "/wk"), (d, hkv * dh)),
        "wv": dense_init(rng_for(rng, name + "/wv"), (d, hkv * dh)),
        "wo": dense_init(rng_for(rng, name + "/wo"), (hq * dh, d)),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((dh,), jnp.float32)
        p["k_norm"] = jnp.ones((dh,), jnp.float32)
    return p


def _theta(cfg: ModelConfig, layer_kind: str) -> float:
    if layer_kind == "L" and cfg.rope_theta_local is not None:
        return cfg.rope_theta_local
    return cfg.rope_theta


def _qkv(p, x, cfg: ModelConfig, positions, layer_kind: str, kv_repeat: int,
         rope: bool = True):
    dt = cdtype(cfg)
    b, s, _ = x.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ p["wq"].astype(dt)).reshape(b, s, hq, dh)
    k = (x @ p["wk"].astype(dt)).reshape(b, s, hkv, dh)
    v = (x @ p["wv"].astype(dt)).reshape(b, s, hkv, dh)
    if cfg.qk_norm:
        q = rms_head_norm(p["q_norm"], q, cfg.norm_eps)
        k = rms_head_norm(p["k_norm"], k, cfg.norm_eps)
    if rope and cfg.pos_kind == "rope":
        th = _theta(cfg, layer_kind)
        q = apply_rope(q, positions, th)
        k = apply_rope(k, positions, th)
    if kv_repeat > 1:
        k = jnp.repeat(k, kv_repeat, axis=2)
        v = jnp.repeat(v, kv_repeat, axis=2)
    return q, k, v


def _scale(cfg: ModelConfig) -> float:
    return (cfg.query_scale if cfg.query_scale is not None
            else cfg.head_dim**-0.5)


def attn_train(p, x, cfg: ModelConfig, *, layer_kind: str, positions,
               kv_repeat: int = 1, causal: bool = True, chunk_kv: int = 1024):
    q, k, v = _qkv(p, x, cfg, positions, layer_kind, kv_repeat)
    window = cfg.sliding_window if layer_kind == "L" else None
    out = chunked_attention(q, k, v, causal=causal, scale=_scale(cfg),
                            window=window, softcap=cfg.attn_logit_softcap,
                            chunk_kv=chunk_kv)
    b, s, _, _ = out.shape
    return out.reshape(b, s, -1) @ p["wo"].astype(cdtype(cfg))


def init_cache_attn(cfg: ModelConfig, layer_kind: str, batch: int,
                    max_seq: int, kv_repeat: int = 1, dtype=None,
                    quantized: bool = False):
    dt = dtype or cdtype(cfg)
    window = cfg.sliding_window if layer_kind == "L" else None
    sc = min(max_seq, window) if window else max_seq
    hkv = cfg.n_kv_heads * kv_repeat
    shape = (batch, sc, hkv, cfg.head_dim)
    if quantized:
        # int8 KV with a per-head static scale (set at prefill): halves
        # HBM footprint + stream bytes of decode at <0.5% score error
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.ones((hkv,), jnp.float32),
                "v_scale": jnp.ones((hkv,), jnp.float32)}
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def _cache_read(cache, cfg):
    """Dequantize-on-read for int8 caches; identity otherwise."""
    if "k_scale" not in cache:
        return cache["k"], cache["v"]
    dt = cdtype(cfg)
    k = cache["k"].astype(dt) * cache["k_scale"][None, None, :, None].astype(dt)
    v = cache["v"].astype(dt) * cache["v_scale"][None, None, :, None].astype(dt)
    return k, v


def _cache_write(cache, k_new, v_new, slot):
    """Quantize-on-write for int8 caches (static per-head scale).

    k/v_new (B, L, Hkv, Dh); ``slot`` (B, L) per-row write position (a
    scatter, so continuous-batching slots at different depths coexist and
    a prompt chunk lands in one call).  Out-of-range slots (masked chunk
    rows pass Sc) are dropped."""
    if "k_scale" in cache:
        ks = cache["k_scale"][None, None, :, None]
        vs = cache["v_scale"][None, None, :, None]
        k_new = jnp.clip(jnp.round(k_new.astype(jnp.float32) / ks),
                         -127, 127).astype(jnp.int8)
        v_new = jnp.clip(jnp.round(v_new.astype(jnp.float32) / vs),
                         -127, 127).astype(jnp.int8)
    b = k_new.shape[0]
    rows = jnp.arange(b)[:, None]
    kc = cache["k"].at[rows, slot].set(k_new.astype(cache["k"].dtype),
                                       mode="drop")
    vc = cache["v"].at[rows, slot].set(v_new.astype(cache["v"].dtype),
                                       mode="drop")
    return kc, vc


def attn_prefill(p, x, cfg: ModelConfig, *, layer_kind: str, positions,
                 kv_repeat: int = 1, chunk_kv: int = 1024):
    """Causal prefill returning (y, cache).  'L' layers keep only the last
    ``window`` keys, placed at their ring slots."""
    q, k, v = _qkv(p, x, cfg, positions, layer_kind, kv_repeat)
    window = cfg.sliding_window if layer_kind == "L" else None
    out = chunked_attention(q, k, v, causal=True, scale=_scale(cfg),
                            window=window, softcap=cfg.attn_logit_softcap,
                            chunk_kv=chunk_kv)
    b, s, _, _ = out.shape
    y = out.reshape(b, s, -1) @ p["wo"].astype(cdtype(cfg))

    if window is not None and s > window:
        tail_k, tail_v = k[:, -window:], v[:, -window:]
        # slot for absolute position pos is pos % window; tail position j
        # (0-based in the tail) is absolute s - window + j
        slots = jnp.mod(s - window + jnp.arange(window), window)
        inv = jnp.argsort(slots)
        cache = {"k": tail_k[:, inv], "v": tail_v[:, inv]}
    else:
        sc = window if window else s
        padn = sc - s if window else 0
        cache = {
            "k": jnp.pad(k, ((0, 0), (0, padn), (0, 0), (0, 0))) if padn else k,
            "v": jnp.pad(v, ((0, 0), (0, padn), (0, 0), (0, 0))) if padn else v,
        }
    return y, cache


def init_cache_attn_clustered(cfg: ModelConfig, batch: int, *,
                              n_clusters: int = 512, tail: int = 256,
                              kv_repeat: int = 1, dtype=None,
                              pool_blocks: int = 0, block_size: int = 0):
    """Clustered KV cache for global-attention layers (the paper's memory
    manager): C median centroids (+ per-centroid counts) stand in for the
    compressed prefix; the most recent ``tail`` keys stay exact in a ring.
    The serving runtime refreshes centroids with core.kv_compress every
    ``tail`` steps, so the prefix is always covered.

    With ``pool_blocks``/``block_size`` set (paged serving), the tail
    leaves become a shared block pool ``(pool_blocks, block_size, H, Dh)``
    instead of a per-slot ring — ring offset ``r`` of a slot lives at
    offset ``r % block_size`` of the physical block its block table maps
    for ring block ``r // block_size`` (runtime/kv_pool.py).  Centroids,
    counts, and ``cov`` stay dense per slot either way."""
    dt = dtype or cdtype(cfg)
    hkv = cfg.n_kv_heads * kv_repeat
    dh = cfg.head_dim
    if pool_blocks:
        tail_shape = (pool_blocks, block_size, hkv, dh)
    else:
        tail_shape = (batch, tail, hkv, dh)
    return {
        "k_cents": jnp.zeros((batch, n_clusters, hkv, dh), dt),
        "v_cents": jnp.zeros((batch, n_clusters, hkv, dh), dt),
        "counts": jnp.zeros((batch, n_clusters, hkv), jnp.float32),
        "k_tail": jnp.zeros(tail_shape, dt),
        "v_tail": jnp.zeros(tail_shape, dt),
        # centroids summarize positions [0, cov); tail is exact for
        # [cov, t) — the partition makes compaction loss-free at the
        # ring-eviction boundary
        "cov": jnp.zeros((batch,), jnp.int32),
    }


# The per-SLOT summary state of a clustered cache leaf: everything a
# slot owns beyond its tail-ring payload.  This is exactly the state the
# prefix-sharing admission path snapshots at chunk boundaries and
# restores into a fresh slot (runtime/prefix_cache.py) — the tail bytes
# themselves are shared at block granularity through the pool instead.
CLUSTERED_SLOT_KEYS = ("k_cents", "v_cents", "counts", "cov")

USE_CLUSTERED_KERNEL = True  # Pallas fused path (interpret mode off-TPU)


def attn_decode_clustered(p, x, cfg: ModelConfig, *, cache, t,
                          kv_repeat: int = 1, use_kernel: bool = None,
                          chunk_len=None):
    """Attention over [median centroids ⊕ exact tail ring] — one token per
    slot (decode), or mixed-mode with a prompt chunk in flight.

    Centroid c with m keys gets a +log(m) logit bias (clustered-attention
    estimator).  The new keys/values are written into the tail ring at
    position % tail; centroid refresh happens outside the step (runtime).
    ``t`` may be scalar or per-slot (B,): the slot's cache length BEFORE
    this step.  Tail entries at positions < cov are already summarized by
    centroids and masked out (no double counting).

    Mixed mode (``chunk_len`` (B,) with x (B, L, d)): slot rows [0,
    chunk_len) are consecutive prompt positions t..t+chunk_len-1; their
    K/V go into the ring before scoring, so intra-chunk causal attention
    falls out of the ring mask.  Decode slots ride along with chunk_len 1.
    Dispatches to the fused Pallas ``clustered_decode`` kernel."""
    b, l = x.shape[0], x.shape[1]
    tb = jnp.broadcast_to(jnp.asarray(t, jnp.int32), (b,))
    chunked = chunk_len is not None
    cl = (jnp.broadcast_to(jnp.asarray(chunk_len, jnp.int32), (b,))
          if chunked else jnp.ones((b,), jnp.int32))
    ri = jnp.arange(l)[None, :]                           # (1, L)
    positions = tb[:, None] + ri
    q, k, v = _qkv(p, x, cfg, positions, "G", kv_repeat)
    tail = cache["k_tail"].shape[1]
    # masked chunk rows write out of range (dropped)
    slot = jnp.where(ri < cl[:, None], jnp.mod(positions, tail), tail)
    rows = jnp.arange(b)[:, None]
    k_tail = cache["k_tail"].at[rows, slot].set(
        k.astype(cache["k_tail"].dtype), mode="drop")
    v_tail = cache["v_tail"].at[rows, slot].set(
        v.astype(cache["v_tail"].dtype), mode="drop")
    cov = jnp.broadcast_to(jnp.asarray(cache.get("cov", 0), jnp.int32), (b,))

    hq = cfg.n_heads
    hkv = cache["k_tail"].shape[2]
    g = hq // hkv
    scale = _scale(cfg)
    if use_kernel is None:
        use_kernel = USE_CLUSTERED_KERNEL

    if use_kernel:
        from repro.kernels import ops as kops
        out = kops.clustered_decode(
            q if chunked else q[:, 0],
            cache["k_cents"], cache["v_cents"], cache["counts"],
            k_tail, v_tail, tb, cov, cl, scale=scale,
            softcap=cfg.attn_logit_softcap)
        out = out.reshape(b, l, hkv, g, cfg.head_dim)
    else:
        qh = q.astype(jnp.float32).reshape(b, l, hkv, g, -1)
        s_c = jnp.einsum("blhgd,bchd->bhlgc", qh,
                         cache["k_cents"].astype(jnp.float32)) * scale
        s_c = _softcap(s_c, cfg.attn_logit_softcap)
        cnt = cache["counts"].transpose(0, 2, 1)[:, :, None, None, :]
        s_c = jnp.where(cnt > 0, s_c + jnp.log(jnp.maximum(cnt, 1e-9)), NEG)

        s_t = jnp.einsum("blhgd,bshd->bhlgs", qh,
                         k_tail.astype(jnp.float32)) * scale
        s_t = _softcap(s_t, cfg.attn_logit_softcap)
        pos = ring_slot_positions(tail, tb + cl)                 # (B, R)
        qpos1 = tb[:, None] + ri + 1                             # (B, L)
        ok = ((pos[:, None, :] >= 0)
              & (pos[:, None, :] < qpos1[:, :, None])
              & (pos[:, None, :] >= cov[:, None, None])
              & (ri < cl[:, None])[:, :, None])                  # (B, L, R)
        s_t = jnp.where(ok[:, None, :, None, :], s_t, NEG)

        s = jnp.concatenate([s_c, s_t], axis=-1)
        m = s.max(-1, keepdims=True)
        pw = jnp.exp(s - m)
        pw = pw / jnp.maximum(pw.sum(-1, keepdims=True), 1e-30)
        nc = cache["k_cents"].shape[1]
        out = (jnp.einsum("bhlgc,bchd->blhgd", pw[..., :nc],
                          cache["v_cents"].astype(jnp.float32))
               + jnp.einsum("bhlgs,bshd->blhgd", pw[..., nc:],
                            v_tail.astype(jnp.float32)))
    # under mesh serving the per-head context is model-sharded; gather heads
    # to a replicated layout BEFORE the output projection so the wo
    # contraction sums all head dims in one (device-order-independent)
    # pass — keeps mesh decode bit-identical to single-device greedy
    out_flat = annotate(out.reshape(b, l, hq * cfg.head_dim),
                        "batch", "seq", None)
    y = out_flat.astype(x.dtype) @ p["wo"].astype(cdtype(cfg))
    new_cache = dict(cache, k_tail=k_tail, v_tail=v_tail)
    return y, new_cache


def attn_decode_clustered_packed(p, x, cfg: ModelConfig, *, cache,
                                 row_slot, row_pos, row_tw, block_tables,
                                 block_size: int, kv_repeat: int = 1,
                                 row_wlo=None, width: int | None = None):
    """Paged clustered-KV attention over packed ragged rows.

    x (N, 1, d): one embedding per real (slot, position) pair this step —
    every decode slot's pending token ⊕ each admitting slot's prompt-chunk
    rows, padded only to the per-shard row bucket (compute ∝ real tokens,
    PagedAttention-style).  row_slot (N,) physical slot; row_pos (N,) the
    row's absolute position (−1 ⇒ padding row, output garbage by
    contract); row_tw (N,) the row's slot ring watermark t + chunk_len
    (all of a chunk's rows are written before any row scores, so
    intra-chunk causality falls out of the per-row position mask exactly
    as in the dense mixed launch); ``width`` the launch's static chunk
    length, the longest run of one slot's rows; block_tables (B, T)
    global physical block ids — every entry valid, with blocks being
    *written* this step freshly allocated OR copy-on-write-owned by the
    engine (a sanitized dead-block alias, or a block another slot still
    references, would corrupt its true owner: kv_pool.ensure enforces
    ref == 1 before any row's write lands).

    Prefix sharing needs no change here: a shared prefix is just several
    table rows pointing at the same physical blocks, and a slot seeded
    mid-prompt (fed = F tokens reused, cov from the shared frontier)
    feeds its first row at position F like any other chunk row — the
    gather/mask math is identical, which is what keeps shared-admission
    greedy tokens bit-identical to unshared serving.

    The tail write scatters each row's K/V into its slot's pool block at
    the ring offset the dense path would use, so the paged cache holds
    bit-identical live bytes and greedy outputs match the dense engine
    exactly."""
    n = x.shape[0]
    positions = row_pos[:, None]                          # (N, 1)
    q, k, v = _qkv(p, x, cfg, positions, "G", kv_repeat)
    k, v = k[:, 0], v[:, 0]                               # (N, Hkv, Dh)
    t_blocks = block_tables.shape[1]
    ring = t_blocks * block_size
    nb = cache["k_tail"].shape[0]
    row_bt = jnp.take(block_tables, row_slot, axis=0)     # (N, T)
    roff = jnp.mod(row_pos, ring)
    blk = jnp.take_along_axis(row_bt, (roff // block_size)[:, None],
                              axis=1)[:, 0]
    valid = row_pos >= 0
    blk = jnp.where(valid, blk, nb)                       # pad rows drop
    off = roff % block_size
    with scope("kv_pool_write"):
        k_pool = cache["k_tail"].at[blk, off].set(
            k.astype(cache["k_tail"].dtype), mode="drop")
        v_pool = cache["v_tail"].at[blk, off].set(
            v.astype(cache["v_tail"].dtype), mode="drop")

    qpos1 = jnp.where(valid, row_pos + 1, 0)
    row_cov = jnp.take(cache["cov"], row_slot, axis=0)
    if row_wlo is None:
        # no per-row retention window: the cov frontier is the only
        # lower bound (zeros keep the kernel mask bit-identical)
        row_wlo = jnp.zeros_like(qpos1)
    hq = cfg.n_heads
    from repro.kernels import ops as kops
    with scope("paged_attention"):
        out = kops.paged_clustered_decode(
            q[:, 0], cache["k_cents"], cache["v_cents"], cache["counts"],
            k_pool, v_pool, row_slot, row_bt, qpos1, row_tw, row_cov,
            row_wlo=row_wlo, scale=_scale(cfg),
            softcap=cfg.attn_logit_softcap, width=width)
    # same head-gather-before-wo rule as the dense clustered path
    out_flat = annotate(out.reshape(n, 1, hq * cfg.head_dim),
                        "batch", "seq", None)
    y = out_flat.astype(x.dtype) @ p["wo"].astype(cdtype(cfg))
    new_cache = dict(cache, k_tail=k_pool, v_tail=v_pool)
    return y, new_cache


def attn_decode_window_packed(p, x, cfg: ModelConfig, *, cache, row_slot,
                              row_pos, row_cidx, width: int,
                              kv_repeat: int = 1):
    """Sliding-window ('L') attention over packed ragged rows.

    The local-layer twin of ``attn_decode_clustered_packed``: the paged
    engine packs one row per real (slot, position) pair, but local rings
    stay dense per slot — ``cache`` is the ordinary {'k','v'} (B, W, Hkv,
    Dh) ring, never pool-backed (WindowRetention's retirement is virtual:
    a position dies by falling out of the window, storage is reclaimed by
    the ring overwrite itself).

    ``row_cidx`` (N,) is each row's index within its admission chunk
    (decode rows 0) and ``width`` the static max chunk length this launch:
    rows commit in ``row_cidx`` order — scatter the K/V of every row at
    chunk index jj into its slot's ring, gather, score at watermark
    row_pos+1 — which reproduces the blocking engine's one-token-at-a-time
    window schedule exactly (two rows of one slot never share a cidx, so
    each scatter round is conflict-free)."""
    n = x.shape[0]
    window = cfg.sliding_window
    positions = row_pos[:, None]                          # (N, 1)
    q, k, v = _qkv(p, x, cfg, positions, "L", kv_repeat)
    k, v = k[:, 0], v[:, 0]                               # (N, Hkv, Dh)
    sc = cache["k"].shape[1]
    valid = row_pos >= 0
    kc, vc = cache["k"], cache["v"]
    out = jnp.zeros((n, cfg.n_heads, cfg.head_dim), jnp.float32)
    for jj in range(width):
        sel = valid & (row_cidx == jj)
        slot_w = jnp.where(sel, jnp.mod(row_pos, sc), sc)
        kc = kc.at[row_slot, slot_w].set(k.astype(kc.dtype), mode="drop")
        vc = vc.at[row_slot, slot_w].set(v.astype(vc.dtype), mode="drop")
        kcg = jnp.take(kc, row_slot, axis=0)              # (N, W, Hkv, Dh)
        vcg = jnp.take(vc, row_slot, axis=0)
        out_jj = decode_attention(q[:, 0], kcg, vcg, t=row_pos + 1,
                                  scale=_scale(cfg), window=window,
                                  softcap=cfg.attn_logit_softcap,
                                  ring=True)
        out = jnp.where(sel[:, None, None], out_jj.astype(jnp.float32),
                        out)
    # same head-gather-before-wo rule as the clustered packed path
    out_flat = annotate(out.reshape(n, 1, -1), "batch", "seq", None)
    y = out_flat.astype(x.dtype) @ p["wo"].astype(cdtype(cfg))
    return y, dict(cache, k=kc, v=vc)


def attn_decode(p, x, cfg: ModelConfig, *, layer_kind: str, cache, t,
                kv_repeat: int = 1, chunk_len=None):
    """x (B, 1, d) decode, or (B, L, d) mixed-mode with per-slot
    ``chunk_len`` (B,) valid rows (chunked prefill interleaved with
    decode); cache {'k','v'} (B, Sc, Hkv, Dh); t scalar int32 or a
    per-slot (B,) vector: the slot's cache length BEFORE this step."""
    if "k_cents" in cache:
        return attn_decode_clustered(p, x, cfg, cache=cache, t=t,
                                     kv_repeat=kv_repeat,
                                     chunk_len=chunk_len)
    b, l = x.shape[0], x.shape[1]
    tb = jnp.broadcast_to(jnp.asarray(t, jnp.int32), (b,))
    chunked = chunk_len is not None
    cl = (jnp.broadcast_to(jnp.asarray(chunk_len, jnp.int32), (b,))
          if chunked else jnp.ones((b,), jnp.int32))
    ri = jnp.arange(l)[None, :]
    positions = tb[:, None] + ri                          # (B, L)
    q, k, v = _qkv(p, x, cfg, positions, layer_kind, kv_repeat)
    window = cfg.sliding_window if layer_kind == "L" else None
    sc = cache["k"].shape[1]
    if chunked and window is not None:
        # WindowRetention's staging rule: writing a whole chunk into a
        # W-sized ring at once would overwrite positions t+i-W that are
        # still inside earlier rows' attention windows — there is no
        # coverage frontier here to absorb them first (unlike the
        # clustered cache).  So rows commit sequentially: write row i at
        # its ring slot, then score it at watermark t+i+1, exactly the
        # schedule the blocking engine runs one decode step at a time.
        # A row's overwrite victim (position t+i-W) is already outside
        # the window of every later row, so nothing is lost.
        new_cache = dict(cache)
        outs = []
        for i in range(l):
            slot_i = jnp.where(i < cl, jnp.mod(tb + i, sc), sc)[:, None]
            kc, vc = _cache_write(new_cache, k[:, i:i + 1], v[:, i:i + 1],
                                  slot_i)
            new_cache = dict(new_cache, k=kc, v=vc)
            k_read, v_read = _cache_read(new_cache, cfg)
            outs.append(decode_attention(
                q[:, i], k_read, v_read, t=tb + i + 1, scale=_scale(cfg),
                window=window, softcap=cfg.attn_logit_softcap, ring=True))
        out = jnp.stack(outs, axis=1)
        out_flat = annotate(out.reshape(b, l, -1), "batch", "seq", None)
        return out_flat @ p["wo"].astype(cdtype(cfg)), new_cache
    slot = jnp.mod(positions, sc) if window \
        else jnp.minimum(positions, sc - 1)
    slot = jnp.where(ri < cl[:, None], slot, sc)          # drop masked rows
    kc, vc = _cache_write(cache, k, v, slot)
    new_cache = dict(cache, k=kc, v=vc)
    k_read, v_read = _cache_read(new_cache, cfg)
    if chunked:
        out = decode_attention(q, k_read, v_read, t=tb, chunk_len=cl,
                               scale=_scale(cfg), window=window,
                               softcap=cfg.attn_logit_softcap,
                               ring=window is not None)
    else:
        out = decode_attention(q[:, 0], k_read, v_read, t=tb + 1,
                               scale=_scale(cfg),
                               window=window,
                               softcap=cfg.attn_logit_softcap,
                               ring=window is not None)
    # same head-gather-before-wo rule as the clustered path (see above)
    out_flat = annotate(out.reshape(b, l, -1), "batch", "seq", None)
    y = out_flat @ p["wo"].astype(cdtype(cfg))
    return y, new_cache


# ---------------------------------------------------------------------------
# Cross-attention (encoder–decoder)
# ---------------------------------------------------------------------------


def init_cross_attn(rng, cfg: ModelConfig, name: str = "xattn"):
    return init_attn(rng, cfg, name)


def cross_attn_apply(p, x, enc_kv, cfg: ModelConfig):
    """x (B, Sq, d); enc_kv = (k, v) precomputed from encoder output."""
    dt = cdtype(cfg)
    b, s, _ = x.shape
    hq, dh = cfg.n_heads, cfg.head_dim
    q = (x @ p["wq"].astype(dt)).reshape(b, s, hq, dh)
    k, v = enc_kv
    out = chunked_attention(q, k, v, causal=False, scale=_scale(cfg),
                            softcap=cfg.attn_logit_softcap)
    return out.reshape(b, s, -1) @ p["wo"].astype(dt)


def cross_kv(p, enc_out, cfg: ModelConfig):
    dt = cdtype(cfg)
    b, s, _ = enc_out.shape
    hkv, dh = cfg.n_kv_heads, cfg.head_dim
    k = (enc_out @ p["wk"].astype(dt)).reshape(b, s, hkv, dh)
    v = (enc_out @ p["wv"].astype(dt)).reshape(b, s, hkv, dh)
    return k, v


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3): low-rank Q/KV with compressed-latent cache
# ---------------------------------------------------------------------------


def init_mla(rng, cfg: ModelConfig, name: str = "mla"):
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    qd = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "wdq": dense_init(rng_for(rng, name + "/wdq"), (d, m.q_lora_rank)),
        "q_norm": jnp.ones((m.q_lora_rank,), jnp.float32),
        "wuq": dense_init(rng_for(rng, name + "/wuq"),
                          (m.q_lora_rank, h * qd)),
        "wdkv": dense_init(rng_for(rng, name + "/wdkv"), (d, m.kv_lora_rank)),
        "kv_norm": jnp.ones((m.kv_lora_rank,), jnp.float32),
        "wukv": dense_init(
            rng_for(rng, name + "/wukv"),
            (m.kv_lora_rank, h * (m.qk_nope_head_dim + m.v_head_dim))),
        "wkr": dense_init(rng_for(rng, name + "/wkr"),
                          (d, m.qk_rope_head_dim)),
        "wo": dense_init(rng_for(rng, name + "/wo"), (h * m.v_head_dim, d)),
    }


def _mla_q(p, x, cfg: ModelConfig, positions):
    dt = cdtype(cfg)
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    cq = rms_head_norm(p["q_norm"], x @ p["wdq"].astype(dt), cfg.norm_eps)
    q = (cq @ p["wuq"].astype(dt)).reshape(
        b, s, h, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope = q[..., :m.qk_nope_head_dim]
    q_rope = apply_rope(q[..., m.qk_nope_head_dim:], positions,
                        cfg.rope_theta)
    return q_nope, q_rope


def _mla_latent(p, x, cfg: ModelConfig, positions):
    dt = cdtype(cfg)
    ckv = rms_head_norm(p["kv_norm"], x @ p["wdkv"].astype(dt), cfg.norm_eps)
    kpe = (x @ p["wkr"].astype(dt))[:, :, None, :]       # (B,S,1,rope)
    kpe = apply_rope(kpe, positions, cfg.rope_theta)[:, :, 0]
    return ckv, kpe


def mla_train(p, x, cfg: ModelConfig, *, positions, chunk_kv: int = 1024):
    """Expanded (training/prefill) form: materializes per-head K/V."""
    m = cfg.mla
    dt = cdtype(cfg)
    b, s, _ = x.shape
    h = cfg.n_heads
    q_nope, q_rope = _mla_q(p, x, cfg, positions)
    ckv, kpe = _mla_latent(p, x, cfg, positions)
    kv = (ckv @ p["wukv"].astype(dt)).reshape(
        b, s, h, m.qk_nope_head_dim + m.v_head_dim)
    k_nope = kv[..., :m.qk_nope_head_dim]
    v = kv[..., m.qk_nope_head_dim:]
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kpe[:, :, None, :],
                                  (b, s, h, m.qk_rope_head_dim))], axis=-1)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    out = chunked_attention(q, k, v, causal=True, scale=scale,
                            chunk_kv=chunk_kv)
    return out.reshape(b, s, -1) @ p["wo"].astype(dt)


def init_cache_mla(cfg: ModelConfig, batch: int, max_seq: int, dtype=None):
    m = cfg.mla
    dt = dtype or cdtype(cfg)
    return {
        "ckv": jnp.zeros((batch, max_seq, m.kv_lora_rank), dt),
        "kpe": jnp.zeros((batch, max_seq, m.qk_rope_head_dim), dt),
    }


def mla_prefill(p, x, cfg: ModelConfig, *, positions, max_seq: int,
                chunk_kv: int = 1024):
    y = mla_train(p, x, cfg, positions=positions, chunk_kv=chunk_kv)
    ckv, kpe = _mla_latent(p, x, cfg, positions)
    b, s = x.shape[0], x.shape[1]
    pad = max_seq - s
    cache = {
        "ckv": jnp.pad(ckv, ((0, 0), (0, pad), (0, 0))),
        "kpe": jnp.pad(kpe, ((0, 0), (0, pad), (0, 0))),
    }
    return y, cache


def mla_decode(p, x, cfg: ModelConfig, *, cache, t):
    """Absorbed decode: queries hit the latent cache directly — the cache
    holds only (c_kv, k_pe) per token (the paper-exact compressed cache)."""
    m = cfg.mla
    dt = cdtype(cfg)
    b = x.shape[0]
    h = cfg.n_heads
    tb = jnp.broadcast_to(jnp.asarray(t, jnp.int32), (b,))
    positions = tb[:, None]
    q_nope, q_rope = _mla_q(p, x, cfg, positions)        # (B,1,H,·)
    ckv_new, kpe_new = _mla_latent(p, x, cfg, positions)
    rows = jnp.arange(b)
    ckv = cache["ckv"].at[rows, tb].set(
        ckv_new[:, 0].astype(cache["ckv"].dtype))
    kpe = cache["kpe"].at[rows, tb].set(
        kpe_new[:, 0].astype(cache["kpe"].dtype))

    wukv = p["wukv"].astype(dt).reshape(
        m.kv_lora_rank, h, m.qk_nope_head_dim + m.v_head_dim)
    wuk = wukv[..., :m.qk_nope_head_dim]                 # (r, H, nope)
    wuv = wukv[..., m.qk_nope_head_dim:]                 # (r, H, v)

    # absorb W_uk into the query: q' (B, H, r)
    q_abs = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], wuk)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    s = (jnp.einsum("bhr,bsr->bhs", q_abs.astype(jnp.float32),
                    ckv.astype(jnp.float32))
         + jnp.einsum("bhe,bse->bhs", q_rope[:, 0].astype(jnp.float32),
                      kpe.astype(jnp.float32))) * scale
    pos = jnp.arange(ckv.shape[1])
    s = jnp.where((pos[None, :] < (tb + 1)[:, None])[:, None, :], s, NEG)
    pmax = s.max(-1, keepdims=True)
    w = jnp.exp(s - pmax)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-30)
    ctx = jnp.einsum("bhs,bsr->bhr", w, ckv.astype(jnp.float32))  # (B,H,r)
    out = jnp.einsum("bhr,rhv->bhv", ctx.astype(dt), wuv)
    y = out.reshape(b, 1, -1) @ p["wo"].astype(dt)
    return y, {"ckv": ckv, "kpe": kpe}
