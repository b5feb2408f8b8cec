"""Pallas TPU kernel: fused clustered-KV decode attention, mixed-mode.

Attention over [median centroids ⊕ exact tail ring] — the clustered-
attention estimator of the paper's memory manager — in a single
VMEM-resident pass per (batch, kv-head) grid instance:

  * centroid logits get the +log(count) bias (a centroid standing for m
    keys receives the softmax mass of m identical-score keys); empty
    clusters (count == 0) are masked,
  * tail logits are masked by ring validity (position in [cov, qpos]; the
    positions below ``cov`` are already summarized by centroids, so the
    partition is exact — nothing double-counted, nothing lost),
  * one joint softmax over the concatenated score row and two MXU
    combines against v_cents / v_tail.

**Mixed-mode launch** (chunked prefill interleaved with decode): every
slot carries up to L query rows.  Decode slots use one row (their next
token); a slot admitting a prompt carries a whole chunk whose K/V were
written into its tail ring *before* the launch, so intra-chunk causal
attention falls out of the same ring mask — query row i (absolute
position t + i) sees ring positions < t + i + 1.  Per-slot ``t`` /
``cov`` / ``chunk_len`` vectors come in through SMEM, so decode slots at
different depths and an in-flight prefill chunk score in one launch.
Caller invariant: the chunk's pre-write overwrites ring positions
t+i-R, so ``cov >= t + chunk_len - R`` must hold (the engine's
absorb_chunk pre-pass guarantees it) — the overwritten positions are
then summarized by centroids and nothing is lost.

Layout (grid = (B, Hkv); scalar prefetch: t, cov, chunk_len (B,) in
SMEM, read at ``pl.program_id(0)``).  Head-indexed operands are viewed
with heads folded into the lane axis, so every block's last two dims are
either full or (8, 128)-aligned as Mosaic requires:
  q        (1, 1, L*G, Dh)  VMEM  — this kv-head's query rows, (B, Hkv,
                                    L*G, Dh)
  k_cents  (1, C, Dh)       VMEM  — lane block h of (B, C, Hkv*Dh);
                                    v_cents same
  counts   (1, 1, 1, C)     VMEM  — (B, Hkv, 1, C)
  k_tail   (1, R, Dh)       VMEM  — lane block h of (B, R, Hkv*Dh) (ring
                                    order, chunk rows already written);
                                    v_tail same
  out      (1, 1, L*G, Dh)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

NEG = -1e30


def score_and_combine(q, kc, vc, cnt, kt, vt, row_ok, tail_ok, *,
                      scale: float, softcap):
    """Shared [centroids ⊕ tail ring] joint-softmax body.

    q (rows, Dh) f32 query rows; kc/vc (C, Dh); cnt (C,); kt/vt (R, Dh);
    row_ok broadcastable to (rows, C) — masks invalid/padding rows;
    tail_ok (rows, R) — the full ring validity mask (position window,
    coverage frontier, and row validity pre-combined by the caller).
    Returns (rows, Dh) f32.

    Both the dense ``clustered_decode`` kernel and the paged
    ``paged_clustered_decode`` kernel call THIS function for their
    scoring — bit-identity between the two engines is a hard invariant
    (the paged engine's tokens must equal the dense engine's), so the
    math must never fork."""
    s_c = jax.lax.dot_general(q, kc, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s_c = jnp.tanh(s_c / softcap) * softcap
    cnt_row = cnt[None, :]                                   # (1, C)
    s_c = jnp.where((cnt_row > 0) & row_ok,
                    s_c + jnp.log(jnp.maximum(cnt_row, 1e-9)), NEG)

    s_t = jax.lax.dot_general(q, kt, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s_t = jnp.tanh(s_t / softcap) * softcap
    s_t = jnp.where(tail_ok, s_t, NEG)

    m = jnp.maximum(s_c.max(-1, keepdims=True), s_t.max(-1, keepdims=True))
    p_c = jnp.exp(s_c - m)
    p_t = jnp.exp(s_t - m)
    lsum = p_c.sum(-1, keepdims=True) + p_t.sum(-1, keepdims=True)
    acc = (jax.lax.dot_general(p_c, vc, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
           + jax.lax.dot_general(p_t, vt, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32))
    return acc / jnp.maximum(lsum, 1e-30)


def _kernel(t_ref, cov_ref, len_ref, q_ref, kc_ref, vc_ref, cnt_ref, kt_ref,
            vt_ref, o_ref, *, l: int, g: int, r: int, scale: float, softcap):
    i = pl.program_id(0)
    t = t_ref[i]
    cov = cov_ref[i]
    cl = len_ref[i]
    q = q_ref[0, 0].astype(jnp.float32)                      # (L*G, Dh)
    kc = kc_ref[0].astype(jnp.float32)                       # (C, Dh)
    vc = vc_ref[0].astype(jnp.float32)
    cnt = cnt_ref[0, 0, 0].astype(jnp.float32)               # (C,)
    kt = kt_ref[0].astype(jnp.float32)                       # (R, Dh)
    vt = vt_ref[0].astype(jnp.float32)

    # query row i*g + j carries chunk index i → absolute position t + i
    li = jax.lax.broadcasted_iota(jnp.int32, (l * g, 1), 0) // g
    row_ok = li < cl

    # chunk rows sit in the ring already: tw = t + cl entries total.  Ring
    # slot s holds position s while tw <= R, else the wrapped window.
    sl = jax.lax.broadcasted_iota(jnp.int32, (1, r), 1)
    tw = t + cl
    wrapped = tw - r + jnp.mod(sl - tw, r)
    pos = jnp.where(tw <= r, sl, wrapped)                    # (1, R)
    qpos = t + li                                            # (L*G, 1)
    ok = (pos >= 0) & (pos < qpos + 1) & (pos >= cov) & row_ok

    out = score_and_combine(q, kc, vc, cnt, kt, vt, row_ok, ok,
                            scale=scale, softcap=softcap)
    o_ref[0, 0] = out.astype(o_ref.dtype)


def clustered_decode_shardmap(q, k_cents, v_cents, counts, k_tail, v_tail,
                              t, cov, chunk_len=None, *, mesh, data_axes,
                              model_axes, scale: float, softcap=None,
                              interpret: bool = False):
    """Dispatch the Pallas kernel once per mesh shard.

    The kernel grid is (batch, kv-head) and every grid cell is independent,
    so a (data, model)-sharded launch is exact: each shard runs the same
    kernel on its local (B/d, Hkv/m) block — no collectives, and the
    existing interpret-mode CPU fallback applies per shard unchanged.

    ``data_axes`` / ``model_axes`` are the mesh axis tuples partitioning the
    batch / head dims (either may be None → replicated along that dim); the
    caller (kernels.ops) checks divisibility before choosing them.  t / cov
    / chunk_len must already be (B,) vectors so they shard with the batch.
    """
    b = q.shape[0]
    t = jnp.broadcast_to(jnp.asarray(t, jnp.int32), (b,))
    cov = jnp.broadcast_to(jnp.asarray(cov, jnp.int32), (b,))
    if chunk_len is None:
        chunk_len = jnp.ones((b,), jnp.int32)
    chunk_len = jnp.broadcast_to(jnp.asarray(chunk_len, jnp.int32), (b,))
    qspec = P(data_axes, model_axes, None) if q.ndim == 3 else \
        P(data_axes, None, model_axes, None)
    d, m = data_axes, model_axes
    f = jax.shard_map(
        functools.partial(clustered_decode_pallas, scale=scale,
                          softcap=softcap, interpret=interpret),
        mesh=mesh,
        in_specs=(
            qspec,                # q        (B, [L,] Hq, Dh)
            P(d, None, m, None),  # k_cents  (B, C, Hkv, Dh)
            P(d, None, m, None),  # v_cents
            P(d, None, m),        # counts   (B, C, Hkv)
            P(d, None, m, None),  # k_tail   (B, R, Hkv, Dh)
            P(d, None, m, None),  # v_tail
            P(d),                 # t        (B,)
            P(d),                 # cov      (B,)
            P(d),                 # chunk_len (B,)
        ),
        out_specs=qspec,
        check_vma=False,  # the Pallas call has no replication rule
    )
    return f(q, k_cents, v_cents, counts, k_tail, v_tail, t, cov, chunk_len)


def clustered_decode_pallas(q, k_cents, v_cents, counts, k_tail, v_tail,
                            t, cov, chunk_len=None, *, scale: float,
                            softcap=None, interpret: bool | None = None):
    """q (B, Hq, Dh) decode form, or (B, L, Hq, Dh) mixed form with
    per-slot ``chunk_len`` (B,) valid rows; k/v_cents (B, C, Hkv, Dh);
    counts (B, C, Hkv); k/v_tail (B, R, Hkv, Dh) ring-ordered with the
    chunk rows already written; t, cov (B,) int32 → output shaped like q.
    Rows at index >= chunk_len are fully masked and must be discarded by
    the caller (their softmax is a degenerate uniform)."""
    if interpret is None:
        from repro.kernels.ops import interpret_default
        interpret = interpret_default()
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    b, l, hq, dh = q.shape
    c = k_cents.shape[1]
    r = k_tail.shape[1]
    hkv = k_cents.shape[2]
    g = hq // hkv
    qh = q.reshape(b, l, hkv, g, dh).transpose(0, 2, 1, 3, 4).reshape(
        b, hkv, l * g, dh)
    cnt_t = counts.transpose(0, 2, 1).reshape(b, hkv, 1, c)  # (B, Hkv, 1, C)
    t = jnp.broadcast_to(jnp.asarray(t, jnp.int32), (b,))
    cov = jnp.broadcast_to(jnp.asarray(cov, jnp.int32), (b,))
    if chunk_len is None:
        chunk_len = jnp.ones((b,), jnp.int32)
    chunk_len = jnp.broadcast_to(jnp.asarray(chunk_len, jnp.int32), (b,))

    def lanes(x):                       # (B, n, Hkv, Dh) → (B, n, Hkv*Dh)
        return x.reshape(x.shape[0], x.shape[1], hkv * dh)

    head_block = lambda n: pl.BlockSpec(                     # noqa: E731
        (1, n, dh), lambda i, h, *_: (i, 0, h))
    rows_block = pl.BlockSpec((1, 1, l * g, dh),
                              lambda i, h, *_: (i, h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                # t, cov, chunk_len
        grid=(b, hkv),
        in_specs=[
            rows_block,
            head_block(c),
            head_block(c),
            pl.BlockSpec((1, 1, 1, c), lambda i, h, *_: (i, h, 0, 0)),
            head_block(r),
            head_block(r),
        ],
        out_specs=rows_block,
    )
    out = pl.pallas_call(
        functools.partial(_kernel, l=l, g=g, r=r, scale=scale,
                          softcap=softcap),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, l * g, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(t, cov, chunk_len, qh, lanes(k_cents), lanes(v_cents), cnt_t,
      lanes(k_tail), lanes(v_tail))
    out = out.reshape(b, hkv, l, g, dh).transpose(0, 2, 1, 3, 4).reshape(
        b, l, hq, dh)
    return out[:, 0] if squeeze else out
