"""Pallas TPU kernel: paged clustered-KV decode over packed ragged rows.

The dense ``clustered_decode`` launch pays ``slots × width`` query rows
(``width`` = the prefill chunk during mixed steps) and reads each slot's
tail ring from a contiguous per-slot buffer.  This kernel removes both
costs for the paged engine:

  * **packed ragged rows** — the grid's first dimension is the number of
    *real* (slot, position) pairs this step (every active decode slot's
    one token ⊕ the admitting slots' chunk rows), padded only to the
    per-shard row bucket.  Compute scales with real tokens, not
    ``slots × width`` (the PagedAttention-style ragged batch);
  * **block-table gathers** — each row's tail ring is scattered across
    fixed-size pool blocks; the row's block table (scalar-prefetched, so
    the index maps can steer the DMA) walks the grid's trailing dimension
    and stages one block per step into a VMEM scratch ring, then the last
    step runs the identical [centroids ⊕ ring] joint softmax as the dense
    kernel.

Bit-identity with the dense kernel is deliberate: the staged scratch ring
reproduces the dense kernel's ``(R, Dh)`` tail operand exactly (same f32
casts, same dot_general contractions, same mask order), so the paged
engine's greedy tokens match the dense engine's bit for bit — pinned in
tests.

Layout (grid = (N rows, Hkv, T tail blocks)).  Scalar prefetch, all in
SMEM: the flattened row block table (N*T,), row→slot map (N,), and per
row: qpos1 (query position + 1; 0 ⇒ padding row, fully masked), tw (slot
ring watermark t + chunk_len), cov (coverage frontier), wlo (retention
window floor).  Head-indexed operands fold heads into the lane axis so
every block's last two dims are full or (8, 128)-aligned:
  q        (1, 1, G, Dh)  VMEM  — this row × kv-head's query
  k_cents  (1, C, Dh)     VMEM  — lane block h of (B, C, Hkv*Dh),
                                  gathered per row via the slot map
  counts   (1, 1, 1, C)   VMEM  — (B, Hkv, 1, C)
  k_pool   (1, bs, Dh)    VMEM  — lane block h of one physical tail block
                                  of (nb, bs, Hkv*Dh) per grid step,
                                  gathered via the block table
  out      (1, 1, G, Dh)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from repro.kernels.clustered_decode import score_and_combine


def _kernel(bt_ref, slot_ref, qpos1_ref, tw_ref, cov_ref, wlo_ref, q_ref,
            kc_ref, vc_ref, cnt_ref, kp_ref, vp_ref, o_ref, kt_s, vt_s, *,
            bs: int, nblk: int, r: int, scale: float, softcap):
    i, j = pl.program_id(0), pl.program_id(2)
    # stage this row's tail block j into the scratch ring at its ring
    # offsets [j*bs, (j+1)*bs) — after the last step the scratch holds the
    # same (R, Dh) f32 operand the dense kernel reads contiguously
    kt_s[pl.ds(j * bs, bs), :] = kp_ref[0].astype(jnp.float32)
    vt_s[pl.ds(j * bs, bs), :] = vp_ref[0].astype(jnp.float32)

    @pl.when(j == nblk - 1)
    def _compute():
        qpos1 = qpos1_ref[i]
        tw = tw_ref[i]
        cov = cov_ref[i]
        wlo = wlo_ref[i]
        q = q_ref[0, 0].astype(jnp.float32)                  # (G, Dh)
        kc = kc_ref[0].astype(jnp.float32)                   # (C, Dh)
        vc = vc_ref[0].astype(jnp.float32)
        cnt = cnt_ref[0, 0, 0].astype(jnp.float32)           # (C,)

        row_ok = qpos1 > 0                                   # padding row?

        # ring offset s claims position s while tw <= R, else the wrapped
        # window — identical mask math to the dense kernel, with the
        # row's own absolute position (qpos1 - 1) as the causal bound.
        # ``wlo`` is the row's retention window lower bound (0 under
        # FrontierRetention — cov alone gates; t - window under
        # WindowRetention), masked alongside cov so a retired-but-not-yet
        # -overwritten ring entry can never score
        sl = jax.lax.broadcasted_iota(jnp.int32, (1, r), 1)
        wrapped = tw - r + jnp.mod(sl - tw, r)
        pos = jnp.where(tw <= r, sl, wrapped)                # (1, R)
        ok = ((pos >= 0) & (pos < qpos1) & (pos >= cov) & (pos >= wlo)
              & row_ok)

        # the scoring body is SHARED with the dense kernel — the staged
        # scratch ring is its (R, Dh) tail operand, so the paged engine's
        # outputs are bit-identical to the dense engine's per row
        out = score_and_combine(q, kc, vc, cnt, kt_s[:], vt_s[:],
                                row_ok, ok, scale=scale, softcap=softcap)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def paged_clustered_decode_pallas(q, k_cents, v_cents, counts, k_pool,
                                  v_pool, row_slot, row_bt, qpos1, tw, cov,
                                  wlo=None, *, scale: float, softcap=None,
                                  interpret: bool | None = None):
    """q (N, Hq, Dh) packed rows; k/v_cents (B, C, Hkv, Dh); counts
    (B, C, Hkv); k/v_pool (nb, bs, Hkv, Dh) block pools; row_slot (N,)
    slot per row; row_bt (N, T) physical block per (row, ring block) —
    every entry must be a valid pool index (the caller maps unallocated
    blocks to a garbage block whose offsets the masks exclude); qpos1
    (N,) = row position + 1 (0 for padding rows); tw (N,) slot ring
    watermark t + chunk_len; cov (N,) coverage frontier; wlo (N,) the
    row's retention window lower bound (None/zeros ⇒ frontier-only
    masking, bit-identical to before).  → (N, Hq, Dh); padding rows
    return a degenerate uniform the caller must discard."""
    if interpret is None:
        from repro.kernels.ops import interpret_default
        interpret = interpret_default()
    n, hq, dh = q.shape
    c = k_cents.shape[1]
    hkv = k_cents.shape[2]
    g = hq // hkv
    bs = k_pool.shape[1]
    t_blocks = row_bt.shape[1]
    r = t_blocks * bs
    qh = q.reshape(n, hkv, g, dh)
    cnt_t = counts.transpose(0, 2, 1).reshape(-1, hkv, 1, c)  # (B, Hkv, 1, C)
    # flat (N*T,) table: a 2-D SMEM operand pads its minor dim to 128
    row_bt = jnp.asarray(row_bt, jnp.int32).reshape(n * t_blocks)
    row_slot = jnp.asarray(row_slot, jnp.int32)
    qpos1 = jnp.asarray(qpos1, jnp.int32)
    tw = jnp.asarray(tw, jnp.int32)
    cov = jnp.asarray(cov, jnp.int32)
    if wlo is None:
        wlo = jnp.zeros_like(qpos1)
    wlo = jnp.asarray(wlo, jnp.int32)

    def lanes(x):                       # (m, n, Hkv, Dh) → (m, n, Hkv*Dh)
        return x.reshape(x.shape[0], x.shape[1], hkv * dh)

    cent_block = pl.BlockSpec(
        (1, c, dh), lambda i, h, j, bt, sl, *_: (sl[i], 0, h))
    pool_block = pl.BlockSpec(
        (1, bs, dh), lambda i, h, j, bt, sl, *_: (bt[i * t_blocks + j], 0, h))
    rows_block = pl.BlockSpec((1, 1, g, dh), lambda i, h, *_: (i, h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,     # row_bt, row_slot, qpos1, tw, cov, wlo
        grid=(n, hkv, t_blocks),
        in_specs=[
            rows_block,
            cent_block,
            cent_block,
            pl.BlockSpec((1, 1, 1, c),
                         lambda i, h, j, bt, sl, *_: (sl[i], h, 0, 0)),
            pool_block,
            pool_block,
        ],
        out_specs=rows_block,
        scratch_shapes=[
            pltpu.VMEM((r, dh), jnp.float32),
            pltpu.VMEM((r, dh), jnp.float32),
        ],
    )
    kernel = functools.partial(_kernel, bs=bs, nblk=t_blocks, r=r,
                               scale=scale, softcap=softcap)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, hkv, g, dh), q.dtype),
        # rows/heads may split across cores (each core's scratch ring is
        # private); the tail-block walk must stay sequential per (row,
        # head) so the staging completes before the compute step
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(row_bt, row_slot, qpos1, tw, cov, wlo, qh, lanes(k_cents),
      lanes(v_cents), cnt_t, lanes(k_pool), lanes(v_pool))
    return out.reshape(n, hq, dh)


def _fold_axis_index(axes, mesh):
    idx = jnp.int32(0)
    for a in axes:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx


def paged_clustered_decode_shardmap(q, k_cents, v_cents, counts, k_pool,
                                    v_pool, row_slot, row_bt, qpos1, tw,
                                    cov, wlo, *, mesh, data_axes,
                                    model_axes, scale: float, softcap=None,
                                    interpret: bool = False):
    """Dispatch the paged kernel once per mesh shard.

    Rows, slots, and the block pool all partition over ``data``
    (contiguous leading-axis shards, so a slot's blocks live on its own
    shard by construction — see runtime/kv_pool.py); kv-head grid cells
    partition over ``model``.  Block ids and slot ids arrive global and
    are rebased to the local shard inside the island, so the engine keeps
    a single flat table."""
    d, m = data_axes, model_axes

    def body(q, kc, vc, cnt, kp, vp, rs, rbt, qp1, tw_, cov_, wlo_):
        if d:
            di = _fold_axis_index(d, mesh)
            rs = rs - di * kc.shape[0]
            rbt = rbt - di * kp.shape[0]
        return paged_clustered_decode_pallas(
            q, kc, vc, cnt, kp, vp, rs, rbt, qp1, tw_, cov_, wlo_,
            scale=scale, softcap=softcap, interpret=interpret)

    f = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(d, m, None),        # q        (N, Hq, Dh)
            P(d, None, m, None),  # k_cents  (B, C, Hkv, Dh)
            P(d, None, m, None),  # v_cents
            P(d, None, m),        # counts   (B, C, Hkv)
            P(d, None, m, None),  # k_pool   (nb, bs, Hkv, Dh)
            P(d, None, m, None),  # v_pool
            P(d),                 # row_slot (N,)
            P(d, None),           # row_bt   (N, T)
            P(d),                 # qpos1    (N,)
            P(d),                 # tw       (N,)
            P(d),                 # cov      (N,)
            P(d),                 # wlo      (N,) retention window floor
        ),
        out_specs=P(d, m, None),
        check_vma=False,  # the Pallas call has no replication rule
    )
    return f(q, k_cents, v_cents, counts, k_pool, v_pool, row_slot, row_bt,
             qpos1, tw, cov, wlo)
