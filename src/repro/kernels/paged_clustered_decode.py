"""Pallas TPU kernel: paged clustered-KV decode over packed ragged rows.

The dense ``clustered_decode`` launch pays ``slots × width`` query rows
(``width`` = the prefill chunk during mixed steps) and reads each slot's
tail ring from a contiguous per-slot buffer.  This kernel removes both
costs for the paged engine:

  * **packed ragged rows** — one row per *real* (slot, position) pair
    this step (every active decode slot's one token ⊕ the admitting
    slots' chunk rows), padded only to the per-shard row bucket.  Compute
    scales with real tokens, not ``slots × width`` (the
    PagedAttention-style ragged batch);
  * **slot runs** — the engine packs a shard's rows in slot order with a
    chunk's rows contiguous, so a *run* (a maximal sequence of
    consecutive real rows of one slot) shares one tail ring and one set
    of centroids.  Each run stages its slot's live ring blocks once, for
    every kv-head (one DMA per block for K and one for V, steered by the
    scalar-prefetched block table), with its centroids and counts, and
    then scores all of its rows together: one ``score_and_combine`` call
    per kv-head with M = rows × G query rows, as the dense kernel scores
    a chunk.  A live block holds a position below min(tw, R); every
    block is live once the ring has wrapped.  Padding rows
    (``qpos1 == 0``) stage and score nothing.

Bit-identity with the dense kernel is deliberate: the staged ring
reproduces the dense kernel's ``(R, Dh)`` tail operand exactly (same f32
casts, same dot_general contractions, same masks), so the paged engine's
greedy tokens match the dense engine's bit for bit — pinned in tests.
Ring offsets no DMA filled hold an earlier run's blocks (or the zeros
written when the launch starts): always finite, and always masked.

Layout (grid = (1,); the kernel walks the N rows itself, so a padding
row or a run's inner row costs a few scalar reads).  Scalar prefetch,
all in SMEM: the flattened row block table (N*T,), row→slot map (N,),
and per row: qpos1 (query position + 1; 0 ⇒ padding row), tw (slot ring
watermark t + chunk_len), cov (coverage frontier), wlo (retention window
floor).  A run's rows share their slot's block-table row and watermark
(the engine gathers both by slot).  Row i marks its place in its run: a
run's first row starts its staging (unless the run before it, ending on
row i - 1, already did); its last row starts the next run's staging into
the other of two ring buffers, waits for its own, and scores the run.
Operands (P = N*G query rows plus one window of padding):
  q        (Hkv, P, Dh)       VMEM — query rows, head-major
  lim      (P, 3) int32       VMEM — qpos1, cov, wlo per query row
  k_cents  (B, C, Hkv*Dh)     HBM  — one slot's (C, Hkv*Dh) staged per
                                     run; v_cents same
  counts   (B, Hkv, 1, C')    HBM  — one slot's per run (C' = C padded to
                                     whole lane tiles)
  k_pool   (nb, bs, Hkv*Dh)   HBM  — one (bs, Hkv*Dh) block per DMA into
                                     a (R, Hkv*Dh) ring buffer; v_pool same
  out      (Hkv, P, Dh)       VMEM — padding rows read 0
Scoring shapes are static: a decode row's window holds its G query rows,
a longer run's W rows (W = min(``width``, N), the launch's chunk; a run
longer than W takes several windows).  A window starts on a whole
sublane tile, so it may also hold rows of neighbouring runs: they are
masked and keep their outputs.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from repro.kernels.clustered_decode import score_and_combine


def _kernel(bt_ref, slot_ref, qpos1_ref, tw_ref, cov_ref, wlo_ref, q_ref,
            lim_ref, kc_hbm, vc_hbm, cnt_hbm, kp_hbm, vp_hbm, o_ref, kt_s,
            vt_s, kc_s, vc_s, cnt_s, sem, *, n: int, g: int, dh: int,
            bs: int, nblk: int, r: int, c: int, w: int, align: int,
            scale: float, softcap):
    hkv = q_ref.shape[0]

    def copies(row, buf):
        """The DMAs that stage row ``row``'s slot into buffer ``buf``,
        each with its guard: centroids and counts, then the ring's live
        blocks (those holding a position below min(tw, R))."""
        sl = slot_ref[row]
        nlive = (jnp.minimum(tw_ref[row], r) + bs - 1) // bs
        out = [(True, pltpu.make_async_copy(src.at[sl], dst.at[buf],
                                            sem.at[buf, k]))
               for k, (src, dst) in enumerate(
                   ((kc_hbm, kc_s), (vc_hbm, vc_s), (cnt_hbm, cnt_s)))]
        for j in range(nblk):
            blk = bt_ref[row * nblk + j]
            for k, (src, dst) in enumerate(((kp_hbm, kt_s), (vp_hbm, vt_s))):
                out.append((j < nlive, pltpu.make_async_copy(
                    src.at[blk], dst.at[buf, pl.ds(j * bs, bs)],
                    sem.at[buf, 3 + k])))
        return out

    def each(ops, method):
        for ok, op in ops:
            pl.when(ok)(getattr(op, method))

    def score_run(start, i, buf):
        """Score the run of rows [start, i] against its slot, staged in
        buffer ``buf``."""
        # ring offset s claims position s while tw <= R, else the wrapped
        # window — identical mask math to the dense kernel, with each
        # row's own absolute position (qpos1 - 1) as the causal bound.
        # ``wlo`` is the row's retention window lower bound (0 under
        # FrontierRetention — cov alone gates; t - window under
        # WindowRetention), masked alongside cov so a retired-but-not-yet
        # -overwritten ring entry can never score
        tw = tw_ref[i]
        sl = jax.lax.broadcasted_iota(jnp.int32, (1, r), 1)
        wrapped = tw - r + jnp.mod(sl - tw, r)
        pos = jnp.where(tw <= r, sl, wrapped)                # (1, R)

        def score(lo, rows):
            """Score the rows of [lo, min(lo + rows, i + 1)), every
            kv-head, through a window of whole sublane tiles; the
            window's other rows keep their outputs."""
            m = _window(rows, g, align)
            a = pl.multiple_of(lo * g // align * align, align)
            lim = lim_ref[pl.ds(a, m), :]
            qpos1, cov, wlo = lim[:, 0:1], lim[:, 1:2], lim[:, 2:3]
            row = (a + jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0)) // g
            row_ok = ((row >= lo) & (row < lo + rows) & (row <= i)
                      & (qpos1 > 0))
            ok = ((pos >= 0) & (pos < qpos1) & (pos >= cov) & (pos >= wlo)
                  & row_ok)
            for h in range(hkv):
                lanes = slice(h * dh, (h + 1) * dh)
                # the scoring body is SHARED with the dense kernel — the
                # staged ring is its (R, Dh) tail operand, so the paged
                # engine's outputs are bit-identical to the dense
                # engine's per row
                out = score_and_combine(
                    q_ref[h, pl.ds(a, m), :].astype(jnp.float32),
                    kc_s[buf, :, lanes].astype(jnp.float32),
                    vc_s[buf, :, lanes].astype(jnp.float32),
                    cnt_s[buf, h, 0, :c].astype(jnp.float32),
                    kt_s[buf, :, lanes].astype(jnp.float32),
                    vt_s[buf, :, lanes].astype(jnp.float32),
                    row_ok, ok, scale=scale, softcap=softcap)
                o_ref[h, pl.ds(a, m), :] = jnp.where(
                    row_ok, out.astype(o_ref.dtype), o_ref[h, pl.ds(a, m), :])

        @pl.when(start == i)
        def _decode_row():
            score(i, 1)

        @pl.when(start < i)
        def _chunk():
            # W rows at a time: one window unless the run is longer than W
            jax.lax.fori_loop(
                0, (i - start + w) // w,
                lambda k, carry: (score(start + k * w, w), carry)[1], None)

    def row(i, carry):
        """Row i's place in its run: a run's first row starts its staging
        (unless the run before it, ending on row i - 1, already did); its
        last row starts the next run's staging into the other buffer,
        waits for its own, and scores the run."""
        start, buf = carry
        prev, nxt = jnp.maximum(i - 1, 0), jnp.minimum(i + 1, n - 1)
        prev_real = (i > 0) & (qpos1_ref[prev] > 0)
        next_real = (i < n - 1) & (qpos1_ref[nxt] > 0)
        real = qpos1_ref[i] > 0
        first = real & ~(prev_real & (slot_ref[prev] == slot_ref[i]))
        last = real & ~(next_real & (slot_ref[nxt] == slot_ref[i]))
        start = jnp.where(first, i, start)
        buf = jnp.where(first, 1 - buf, buf)

        @pl.when(first & ~prev_real)
        def _stage():
            each(copies(i, buf), "start")

        @pl.when(last)
        def _score():
            @pl.when(next_real)
            def _prefetch():
                each(copies(nxt, 1 - buf), "start")

            each(copies(start, buf), "wait")
            score_run(start, i, buf)

        return start, buf

    # ring offsets no DMA fills must be finite (a masked p = 0 times NaN
    # is NaN), and padding rows' outputs are never written
    kt_s[...] = jnp.zeros_like(kt_s)
    vt_s[...] = jnp.zeros_like(vt_s)
    o_ref[...] = jnp.zeros_like(o_ref)
    jax.lax.fori_loop(0, n, row, (jnp.int32(0), jnp.int32(1)))


def _window(rows: int, g: int, align: int) -> int:
    """Query rows of a window that holds ``rows`` packed rows of G query
    rows each from any offset, its start rounded down to ``align``."""
    span = align - math.gcd(g, align) + rows * g
    return -(-span // align) * align


def paged_clustered_decode_pallas(q, k_cents, v_cents, counts, k_pool,
                                  v_pool, row_slot, row_bt, qpos1, tw, cov,
                                  wlo=None, *, scale: float, softcap=None,
                                  width: int | None = None,
                                  interpret: bool | None = None):
    """q (N, Hq, Dh) packed rows; k/v_cents (B, C, Hkv, Dh); counts
    (B, C, Hkv); k/v_pool (nb, bs, Hkv, Dh) block pools; row_slot (N,)
    slot per row; row_bt (N, T) physical block per (row, ring block) —
    every entry must be a valid pool index (the caller maps unallocated
    blocks to a garbage block whose offsets the masks exclude); qpos1
    (N,) = row position + 1 (0 for padding rows); tw (N,) slot ring
    watermark t + chunk_len; cov (N,) coverage frontier; wlo (N,) the
    row's retention window lower bound (None/zeros ⇒ frontier-only
    masking, bit-identical to before); ``width`` (static) the longest
    run the caller packs, its prefill chunk (None ⇒ N).  → (N, Hq, Dh);
    padding rows return zeros the caller must discard."""
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
    w = n if width is None else max(1, min(width, n))
    # dynamic windows start on whole sublane tiles of the q rows' dtype;
    # the row axis is padded so that the widest window never runs past it
    align = 8 * max(1, 4 // q.dtype.itemsize)
    rows_p = n * g + _window(w, g, align)
    # head-major query rows: one kv-head's rows of a run are contiguous
    qh = q.reshape(n, hkv, g, dh).transpose(1, 0, 2, 3).reshape(
        hkv, n * g, dh)
    qh = jnp.pad(qh, ((0, 0), (0, rows_p - n * g), (0, 0)))
    # (B, Hkv, 1, C) padded to whole lane tiles: a DMA moves whole tiles
    cnt_t = jnp.pad(counts.transpose(0, 2, 1).reshape(-1, hkv, 1, c),
                    ((0, 0), (0, 0), (0, 0), (0, -c % 128)))
    # flat (N*T,) table: a 2-D SMEM operand pads its minor dim to 128
    row_bt = jnp.asarray(row_bt, jnp.int32).reshape(n * t_blocks)
    row_slot = jnp.asarray(row_slot, jnp.int32)
    qpos1 = jnp.asarray(qpos1, jnp.int32)
    tw = jnp.asarray(tw, jnp.int32)
    cov = jnp.asarray(cov, jnp.int32)
    if wlo is None:
        wlo = jnp.zeros_like(qpos1)
    wlo = jnp.asarray(wlo, jnp.int32)
    lim = jnp.repeat(jnp.stack([qpos1, cov, wlo], axis=1), g, axis=0)
    lim = jnp.pad(lim, ((0, rows_p - n * g), (0, 0)))

    def lanes(x):                       # (m, n, Hkv, Dh) → (m, n, Hkv*Dh)
        return x.reshape(x.shape[0], x.shape[1], hkv * dh)

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    resident = lambda shape: pl.BlockSpec(                   # noqa: E731
        shape, lambda i, *_: (0,) * len(shape))
    # one grid step: the kernel walks the rows itself, so padding rows and
    # a run's inner rows cost a few scalar reads, not a pipeline step
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,     # row_bt, row_slot, qpos1, tw, cov, wlo
        grid=(1,),
        in_specs=[resident((hkv, rows_p, dh)), resident((rows_p, 3)),
                  hbm, hbm, hbm, hbm, hbm],
        out_specs=resident((hkv, rows_p, dh)),
        scratch_shapes=[
            pltpu.VMEM((2, r, hkv * dh), k_pool.dtype),
            pltpu.VMEM((2, r, hkv * dh), v_pool.dtype),
            pltpu.VMEM((2, c, hkv * dh), k_cents.dtype),
            pltpu.VMEM((2, c, hkv * dh), v_cents.dtype),
            pltpu.VMEM((2,) + cnt_t.shape[1:], cnt_t.dtype),
            pltpu.SemaphoreType.DMA((2, 5)),
        ],
    )
    kernel = functools.partial(_kernel, n=n, g=g, dh=dh, bs=bs,
                               nblk=t_blocks, r=r, c=c, w=w, align=align,
                               scale=scale, softcap=softcap)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((hkv, rows_p, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(row_bt, row_slot, qpos1, tw, cov, wlo, qh, lim, lanes(k_cents),
      lanes(v_cents), cnt_t, lanes(k_pool), lanes(v_pool))
    return out[:, :n * g].reshape(hkv, n, g, dh).transpose(
        1, 0, 2, 3).reshape(n, hq, dh)


def _fold_axis_index(axes, mesh):
    idx = jnp.int32(0)
    for a in axes:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx


def paged_clustered_decode_shardmap(q, k_cents, v_cents, counts, k_pool,
                                    v_pool, row_slot, row_bt, qpos1, tw,
                                    cov, wlo, *, mesh, data_axes,
                                    model_axes, scale: float, softcap=None,
                                    width: int | None = None,
                                    interpret: bool = False):
    """Dispatch the paged kernel once per mesh shard.

    Rows, slots, and the block pool all partition over ``data``
    (contiguous leading-axis shards, so a slot's blocks live on its own
    shard by construction — see runtime/kv_pool.py); kv-heads partition
    over ``model``.  Block ids and slot ids arrive global and
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
            scale=scale, softcap=softcap, width=width, interpret=interpret)

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
