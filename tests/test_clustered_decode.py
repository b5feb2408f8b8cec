"""Clustered-KV decode path tests.

With empty centroids (counts=0) the clustered path must EXACTLY match
exact-cache decode while positions fit in the tail ring — pins masking,
ring indexing, and the count-bias math.  A second test fills centroids
from the paper's compressor and checks the approximation is close."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import attention as attn
from repro.models import transformer as tfm
from repro.core import kv_compress


def f32(cfg):
    return dataclasses.replace(cfg, dtype="float32")


def test_empty_centroids_match_exact_within_tail():
    cfg = f32(configs.get_reduced("qwen3-4b"))
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, size=(2, 10)), jnp.int32)

    cache_e = tfm.init_cache(cfg, 2, 32)
    cache_c = tfm.init_cache(cfg, 2, 32, kv_mode="clustered",
                             kv_clusters=8, kv_tail=16)
    step = lambda c, tk, t: tfm.decode_step(params, cfg, c, tk, t)
    for t in range(10):
        le, cache_e = step(cache_e, toks[:, t:t + 1], jnp.int32(t))
        lc, cache_c = step(cache_c, toks[:, t:t + 1], jnp.int32(t))
        np.testing.assert_allclose(np.asarray(le), np.asarray(lc),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"t={t}")


def test_compressed_centroids_approximate_attention():
    cfg = f32(configs.get_reduced("qwen3-4b"))
    p = attn.init_attn(jax.random.PRNGKey(1), cfg)
    rng = np.random.default_rng(1)
    b, s = 1, 96
    hkv, dh = cfg.n_kv_heads, cfg.head_dim
    # clustery keys
    centers = rng.normal(size=(6, dh)) * 2
    k = jnp.asarray(centers[rng.integers(0, 6, size=(b, s, hkv))]
                    + rng.normal(size=(b, s, hkv, dh)) * 0.1, jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, hkv, dh)), jnp.float32)

    # exact decode attention at t = s
    q = jnp.asarray(rng.normal(size=(b, cfg.n_heads, dh)), jnp.float32)
    out_e = attn.decode_attention(q, k, v, t=s, scale=dh**-0.5)

    # compress prefix (no tail for comparability), build clustered cache
    ccfg = kv_compress.KVCompressConfig(n_clusters=12, iters=8,
                                        keep_recent=16)
    ckv = kv_compress.compress_cache(k[0], v[0], ccfg)
    cache = {
        "k_cents": ckv.k_cents.transpose(1, 0, 2)[None],   # (1, C, H, Dh)
        "v_cents": ckv.v_cents.transpose(1, 0, 2)[None],
        "counts": ckv.counts.T[None],                      # (1, C, H)
        "k_tail": jnp.zeros((b, 16, hkv, dh), jnp.float32).at[:, :16].set(
            ckv.k_tail.transpose(1, 0, 2)[None]),
        "v_tail": jnp.zeros((b, 16, hkv, dh), jnp.float32).at[:, :16].set(
            ckv.v_tail.transpose(1, 0, 2)[None]),
    }
    # clustered attention via the layer path needs x; test the math directly
    from repro.models.attention import attn_decode_clustered  # noqa: F401
    # score/combine mirror kv_compress.clustered_attention per head group:
    out_c = []
    for h in range(cfg.n_heads):
        kvh = h * hkv // cfg.n_heads
        ck = kv_compress.CompressedKV(
            k_cents=ckv.k_cents[kvh:kvh + 1], v_cents=ckv.v_cents[kvh:kvh + 1],
            counts=ckv.counts[kvh:kvh + 1], k_tail=ckv.k_tail[kvh:kvh + 1],
            v_tail=ckv.v_tail[kvh:kvh + 1])
        out_c.append(kv_compress.clustered_attention(
            q[0, h:h + 1], ck, scale=dh**-0.5))
    out_c = jnp.stack(out_c, 0)[None, :, 0]
    rel = float(jnp.linalg.norm(out_c - out_e)
                / jnp.maximum(jnp.linalg.norm(out_e), 1e-9))
    assert rel < 0.25, rel


def test_mixed_mode_kernel_matches_stepwise_decode():
    """The mixed-mode launch (prompt chunk + decode rows in one call) must
    reproduce the one-token path run L times: feeding a chunk's rows one
    at a time through the single-row kernel — writing each row into the
    ring before its own scoring — yields the same outputs as scoring the
    whole chunk in one fused call with the rows pre-written.  Pins the
    per-row position masks (intra-chunk causality via the ring) and the
    SMEM chunk_len plumbing."""
    from repro.kernels.clustered_decode import clustered_decode_pallas
    rng = np.random.default_rng(11)
    c, r, hq, hkv, dh, L = 6, 8, 4, 2, 16, 5
    # mid-stream slot, ring wrapped.  The chunk's pre-write overwrites
    # ring positions t0+i-r (the oldest live entries), so the engine
    # invariant cov >= t0 + L - r must hold — those positions are then
    # already summarized by centroids and masked from the ring either way
    t0, cov = 9, 6
    k_cents = jnp.asarray(rng.normal(size=(1, c, hkv, dh)), jnp.float32)
    v_cents = jnp.asarray(rng.normal(size=(1, c, hkv, dh)), jnp.float32)
    counts = jnp.asarray(rng.uniform(0, 3, size=(1, c, hkv)), jnp.float32)
    k_tail = jnp.asarray(rng.normal(size=(1, r, hkv, dh)), jnp.float32)
    v_tail = jnp.asarray(rng.normal(size=(1, r, hkv, dh)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(1, L, hq, dh)), jnp.float32)
    k_new = jnp.asarray(rng.normal(size=(L, hkv, dh)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(L, hkv, dh)), jnp.float32)

    # reference: one row at a time (write row i at slot (t0+i) % r, score)
    kt, vt = k_tail, v_tail
    want = []
    for i in range(L):
        slot = (t0 + i) % r
        kt = kt.at[:, slot].set(k_new[i][None])
        vt = vt.at[:, slot].set(v_new[i][None])
        out = clustered_decode_pallas(
            q[:, i], k_cents, v_cents, counts, kt, vt,
            jnp.asarray([t0 + i], jnp.int32), jnp.asarray([cov], jnp.int32),
            scale=dh**-0.5)
        want.append(np.asarray(out))

    # fused: all rows pre-written, one launch with chunk_len = L
    kt2, vt2 = k_tail, v_tail
    for i in range(L):
        kt2 = kt2.at[:, (t0 + i) % r].set(k_new[i][None])
        vt2 = vt2.at[:, (t0 + i) % r].set(v_new[i][None])
    got = clustered_decode_pallas(
        q, k_cents, v_cents, counts, kt2, vt2,
        jnp.asarray([t0], jnp.int32), jnp.asarray([cov], jnp.int32),
        jnp.asarray([L], jnp.int32), scale=dh**-0.5)
    for i in range(L):
        np.testing.assert_allclose(np.asarray(got)[:, i], want[i],
                                   rtol=1e-5, atol=1e-5, err_msg=f"row {i}")


def test_mixed_mode_masks_rows_past_chunk_len():
    """Rows at index >= chunk_len are garbage by contract, but rows below
    must be unaffected by their presence (mask isolation)."""
    from repro.kernels.clustered_decode import clustered_decode_pallas
    rng = np.random.default_rng(12)
    c, r, hq, hkv, dh, L = 4, 8, 2, 1, 8, 4
    args = dict(
        k_cents=jnp.asarray(rng.normal(size=(1, c, hkv, dh)), jnp.float32),
        v_cents=jnp.asarray(rng.normal(size=(1, c, hkv, dh)), jnp.float32),
        counts=jnp.asarray(rng.uniform(1, 2, size=(1, c, hkv)), jnp.float32),
        k_tail=jnp.asarray(rng.normal(size=(1, r, hkv, dh)), jnp.float32),
        v_tail=jnp.asarray(rng.normal(size=(1, r, hkv, dh)), jnp.float32))
    q = jnp.asarray(rng.normal(size=(1, L, hq, dh)), jnp.float32)
    t = jnp.asarray([5], jnp.int32)
    cov = jnp.asarray([1], jnp.int32)
    out2 = clustered_decode_pallas(q, *args.values(), t, cov,
                                   jnp.asarray([2], jnp.int32),
                                   scale=dh**-0.5)
    q_junk = q.at[:, 2:].set(999.0)      # junk beyond chunk_len
    out2b = clustered_decode_pallas(q_junk, *args.values(), t, cov,
                                    jnp.asarray([2], jnp.int32),
                                    scale=dh**-0.5)
    np.testing.assert_array_equal(np.asarray(out2)[:, :2],
                                  np.asarray(out2b)[:, :2])


# packed-row layouts: a slot id stands for all of its rows (slot 0 admits
# a chunk of L rows, the others decode one), "-" for a padding row;
# ``width`` is the kernel's static chunk width (None: the row count)
PACKINGS = {
    "today": ([0, 1, 2, 3, "-", "-"], None),
    "chunk_first": ([0, 1, 2, 3], 5),
    "chunk_last_short": ([1, 2, 3, 0], 8),
    "two_shards": ([1, 0, "-", 3, 2, "-"], 5),
    "padding_between": ([0, "-", 1, "-", "-", 2, "-", 3], 5),
    "unwrapped_after_wrapped": ([2, 3, 1, 0, "-"], 5),
    "run_longer_than_width": ([3, 0, 2, 1], 2),
    "width_one": ([2, 0, 1, 3, "-"], 1),
}


def _paged_case(packing, rng, *, t, cl, cov, bt):
    """Dense mixed-mode operands for slots at depths ``t`` with chunk
    lengths ``cl`` and coverage frontiers ``cov``, and the paged view of
    them: rows packed as
    ``packing`` says, each slot's ring read through its row of the
    block table ``bt`` (B, T) from pools holding the same bytes."""
    B, C, R, hq, hkv, dh = len(t), 6, 16, 4, 2, 16
    T = bt.shape[1]
    bs = R // T
    L = int(cl.max())
    k_cents = jnp.asarray(rng.normal(size=(B, C, hkv, dh)), jnp.float32)
    v_cents = jnp.asarray(rng.normal(size=(B, C, hkv, dh)), jnp.float32)
    counts = jnp.asarray(rng.uniform(0, 3, size=(B, C, hkv)), jnp.float32)
    nb = int(bt.max()) + 1
    k_pool = jnp.asarray(rng.normal(size=(nb, bs, hkv, dh)), jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=(nb, bs, hkv, dh)), jnp.float32)
    # the dense ring of each slot is what its table row reads
    k_tail = k_pool[bt].reshape(B, R, hkv, dh)
    v_tail = v_pool[bt].reshape(B, R, hkv, dh)
    cov = jnp.asarray(cov, jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, L, hq, dh)), jnp.float32)
    dense = (q, k_cents, v_cents, counts, k_tail, v_tail,
             jnp.asarray(t, jnp.int32), cov, jnp.asarray(cl, jnp.int32))
    rows = []
    for s in packing:
        rows += ([None] if s == "-" else [(s, i) for i in range(cl[s])])
    slot = np.asarray([0 if r is None else r[0] for r in rows], np.int32)
    pos = np.asarray([-1 if r is None else t[r[0]] + r[1] for r in rows],
                     np.int32)
    qp = jnp.stack([jnp.zeros((hq, dh), jnp.float32) if r is None
                    else q[r[0], r[1]] for r in rows])
    qpos1 = jnp.asarray(np.where(pos >= 0, pos + 1, 0), jnp.int32)
    tw = jnp.asarray((t + cl)[slot], jnp.int32)
    paged = (qp, k_cents, v_cents, counts, k_pool, v_pool,
             jnp.asarray(slot), jnp.asarray(bt[slot]), qpos1, tw, cov[slot])
    return dense, paged, rows


@pytest.mark.parametrize("packing", list(PACKINGS))
def test_paged_kernel_bit_identical_to_dense(packing):
    """The packed ragged paged kernel must reproduce the dense mixed-mode
    kernel BIT-exactly per (slot, position) row: same staged f32 tail
    operand, same dot_general contractions, same mask order — that is
    what makes the paged engine token-identical to the dense engine.
    Covers wrapped rings, a mid-flight chunk, decode rows at mixed
    depths, and fully-masked padding rows, under every packing of slot
    runs the kernel stages and scores: a chunk run first or last (and
    shorter than the width), runs parted by padding, two runs reading
    one physical block table, a short unwrapped ring after wrapped ones,
    and runs longer than the scoring window."""
    from repro.kernels.clustered_decode import clustered_decode_pallas
    from repro.kernels.paged_clustered_decode import (
        paged_clustered_decode_pallas)
    order, width = PACKINGS[packing]
    B, T = 4, 4
    bt = np.arange(B * T, dtype=np.int32).reshape(B, T)
    if packing == "two_shards":
        bt[3] = bt[1]               # slot 3 reads slot 1's blocks
    t = np.asarray([9, 3, 30, 21], np.int32)        # pre/post ring wrap
    cl = np.asarray([5, 1, 1, 1], np.int32)         # slot 0 admits a chunk
    dense_args, paged_args, rows = _paged_case(
        order, np.random.default_rng(7), t=t, cl=cl, cov=[6, 0, 20, 10],
        bt=bt)
    dh = dense_args[0].shape[-1]
    dense = clustered_decode_pallas(*dense_args, scale=dh**-0.5)
    got = np.asarray(paged_clustered_decode_pallas(
        *paged_args, scale=dh**-0.5, width=width))
    assert got.shape == (len(rows),) + dense.shape[2:]
    assert np.isfinite(got).all()
    for ri, row in enumerate(rows):
        if row is not None:
            np.testing.assert_array_equal(got[ri],
                                          np.asarray(dense)[row],
                                          err_msg=f"row {row}")


def test_paged_kernel_reads_no_block_past_the_watermark():
    """A pool block that holds no position below a run's watermark
    (min(tw, R)) is never staged: two launches whose pools differ only
    in those blocks, finite in one and NaN in the other, give the same
    real rows bit for bit, and every row, padding included, is
    finite."""
    from repro.kernels.paged_clustered_decode import (
        paged_clustered_decode_pallas)
    B, T = 4, 4
    garbage = B * T                 # unmapped table entries, as served
    t = np.asarray([2, 3, 30, 8], np.int32)
    cl = np.asarray([5, 1, 1, 1], np.int32)
    live = -(-np.minimum(t + cl, 16) // 4)          # (2, 1, 4, 3) blocks
    bt = np.arange(B * T, dtype=np.int32).reshape(B, T)
    bt = np.where(np.arange(T)[None] < live[:, None], bt, garbage)
    _, args, rows = _paged_case([1, 0, "-", 3, 2, "-"],
                                np.random.default_rng(3), t=t, cl=cl,
                                cov=[0, 1, 17, 2], bt=bt.astype(np.int32))
    dead = np.setdiff1d(np.arange(B * T + 1), bt[bt != garbage])
    poisoned = list(args)
    for k in (4, 5):
        poisoned[k] = args[k].at[dead].set(jnp.nan)
    dh = args[0].shape[-1]
    run = lambda a: np.asarray(paged_clustered_decode_pallas(  # noqa: E731
        *a, scale=dh**-0.5, width=5))
    clean, dirty = run(args), run(poisoned)
    assert np.isfinite(clean).all() and np.isfinite(dirty).all()
    real = [ri for ri, row in enumerate(rows) if row is not None]
    np.testing.assert_array_equal(dirty[real], clean[real])


def test_paged_kernel_window_floor_masks_like_cov():
    """The per-row retention window floor ``wlo`` (WindowRetention's
    ``t - window``) must gate ring scoring exactly like the coverage
    frontier — the kernel ANDs ``pos >= cov`` with ``pos >= wlo``, so a
    launch with (cov, wlo) is bit-identical to one with
    (max(cov, wlo), 0), and omitting ``wlo`` reproduces the pre-policy
    frontier-only masking bit-exactly."""
    from repro.kernels.paged_clustered_decode import (
        paged_clustered_decode_pallas)
    rng = np.random.default_rng(11)
    B, C, R, hq, hkv, dh = 3, 4, 16, 4, 2, 16
    bs = 4
    T = R // bs
    k_cents = jnp.asarray(rng.normal(size=(B, C, hkv, dh)), jnp.float32)
    v_cents = jnp.asarray(rng.normal(size=(B, C, hkv, dh)), jnp.float32)
    counts = jnp.asarray(rng.uniform(0, 3, size=(B, C, hkv)), jnp.float32)
    k_tail = jnp.asarray(rng.normal(size=(B, R, hkv, dh)), jnp.float32)
    v_tail = jnp.asarray(rng.normal(size=(B, R, hkv, dh)), jnp.float32)
    k_pool = k_tail.reshape(B * T, bs, hkv, dh)
    v_pool = v_tail.reshape(B * T, bs, hkv, dh)
    bt = jnp.arange(B * T, dtype=jnp.int32).reshape(B, T)
    # decode rows pre/post ring wrap; window floors above AND below cov
    t = jnp.asarray([9, 30, 21], jnp.int32)
    cov = jnp.asarray([2, 18, 0], jnp.int32)
    wlo = jnp.asarray([5, 22, 8], jnp.int32)
    row_slot = jnp.arange(B, dtype=jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, hq, dh)), jnp.float32)
    run = lambda c, w: paged_clustered_decode_pallas(  # noqa: E731
        q, k_cents, v_cents, counts, k_pool, v_pool, row_slot, bt,
        t + 1, t + 1, c, w, scale=dh**-0.5)
    got = run(cov, wlo)
    want = run(jnp.maximum(cov, wlo), jnp.zeros_like(wlo))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the floor really engaged: every row masks more than frontier-only
    base = run(cov, jnp.zeros_like(wlo))
    assert (np.abs(np.asarray(got) - np.asarray(base)).max(axis=(1, 2))
            > 0).all(), "wlo floors masked nothing"
    # None defaults to zeros — bit-identical to the pre-policy behavior
    none = paged_clustered_decode_pallas(
        q, k_cents, v_cents, counts, k_pool, v_pool, row_slot, bt,
        t + 1, t + 1, cov, scale=dh**-0.5)
    np.testing.assert_array_equal(np.asarray(none), np.asarray(base))


def test_int8_kv_decode_close_to_bf16():
    """int8 KV cache with per-head scales ≈ exact decode (scales set from
    observed key/value ranges)."""
    cfg = f32(configs.get_reduced("qwen3-4b"))
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(2)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, size=(2, 12)), jnp.int32)

    cache_e = tfm.init_cache(cfg, 2, 32)
    cache_q = tfm.init_cache(cfg, 2, 32, kv_mode="int8")
    # set plausible static scales (production: calibrated at prefill)
    cache_q = jax.tree.map(
        lambda l: (jnp.full(l.shape, 0.05, l.dtype)
                   if l.dtype == jnp.float32 and l.ndim == 1 else l), cache_q)
    step = lambda c, tk, t: tfm.decode_step(params, cfg, c, tk, t)
    ok = 0
    for t in range(12):
        le, cache_e = step(cache_e, toks[:, t:t + 1], jnp.int32(t))
        lq, cache_q = step(cache_q, toks[:, t:t + 1], jnp.int32(t))
        # logits drift slightly; top-1 agreement is the serving criterion
        ok += int((jnp.argmax(le, -1) == jnp.argmax(lq, -1)).all())
    assert ok >= 10, f"top-1 agreement only {ok}/12"


def test_server_compact_kv_roundtrip():
    """Server.compact_kv turns exact prefix/tail-layer caches into
    clustered ones that decode_step accepts and produces sane logits."""
    from repro.runtime.server import Server, ServerConfig
    # config with NO scan region (tail layers only) so compaction applies
    cfg = dataclasses.replace(
        configs.get_reduced("qwen3-4b"), n_layers=1, dtype="float32")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(5)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, size=(1, 96)), jnp.int32)
    logits_p, cache = tfm.prefill(params, cfg, toks, max_seq=128)

    srv = Server(cfg, ServerConfig(max_seq=128), params)
    ccfg = kv_compress.KVCompressConfig(n_clusters=12, iters=6,
                                        keep_recent=16)
    cache_c = srv.compact_kv(cache, t=96, ccfg=ccfg)
    # compacted leaves exist and shrank (single layer lives in the scan
    # region → stacked (layers, B, C, H, Dh))
    sc = cache_c["scan"]["sub0"]
    assert "k_cents" in sc and sc["k_cents"].shape[2] == 12
    assert sc["k_tail"].shape[2] == 16

    le, _ = tfm.decode_step(params, cfg, cache, toks[:, -1:], jnp.int32(96))
    lc, _ = tfm.decode_step(params, cfg, cache_c, toks[:, -1:],
                            jnp.int32(96))
    assert bool(jnp.isfinite(lc).all())
    # approximation keeps the distribution close (cosine of logits)
    cos = float(jnp.sum(le * lc)
                / (jnp.linalg.norm(le) * jnp.linalg.norm(lc)))
    assert cos > 0.98, cos
