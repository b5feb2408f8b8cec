"""KV-cache memory management via bit-serial k-medians clustering.

Long-context decode is HBM-bound on the KV cache.  This module compresses
a (S, H, Dh) cache to C centroids per head by clustering the *keys* with
the paper's bit-serial k-medians engine (median centroids resist the
outlier keys that attention sinks produce); values are combined per
cluster with softmax-aware averaging, and attention runs over centroids
with a ``log(count)`` bias so a centroid representing m keys receives the
mass of m keys (clustered-attention estimator).

Memory: S → C per layer-head (e.g. 32768 → 512 is 64×) with the quality
measured in benchmarks/bench_kv_compress.py against exact attention.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import bitserial, clustering
from repro.core.clustering import ClusterConfig
from repro.runtime.telemetry import scope


@dataclasses.dataclass(frozen=True)
class KVCompressConfig:
    n_clusters: int = 256
    iters: int = 6
    metric: str = "l2"        # assignment metric for keys
    bits: int = 16            # fixed-point width for median centroids
    keep_recent: int = 128    # exact tail (recency window kept uncompressed)
    refresh_every: int = 0    # serving: decode steps between compactions
                              # (0 = one-shot compaction, full exact tail);
                              # effectively clamped to keep_recent.  The
                              # centroid coverage frontier advances to
                              # t - keep_recent + refresh_every so every ring
                              # entry is folded into centroids before the
                              # next refresh_every decode steps evict it.
    prompt_clusters: int = 0  # chunked admission: centroid budget while a
                              # prompt streams in (absorb_chunk touches only
                              # the first ``prompt_clusters`` rows; 0 = the
                              # full n_clusters budget).  Keeps prompt-time
                              # Lloyd cheap; the first regular compaction
                              # after admission spreads mass over all rows.

    @property
    def refresh(self) -> int:
        return min(self.refresh_every, self.keep_recent)

    @property
    def prompt_budget(self) -> int:
        return self.prompt_clusters or self.n_clusters


def coverage_frontier(pos: int, cfg: KVCompressConfig) -> int:
    """Loss-free coverage frontier for a stream at absolute length ``pos``.

    Positions below the frontier are absorbed into centroids; the exact
    tail ring keeps ``[frontier, pos)``, which fits in ``keep_recent``
    slots with ``refresh`` steps of headroom before the next compaction
    must run.  Every frontier target the serving engine uses (admission,
    streaming absorb, compaction) is this one formula — the
    ``FrontierRetention`` policy delegates here so the retirement rule
    and the k-medians coverage can never drift apart.
    """
    pos = int(pos)
    return max(0, min(pos, pos - cfg.keep_recent + cfg.refresh))


class CompressedKV(NamedTuple):
    k_cents: jnp.ndarray      # (H, C, Dh) key centroids (bit-serial medians)
    v_cents: jnp.ndarray      # (H, C, Dh) mean value per cluster
    counts: jnp.ndarray       # (H, C)
    k_tail: jnp.ndarray       # (H, R, Dh) exact recent keys
    v_tail: jnp.ndarray       # (H, R, Dh)


def compress_head(keys, values, cfg: KVCompressConfig, seed: int = 0,
                  weights=None, init_centroids=None, axis_name=None):
    """keys/values (S, Dh) → centroids for one head.

    ``weights`` (S,) ≥ 0 mask padded positions (weight 0) or carry counts of
    pre-aggregated summaries; ``init_centroids`` warm-starts Lloyd for
    incremental re-compaction between decode bursts.

    ``axis_name``: when the point rows span a mesh axis under ``shard_map``
    (e.g. the (C ⊕ R) recompaction points of a centroid bank sharded over
    the model axis), the weighted bit-serial k-medians psum-merges per-bit
    vote counts — and the value sums / counts here psum the same way — so
    every shard converges on identical centroids (the paper's reduction
    tree).  Distributed fits require ``init_centroids`` (replicated)."""
    ccfg = ClusterConfig(k=cfg.n_clusters, metric=cfg.metric,
                         centroid="median", max_iters=cfg.iters,
                         bits=cfg.bits, init="kmeanspp", seed=seed)
    res = clustering.fit(keys.astype(jnp.float32), ccfg, init_centroids,
                         use_kernel=False, weights=weights,
                         axis_name=axis_name)
    onehot = jax.nn.one_hot(res.assign, cfg.n_clusters, dtype=jnp.float32)
    if weights is not None:
        onehot = onehot * weights.astype(jnp.float32)[:, None]
    vsum = onehot.T @ values.astype(jnp.float32)
    counts = onehot.sum(0)
    if axis_name is not None:
        vsum = jax.lax.psum(vsum, axis_name)
        counts = jax.lax.psum(counts, axis_name)
    v_cents = vsum / jnp.maximum(counts, 1.0)[:, None]
    return res.centroids, v_cents, counts


def compress_cache(k_cache, v_cache, cfg: KVCompressConfig):
    """k/v (S, H, Dh) → CompressedKV.  The most recent ``keep_recent``
    positions stay exact (recency matters most for LM attention)."""
    s, h, dh = k_cache.shape
    r = min(cfg.keep_recent, s)
    head = s - r
    k_old = k_cache[:head].transpose(1, 0, 2)            # (H, S', Dh)
    v_old = v_cache[:head].transpose(1, 0, 2)

    k_cents, v_cents, counts = jax.vmap(
        lambda kk, vv: compress_head_jit(kk, vv, cfg))(k_old, v_old)
    return CompressedKV(
        k_cents=k_cents, v_cents=v_cents, counts=counts,
        k_tail=k_cache[head:].transpose(1, 0, 2),
        v_tail=v_cache[head:].transpose(1, 0, 2))


@partial(jax.jit, static_argnames=("cfg",))
def compress_head_jit(keys, values, cfg: KVCompressConfig):
    return compress_head(keys, values, cfg)


# ---------------------------------------------------------------------------
# Batched, device-resident compaction (serving path)
#
# Cache-layout leaves: k/v_cents (B, C, H, Dh), counts (B, C, H),
# k/v_tail (B, R, H, Dh) in ring order (position p at slot p % R), and
# cov (B,) int32 — centroids summarize positions [0, cov); the tail is
# exact for [cov, t).  Masking the tail at pos >= cov removes the seed's
# double-count/data-loss ambiguity at the ring-eviction boundary: every
# position is represented exactly once, and a position is only ever
# evicted from the ring after a compaction has folded it into centroids
# (guaranteed by refresh_every <= keep_recent).
# ---------------------------------------------------------------------------


def ring_positions(r: int, t):
    """Absolute position held by each of the r ring slots at time t
    (next write goes to slot t % r).  t scalar or (B,) → (..., r).
    Canonical ring math — models/attention.ring_slot_positions delegates
    here so compaction coverage and the attention mask can't drift."""
    s = jnp.arange(r)
    tb = jnp.asarray(t)[..., None]
    wrapped = tb - r + jnp.mod(s - tb, r)
    return jnp.where(tb <= r, jnp.broadcast_to(s, wrapped.shape), wrapped)


def _tail_ring_slice(kb, vb, lb, r: int):
    """Last r positions of one slot's chronological cache, laid out in ring
    order.  kb/vb (S, H, Dh), lb scalar valid length."""
    start = jnp.maximum(lb - r, 0)
    tk = jax.lax.dynamic_slice_in_dim(kb, start, r, 0)   # chrono (r, H, Dh)
    tv = jax.lax.dynamic_slice_in_dim(vb, start, r, 0)
    slots = jnp.mod(start + jnp.arange(r), r)
    return (jnp.zeros_like(tk).at[slots].set(tk),
            jnp.zeros_like(tv).at[slots].set(tv))


@partial(jax.jit, static_argnames=("cfg",))
def compress_cache_batched(k, v, lengths, cfg: KVCompressConfig):
    """Exact slot caches → clustered layout, one jitted call.

    k/v (B, S, H, Dh) chronological slot buffers, lengths (B,) valid
    counts.  vmap over batch ⊕ head — no Python loops, one trace.  Padded
    positions are excluded via point weights, so ragged slots batch
    cleanly (the MapReduce-style "cluster many independent streams at
    once" regime)."""
    b, s, h, dh = k.shape
    r = min(cfg.keep_recent, s)
    cov = jnp.clip(lengths - r + cfg.refresh, 0, lengths)
    pos = jnp.arange(s)
    w = (pos[None, :] < cov[:, None]).astype(jnp.float32)      # (B, S)

    kT = k.transpose(0, 2, 1, 3).astype(jnp.float32)           # (B, H, S, Dh)
    vT = v.transpose(0, 2, 1, 3).astype(jnp.float32)

    def one_slot(kb, vb, wb):
        return jax.vmap(
            lambda kk, vv: compress_head(kk, vv, cfg, weights=wb))(kb, vb)

    k_cents, v_cents, counts = jax.vmap(one_slot)(kT, vT, w)
    k_tail, v_tail = jax.vmap(
        lambda kb, vb, lb: _tail_ring_slice(kb, vb, lb, r))(k, v, lengths)
    return {
        "k_cents": k_cents.transpose(0, 2, 1, 3).astype(k.dtype),
        "v_cents": v_cents.transpose(0, 2, 1, 3).astype(v.dtype),
        "counts": counts.transpose(0, 2, 1),                   # (B, C, H)
        "k_tail": k_tail.astype(k.dtype),
        "v_tail": v_tail.astype(v.dtype),
        "cov": cov.astype(jnp.int32),
    }


@partial(jax.jit, static_argnames=("cfg", "axis_name"))
def recompact_clustered(cache, lengths, cfg: KVCompressConfig,
                        axis_name=None):
    """Incremental re-compaction of an already-clustered cache.

    The points to recluster are the old centroids (weighted by their
    counts — each is a pre-aggregated summary) plus the ring entries that
    have aged past the new coverage frontier.  Warm-started from the old
    centroids, so between decode bursts Lloyd only has to absorb the ≤
    refresh_every new keys — the streaming-clustering update.

    ``axis_name`` makes the k-medians psum-consistent when the point rows
    are sharded across a mesh axis under shard_map (the warm-started
    centroids satisfy the distributed-init requirement).

    Slots whose frontier does not advance (``new_cov == cov``: drained
    slots, admitting slots passed length 0, slots compacted again before
    new tokens aged past the frontier) keep their centroid bank
    BIT-IDENTICAL — re-running Lloyd over the old centroids with zero new
    mass is not a bitwise no-op (duplicate centroids merge under
    lowest-index tie-breaking), so without the gate a compaction
    triggered by one slot would perturb every other slot's summaries,
    making per-slot state depend on *when* neighbours forced a pass.
    Per-slot determinism is what lets the engine compact slots on their
    own cadence and admit prefix-shared requests on a different schedule
    without changing anyone's tokens."""
    with scope("compact_gather"):
        k_cents = cache["k_cents"].astype(jnp.float32)  # (B, C, H, Dh)
        v_cents = cache["v_cents"].astype(jnp.float32)
        counts = cache["counts"]                        # (B, C, H)
        k_tail = cache["k_tail"].astype(jnp.float32)    # (B, R, H, Dh)
        v_tail = cache["v_tail"].astype(jnp.float32)
        cov = cache["cov"]                              # (B,)
        b, c, h, dh = k_cents.shape
        r = k_tail.shape[1]
        lengths = jnp.asarray(lengths)
        # frontier is monotone even for drained slots (engine passes
        # length 0 for finished slots; their cov must not regress and
        # re-admit tail entries already folded into centroids)
        new_cov = jnp.maximum(cov, jnp.clip(lengths - r + cfg.refresh,
                                            0, lengths))

        ring_pos = ring_positions(r, lengths)           # (B, R)
        w_tail = ((ring_pos >= cov[:, None])
                  & (ring_pos < new_cov[:, None])).astype(jnp.float32)

    def one_head(kc, vc, cnt, kt, vt, wt):
        with scope("compact_gather"):
            x = jnp.concatenate([kc, kt], axis=0)       # (C + R, Dh)
            vals = jnp.concatenate([vc, vt], axis=0)
            wgt = jnp.concatenate([cnt, wt], axis=0)
        return compress_head(x, vals, cfg, weights=wgt, init_centroids=kc,
                             axis_name=axis_name)

    def one_slot(kc, vc, cnt, kt, vt, wt):
        return jax.vmap(lambda *a: one_head(*a, wt))(
            kc.transpose(1, 0, 2), vc.transpose(1, 0, 2), cnt.T,
            kt.transpose(1, 0, 2), vt.transpose(1, 0, 2))

    nk, nv, ncnt = jax.vmap(one_slot)(k_cents, v_cents, counts,
                                      k_tail, v_tail, w_tail)
    with scope("compact_write"):
        changed = (new_cov > cov)[:, None, None]
        return dict(
            cache,
            k_cents=jnp.where(changed[..., None], nk.transpose(0, 2, 1, 3),
                              k_cents).astype(cache["k_cents"].dtype),
            v_cents=jnp.where(changed[..., None], nv.transpose(0, 2, 1, 3),
                              v_cents).astype(cache["v_cents"].dtype),
            counts=jnp.where(changed, ncnt.transpose(0, 2, 1), counts),
            cov=new_cov.astype(jnp.int32),
        )


@partial(jax.jit, static_argnames=("cfg",))
def absorb_chunk(cache, lengths, target_cov, cfg: KVCompressConfig):
    """Streaming admission-time compaction: advance a slot's coverage
    frontier to ``target_cov`` by folding the ring entries aged past it
    into centroids — the one-pass stream-clustering update that lets a
    prompt longer than the tail ring be admitted chunk by chunk without
    ever materializing its exact KV.

    Differences from ``recompact_clustered`` (the between-decode-bursts
    refresh):

      * the frontier target is caller-chosen (the engine asks for exactly
        enough coverage that the next prompt chunk can overwrite ring
        slots safely), not derived from ``refresh_every``;
      * only the first ``cfg.prompt_budget`` centroid rows are written —
        the per-request prompt-time centroid budget.  All rows still
        participate as weighted points, so any mass outside the budget is
        migrated in, never dropped (total counts == new_cov per head);
      * dead centroid rows are deterministically re-seeded by farthest-
        point selection (clustering.seed_empty_centroids) before the
        warm-started weighted k-medians — the first absorbed chunk of a
        request starts from an all-zero bank.

    cache: clustered slot leaves (B, ...); lengths (B,) ring positions
    written so far; target_cov (B,) desired frontier (clipped to
    [cov, lengths]).  Slots with target_cov <= cov keep centroid rows
    bit-identical (their ring contributes zero weight and the warm start
    is only reseeded where counts are zero).
    """
    budget = cfg.prompt_budget
    with scope("compact_gather"):
        k_cents = cache["k_cents"].astype(jnp.float32)  # (B, C, H, Dh)
        v_cents = cache["v_cents"].astype(jnp.float32)
        counts = cache["counts"]                        # (B, C, H)
        k_tail = cache["k_tail"].astype(jnp.float32)    # (B, R, H, Dh)
        v_tail = cache["v_tail"].astype(jnp.float32)
        cov = cache["cov"]                              # (B,)
        b, c, h, dh = k_cents.shape
        r = k_tail.shape[1]
        lengths = jnp.asarray(lengths)
        new_cov = jnp.clip(jnp.maximum(cov, jnp.asarray(target_cov)), 0,
                           lengths)

        ring_pos = ring_positions(r, lengths)           # (B, R)
        w_tail = ((ring_pos >= cov[:, None])
                  & (ring_pos < new_cov[:, None])).astype(jnp.float32)
    bcfg = dataclasses.replace(cfg, n_clusters=budget)

    def one_head(kc, vc, cnt, kt, vt, wt, fresh):
        with scope("compact_gather"):
            x = jnp.concatenate([kc, kt], axis=0)       # (C + R, Dh)
            vals = jnp.concatenate([vc, vt], axis=0)
            wgt = jnp.concatenate([cnt, wt], axis=0)
        init = clustering.seed_empty_centroids(
            x, kc[:budget], cnt[:budget] > 0, cfg.metric,
            weights=wgt * fresh)
        nk, nv, ncnt = compress_head(x, vals, bcfg, weights=wgt,
                                     init_centroids=init)
        with scope("compact_write"):
            return (kc.at[:budget].set(nk), vc.at[:budget].set(nv),
                    jnp.concatenate([ncnt, jnp.zeros((c - budget,),
                                                     ncnt.dtype)]))

    def one_slot(kc, vc, cnt, kt, vt, wt, fresh):
        return jax.vmap(lambda *a: one_head(*a, wt, fresh))(
            kc.transpose(1, 0, 2), vc.transpose(1, 0, 2), cnt.T,
            kt.transpose(1, 0, 2), vt.transpose(1, 0, 2))

    # fresh gates the seeding pool so unchanged slots can't be perturbed
    # even by reseeding a zero-count row onto a live point
    fresh = (new_cov > cov).astype(jnp.float32)
    nk, nv, ncnt = jax.vmap(one_slot)(k_cents, v_cents, counts,
                                      k_tail, v_tail, w_tail, fresh)
    with scope("compact_write"):
        changed = (new_cov > cov)[:, None, None]
        out_counts = jnp.where(changed, ncnt.transpose(0, 2, 1), counts)
        return dict(
            cache,
            k_cents=jnp.where(changed[..., None], nk.transpose(0, 2, 1, 3),
                              cache["k_cents"].astype(jnp.float32)
                              ).astype(cache["k_cents"].dtype),
            v_cents=jnp.where(changed[..., None], nv.transpose(0, 2, 1, 3),
                              cache["v_cents"].astype(jnp.float32)
                              ).astype(cache["v_cents"].dtype),
            counts=out_counts,
            cov=new_cov.astype(jnp.int32),
        )


def clustered_attention(q, ckv: CompressedKV, *, scale: float):
    """q (H, Dh) → out (H, Dh) using centroid attention with count bias.

    softmax over [centroids ⊕ exact tail]; centroid c with m keys gets a
    +log(m) logit bias (it stands for m identical-score keys).
    """
    qf = q.astype(jnp.float32)
    s_c = jnp.einsum("hd,hcd->hc", qf, ckv.k_cents.astype(jnp.float32))
    s_c = s_c * scale + jnp.log(jnp.maximum(ckv.counts, 1e-9))
    s_c = jnp.where(ckv.counts > 0, s_c, -1e30)
    s_t = jnp.einsum("hd,hrd->hr", qf,
                     ckv.k_tail.astype(jnp.float32)) * scale
    s = jnp.concatenate([s_c, s_t], axis=1)
    m = s.max(-1, keepdims=True)
    p = jnp.exp(s - m)
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    v_all = jnp.concatenate([ckv.v_cents.astype(jnp.float32),
                             ckv.v_tail.astype(jnp.float32)], axis=1)
    return jnp.einsum("hc,hcd->hd", p, v_all).astype(q.dtype)


def exact_attention(q, k_cache, v_cache, *, scale: float):
    """Oracle for quality evaluation: q (H, Dh), caches (S, H, Dh)."""
    qf = q.astype(jnp.float32)
    s = jnp.einsum("hd,shd->hs", qf, k_cache.astype(jnp.float32)) * scale
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hs,shd->hd", p,
                      v_cache.astype(jnp.float32)).astype(q.dtype)


def memory_ratio(s: int, cfg: KVCompressConfig) -> float:
    return s / float(cfg.n_clusters + cfg.keep_recent)
