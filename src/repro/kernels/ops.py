"""Jit'd public wrappers for the Pallas kernels.

On the CPU backend the kernels execute in ``interpret=True`` mode so the
kernel bodies are validated end to end; on a TPU backend they compile via
Mosaic.  Any other backend is an error, never a silent interpret run.
Above the VMEM point-budget the grouped median falls back to the pure-JAX
two-level reduction-tree path (``core.bitserial``) — mirroring the paper,
where datasets beyond one storage array go through the hierarchical merge
network.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import bitserial
from repro.kernels import bitserial_median as _bsm
from repro.kernels import clustered_decode as _cd
from repro.kernels import distance_argmin as _da
from repro.kernels import paged_clustered_decode as _pcd

# points that fit the VMEM-resident kernel comfortably (u + active + forced
# + temporaries at TD=128 lanes ≈ 4 f32 planes ⇒ ~8 MB at 4096 points)
MAX_KERNEL_POINTS = 4096


def interpret_default() -> bool:
    """True on the CPU backend (kernels run interpreted), False on TPU
    (kernels compile through Mosaic); any other backend raises.  Single
    source of truth for backend detection — every kernel wrapper (here
    and in the kernel modules) resolves ``interpret=None`` through this
    helper, so the choice can't drift between call sites."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"no Pallas path for backend {backend!r}: the kernels compile "
        "through Mosaic on 'tpu' and run interpreted on 'cpu' only")


@partial(jax.jit, static_argnames=("k", "bits", "d_block", "interpret",
                                   "force_kernel"))
def grouped_median_bits(u, assign, k: int, weights=None, *, bits: int = 32,
                        d_block: int = 128, interpret: bool | None = None,
                        force_kernel: bool = False):
    """Per-cluster bit-serial medians of unsigned-ordered uint32 data.

    u (N, D), assign (N,) → (med (k, D) uint32, totals (k,) f32).
    """
    n = u.shape[0]
    if weights is None:
        weights = jnp.ones((n,), jnp.float32)
    if interpret is None:
        interpret = interpret_default()
    if n <= MAX_KERNEL_POINTS or force_kernel:
        med = _bsm.grouped_median_pallas(u, assign, weights, k, bits=bits,
                                         d_block=d_block, interpret=interpret)
        onehot = jax.nn.one_hot(assign, k, dtype=jnp.float32)
        totals = (onehot * weights[:, None]).sum(axis=0)
        return med, totals
    return bitserial.grouped_median_bits(u, assign, k, weights=weights,
                                         bits=bits)


@partial(jax.jit, static_argnames=("metric", "n_block", "interpret"))
def distance_argmin(x, cents, *, metric: str = "l2", n_block: int = 1024,
                    interpret: bool | None = None):
    """Closest-centroid assignment: (assign (N,), mindist (N,))."""
    if interpret is None:
        interpret = interpret_default()
    nb = min(n_block, max(8, x.shape[0]))
    return _da.distance_argmin_pallas(x, cents, metric=metric, n_block=nb,
                                      interpret=interpret)


@partial(jax.jit, static_argnames=("scale", "softcap", "interpret"))
def _clustered_decode_jit(q, k_cents, v_cents, counts, k_tail, v_tail, t,
                          cov, chunk_len, *, scale: float,
                          softcap: float | None, interpret: bool):
    return _cd.clustered_decode_pallas(
        q, k_cents, v_cents, counts, k_tail, v_tail, t, cov, chunk_len,
        scale=scale, softcap=softcap, interpret=interpret)


def _kernel_shard_axes(rules, b: int, hq: int, hkv: int):
    """(data_axes, model_axes) for a (B, Hq/Hkv, …) kernel launch under the
    active sharding rules, or (None, None) when nothing divides.  Heads
    shard over the model axis only when BOTH the query and kv head counts
    divide (the GQA group must stay intact per shard)."""
    data_axes = rules.axes_for("batch", b)
    model_axes = rules.axes_for("heads", hq)
    if model_axes is not None and rules.axes_for("kv_heads", hkv) != model_axes:
        model_axes = None
    return data_axes, model_axes


def clustered_decode(q, k_cents, v_cents, counts, k_tail, v_tail, t, cov,
                     chunk_len=None, *, scale: float,
                     softcap: float | None = None,
                     interpret: bool | None = None):
    """Fused clustered-KV decode attention (centroids ⊕ tail ring).

    q (B, Hq, Dh) for plain decode, or (B, L, Hq, Dh) for the mixed-mode
    launch (chunked prefill interleaved with decode) with per-slot
    ``chunk_len`` (B,) valid query rows; k/v_cents (B, C, Hkv, Dh);
    counts (B, C, Hkv); k/v_tail (B, R, Hkv, Dh); t, cov scalar or (B,)
    → output shaped like q.

    When a sharding-rules context is active (mesh serving), the Pallas
    kernel is dispatched per (data, model) mesh shard via shard_map —
    slots partition over ``data``, kv-head grid cells over ``model`` —
    with divisibility-aware fallback to replication.  Dispatch happens at
    trace time, so this wrapper is deliberately un-jitted (a cached trace
    must never leak across rules contexts); the plain path keeps its own
    jit below."""
    if interpret is None:
        interpret = interpret_default()
    b = q.shape[0]
    if chunk_len is None:
        chunk_len = jnp.ones((b,), jnp.int32)
    chunk_len = jnp.broadcast_to(jnp.asarray(chunk_len, jnp.int32), (b,))
    hq = q.shape[-2]
    from repro.sharding import current_rules
    r = current_rules()
    if r is not None:
        data_axes, model_axes = _kernel_shard_axes(
            r, b, hq, k_cents.shape[2])
        if data_axes is not None or model_axes is not None:
            return _cd.clustered_decode_shardmap(
                q, k_cents, v_cents, counts, k_tail, v_tail, t, cov,
                chunk_len, mesh=r.mesh, data_axes=data_axes,
                model_axes=model_axes, scale=scale, softcap=softcap,
                interpret=interpret)
    return _clustered_decode_jit(
        q, k_cents, v_cents, counts, k_tail, v_tail, t, cov, chunk_len,
        scale=scale, softcap=softcap, interpret=interpret)


@partial(jax.jit, static_argnames=("scale", "softcap", "interpret"))
def _paged_clustered_decode_jit(q, k_cents, v_cents, counts, k_pool, v_pool,
                                row_slot, row_bt, qpos1, tw, cov, wlo, *,
                                scale: float, softcap: float | None,
                                interpret: bool):
    return _pcd.paged_clustered_decode_pallas(
        q, k_cents, v_cents, counts, k_pool, v_pool, row_slot, row_bt,
        qpos1, tw, cov, wlo, scale=scale, softcap=softcap,
        interpret=interpret)


def paged_clustered_decode(q, k_cents, v_cents, counts, k_pool, v_pool,
                           row_slot, row_bt, qpos1, tw, cov, row_wlo=None,
                           *, scale: float,
                           softcap: float | None = None,
                           interpret: bool | None = None):
    """Paged clustered-KV decode over packed ragged rows.

    The paged-vs-dense choice is made at trace time by the caller
    (models/attention dispatches here when the cache carries a block
    pool, and to ``clustered_decode`` above for the dense per-slot ring)
    — this wrapper then picks shard_map vs plain launch exactly like the
    dense one.  q (N, Hq, Dh) packed (slot, position) rows; k/v_pool
    (nb, bs, Hkv, Dh) tail block pools; row_bt (N, T) physical block per
    ring block (all entries valid — unmapped blocks pre-sanitized to a
    masked garbage block); qpos1/tw/cov per-row position + 1 / ring
    watermark / coverage frontier; ``row_wlo`` (N,) per-row retention
    window lower bound (None ⇒ zeros: frontier-only masking, the
    bit-identical pre-policy behavior).

    Under mesh serving rows, slots, and the pool shard over ``data``
    (block ids are global and rebased per shard inside the island), heads
    over ``model``.  Divisibility of the rows, slots, AND pool blocks is
    required for data sharding — the engine packs rows per shard, so a
    fallback to replication only triggers for indivisible slot counts,
    matching the dense path."""
    if interpret is None:
        interpret = interpret_default()
    if row_wlo is None:
        row_wlo = jnp.zeros_like(jnp.asarray(qpos1, jnp.int32))
    hq = q.shape[-2]
    from repro.sharding import current_rules
    r = current_rules()
    if r is not None:
        data_axes, model_axes = _kernel_shard_axes(
            r, k_cents.shape[0], hq, k_cents.shape[2])
        if data_axes is not None:
            # rows and pool must split the same way as slots
            total = 1
            for a in data_axes:
                total *= r.mesh.shape[a]
            if q.shape[0] % total or k_pool.shape[0] % total:
                data_axes = None
        if data_axes is not None or model_axes is not None:
            return _pcd.paged_clustered_decode_shardmap(
                q, k_cents, v_cents, counts, k_pool, v_pool, row_slot,
                row_bt, qpos1, tw, cov, row_wlo, mesh=r.mesh,
                data_axes=data_axes, model_axes=model_axes, scale=scale,
                softcap=softcap, interpret=interpret)
    return _paged_clustered_decode_jit(
        q, k_cents, v_cents, counts, k_pool, v_pool, row_slot, row_bt,
        qpos1, tw, cov, row_wlo, scale=scale, softcap=softcap,
        interpret=interpret)
