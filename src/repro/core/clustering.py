"""K-means / k-medians ("aggregations") clustering engine.

Implements the paper's Algorithm 1 loop (assign → recompute centroids until
convergence) with:

  * centroid = arithmetic mean (k-means) or bit-serial median (k-medians /
    the paper's "aggregations" variant, robust to outliers),
  * L2 or L1 assignment metric,
  * random or k-means++ initialization,
  * full-batch Lloyd, mini-batch, and a shard_map-distributed driver whose
    median update communicates only per-bit (K, D) vote counts — the paper's
    hierarchical reduction tree mapped onto the mesh data axis,
  * the paper's §4 optimal-k search (avgBMP loop) via simplified silhouette,
  * recognition-rate evaluation (paper Table 3 protocol: clusters take their
    majority label; accuracy of that labeling).

Everything is jit-compatible; the Pallas assignment kernel is wired in via
``repro.kernels.ops`` (pure-jnp fallback used automatically on CPU).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import bitserial, quantizer
from repro.runtime.telemetry import scope


@dataclasses.dataclass(frozen=True)
class ClusterConfig:
    k: int
    metric: str = "l1"            # "l1" | "l2"
    centroid: str = "median"      # "median" (paper) | "mean" (k-means)
    max_iters: int = 50
    tol: float = 1e-4
    init: str = "kmeanspp"        # "kmeanspp" | "random"
    bits: int = 32                # fixed-point width for the bit-serial scan
    seed: int = 0
    assign_chunk: int = 4096      # N-chunking for the assignment step


# ---------------------------------------------------------------------------
# Distances / assignment
# ---------------------------------------------------------------------------


def pairwise_dist(x, cents, metric: str):
    """x (n, D), cents (K, D) → (n, K) distances (L2 is squared L2)."""
    if metric == "l2":
        x2 = jnp.sum(x * x, axis=-1, keepdims=True)          # (n, 1)
        c2 = jnp.sum(cents * cents, axis=-1)[None, :]         # (1, K)
        xc = x @ cents.T                                      # MXU
        return jnp.maximum(x2 - 2.0 * xc + c2, 0.0)
    if metric == "l1":
        return jnp.sum(jnp.abs(x[:, None, :] - cents[None, :, :]), axis=-1)
    raise ValueError(f"unknown metric {metric}")


def assign_points(x, cents, metric: str, chunk: int = 4096, use_kernel: bool = True):
    """Chunked assignment: returns (assign (N,), mindist (N,))."""
    if use_kernel:
        # late import to avoid a hard dependency cycle
        from repro.kernels import ops as kops

        return kops.distance_argmin(x, cents, metric=metric)
    return _assign_points_jnp(x, cents, metric, chunk)


def _assign_points_jnp(x, cents, metric: str, chunk: int = 4096):
    n, d = x.shape
    if n <= chunk:
        dist = pairwise_dist(x, cents, metric)
        return jnp.argmin(dist, axis=-1).astype(jnp.int32), jnp.min(dist, axis=-1)

    pad = (-n) % chunk
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    xc = xp.reshape(-1, chunk, d)

    def one(xb):
        dist = pairwise_dist(xb, cents, metric)
        return jnp.argmin(dist, axis=-1).astype(jnp.int32), jnp.min(dist, axis=-1)

    a, m = jax.lax.map(one, xc)
    return a.reshape(-1)[:n], m.reshape(-1)[:n]


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_random(rng, x, k: int):
    idx = jax.random.choice(rng, x.shape[0], (k,), replace=False)
    return x[idx]


def init_kmeanspp(rng, x, k: int, metric: str = "l2", weights=None):
    """k-means++ (D^2 sampling; D^1 for L1/k-medians).  Optional point
    ``weights`` (N,) scale the sampling probabilities — zero-weight points
    (padding / masked slots) are never chosen as seeds."""
    n, d = x.shape
    r0, rloop = jax.random.split(rng)
    if weights is None:
        first = x[jax.random.randint(r0, (), 0, n)]
    else:
        wsum = weights.sum()
        probs0 = jnp.where(wsum > 0, weights / jnp.maximum(wsum, 1e-30),
                           jnp.full((n,), 1.0 / n))
        first = x[jax.random.choice(r0, n, p=probs0)]
    cents = jnp.zeros((k, d), x.dtype).at[0].set(first)
    mind = pairwise_dist(x, first[None, :], metric)[:, 0]

    def body(i, carry):
        cents, mind, key = carry
        key, sub = jax.random.split(key)
        w = mind if metric == "l2" else jnp.maximum(mind, 0.0)
        if weights is not None:
            w = w * weights
        wsum = w.sum()
        probs = jnp.where(wsum > 0, w / jnp.maximum(wsum, 1e-30),
                          jnp.full((n,), 1.0 / n))
        idx = jax.random.choice(sub, n, p=probs)
        c = x[idx]
        cents = cents.at[i].set(c)
        dnew = pairwise_dist(x, c[None, :], metric)[:, 0]
        return cents, jnp.minimum(mind, dnew), key

    cents, _, _ = jax.lax.fori_loop(1, k, body, (cents, mind, rloop))
    return cents


# ---------------------------------------------------------------------------
# Centroid updates
# ---------------------------------------------------------------------------


def seed_empty_centroids(x, cents, live, metric: str, weights=None):
    """Deterministically re-seed dead centroid rows by greedy farthest-point
    (maximin) selection over the weighted point set.

    ``cents`` (K, D) is a warm-start bank; rows with ``live`` False (e.g.
    count == 0) are replaced one at a time by the point farthest from every
    centroid placed so far (k-means++ with argmax instead of sampling, so
    the result is reproducible without threading RNG through the serving
    engine).  Live rows keep their values and shape the distance field.
    Zero-weight points (padding / masked ring slots) are never chosen.

    Needed by streaming admission (kv_compress.absorb_chunk): the first
    chunk of a request arrives with an all-zero centroid bank, and warm-
    starting Lloyd from K identical zero rows collapses every point into
    one cluster.  jit-compatible (fori_loop over K rows).
    """
    n, _ = x.shape
    k = cents.shape[0]
    w = (jnp.ones((n,), jnp.float32) if weights is None
         else weights.astype(jnp.float32))

    def body(i, carry):
        cents, mind = carry
        score = jnp.where(w > 0, mind, -1.0)
        c_new = x[jnp.argmax(score)]
        c_i = jnp.where(live[i], cents[i], c_new)
        cents = cents.at[i].set(c_i)
        d_new = pairwise_dist(x, c_i[None, :], metric)[:, 0]
        return cents, jnp.minimum(mind, d_new)

    with scope("kmedians_reseed"):
        dist0 = pairwise_dist(x, cents, metric)           # (n, K)
        mind = jnp.min(jnp.where(live[None, :], dist0, jnp.inf), axis=1)
        # no live row yet → flat field: the first dead row takes the first
        # positively-weighted point, the rest spread by maximin from there
        mind = jnp.where(jnp.isfinite(mind), mind, 1.0)
        cents, _ = jax.lax.fori_loop(0, k, body, (cents, mind))
    return cents


def update_mean(x, assign, k: int, prev, *, weights=None,
                axis_name: Optional[str] = None):
    """Weighted mean centroids; mirrors ``update_median``'s signature so the
    Lloyd driver treats both centroid kinds uniformly.  Under shard_map the
    per-cluster sums/counts psum over ``axis_name`` — the same reduction
    tree the bit-serial median votes use, so mean and median fits are
    psum-consistent with each other."""
    onehot = jax.nn.one_hot(assign, k, dtype=jnp.float32)
    if weights is not None:
        onehot = onehot * weights.astype(jnp.float32)[:, None]
    sums = onehot.T @ x
    counts = onehot.sum(axis=0)
    if axis_name is not None:
        sums = jax.lax.psum(sums, axis_name)
        counts = jax.lax.psum(counts, axis_name)
    mean = sums / jnp.maximum(counts, 1.0)[:, None]
    return jnp.where(counts[:, None] > 0, mean, prev), counts


def update_median(x, assign, k: int, prev, *, bits: int = 32, scale=None,
                  weights=None, axis_name: Optional[str] = None):
    med, counts = bitserial.grouped_median(
        x, assign, k, bits=bits, scale=scale, weights=weights,
        axis_name=axis_name
    )
    return jnp.where(counts[:, None] > 0, med, prev), counts


# ---------------------------------------------------------------------------
# Lloyd driver
# ---------------------------------------------------------------------------


class ClusterResult(NamedTuple):
    centroids: jnp.ndarray
    assign: jnp.ndarray
    inertia: jnp.ndarray
    n_iters: jnp.ndarray
    counts: jnp.ndarray


def _one_iter(cfg: ClusterConfig, x, cents, scale, axis_name=None,
              use_kernel=True, weights=None):
    with scope("kmedians_assign"):
        assign, mind = assign_points(x, cents, cfg.metric, cfg.assign_chunk,
                                     use_kernel=use_kernel)
    if cfg.centroid == "mean":
        new, counts = update_mean(x, assign, cfg.k, cents, weights=weights,
                                  axis_name=axis_name)
    else:
        with scope("kmedians_median"):
            new, counts = update_median(x, assign, cfg.k, cents,
                                        bits=cfg.bits, scale=scale,
                                        weights=weights, axis_name=axis_name)
    inertia = mind.sum() if weights is None else (mind * weights).sum()
    if axis_name is not None:
        inertia = jax.lax.psum(inertia, axis_name)
    return new, assign, counts, inertia


def fit(x, cfg: ClusterConfig, init_centroids=None, *, use_kernel: bool = True,
        weights=None, axis_name: Optional[str] = None) -> ClusterResult:
    """Full-batch Lloyd iterations until convergence (jit-compatible).

    Optional ``weights`` (N,) ≥ 0 make this a weighted clustering: padded /
    masked points get weight 0 and never influence centroids, counts, or
    inertia; integer weights > 1 treat a point as a pre-aggregated summary
    of that many originals (streaming re-clustering of cluster summaries).

    Under shard_map, pass ``axis_name`` and per-device shards of x; init
    centroids must then be provided (replicated) by the caller.
    """
    rng = jax.random.PRNGKey(cfg.seed)
    if init_centroids is None:
        if axis_name is not None:
            raise ValueError("distributed fit requires init_centroids")
        init_centroids = (
            init_kmeanspp(rng, x, cfg.k, cfg.metric, weights=weights)
            if cfg.init == "kmeanspp"
            else init_random(rng, x, cfg.k)
        )
    # one shared fixed-point scale for the whole run (paper: single 2^f);
    # zero-weight (masked) points must not widen the scale
    x_scale = x if weights is None else x * (weights > 0)[:, None].astype(x.dtype)
    scale = quantizer.auto_scale(x_scale, cfg.bits)
    if axis_name is not None:
        # global per-feature scale: max over shards
        scale = jax.lax.pmin(scale, axis_name)  # min scale = max |x| wins

    def cond(state):
        cents, _, it, moved, _, _ = state
        return jnp.logical_and(it < cfg.max_iters, moved > cfg.tol)

    def body(state):
        cents, _, it, _, _, _ = state
        new, assign, counts, inertia = _one_iter(
            cfg, x, cents, scale, axis_name=axis_name, use_kernel=use_kernel,
            weights=weights
        )
        moved = jnp.max(jnp.abs(new - cents))
        return new, assign, it + 1, moved, counts, inertia

    # assign is per-shard (device-varying under shard_map): derive the
    # initial value from x so the loop carry types are stable
    assign0 = (x[:, 0] * 0).astype(jnp.int32)
    if axis_name is None:
        state0 = (
            init_centroids,
            assign0,
            jnp.int32(0),
            jnp.float32(jnp.inf),
            jnp.zeros((cfg.k,), jnp.float32),
            jnp.float32(0.0),
        )
        cents, assign, it, _, counts, inertia = jax.lax.while_loop(
            cond, body, state0)
    else:
        # while_loop has no shard_map replication rule: run a fixed-trip
        # fori_loop and freeze the state once converged — same fixpoint as
        # the early-exit loop, and scan-lowered so the per-bit psum carries
        # keep consistent replication types.
        rzero = jax.lax.psum(jnp.zeros((), jnp.float32), axis_name)

        def fori_body(_, state):
            converged = ~cond(state)
            new_state = body(state)
            return jax.tree_util.tree_map(
                lambda old, new: jnp.where(converged, old, new),
                state, new_state)

        state0 = (
            init_centroids,
            assign0,
            rzero.astype(jnp.int32),
            jnp.float32(jnp.inf) + rzero,
            jnp.zeros((cfg.k,), jnp.float32) + rzero,
            rzero,
        )
        cents, assign, it, _, counts, inertia = jax.lax.fori_loop(
            0, cfg.max_iters, fori_body, state0)
    return ClusterResult(cents, assign, inertia, it, counts)


def fit_minibatch(rng, x, cfg: ClusterConfig, batch_size: int, n_steps: int,
                  init_centroids=None) -> ClusterResult:
    """Mini-batch variant: per step sample a batch, assign, and blend the
    batch centroid (mean or bit-serial median) into the running centroid with
    a per-cluster learning rate 1/visit-count (Sculley-style)."""
    if init_centroids is None:
        r0, rng = jax.random.split(rng)
        init_centroids = init_kmeanspp(r0, x, cfg.k, cfg.metric)
    scale = quantizer.auto_scale(x, cfg.bits)

    def step(carry, key):
        cents, visits = carry
        idx = jax.random.randint(key, (batch_size,), 0, x.shape[0])
        xb = x[idx]
        assign, _ = _assign_points_jnp(xb, cents, cfg.metric)
        if cfg.centroid == "mean":
            batch_c, counts = update_mean(xb, assign, cfg.k, cents)
        else:
            batch_c, counts = update_median(xb, assign, cfg.k, cents,
                                            bits=cfg.bits, scale=scale)
        visits = visits + counts
        lr = jnp.where(counts > 0, counts / jnp.maximum(visits, 1.0), 0.0)
        cents = cents + lr[:, None] * (batch_c - cents)
        return (cents, visits), None

    keys = jax.random.split(rng, n_steps)
    (cents, visits), _ = jax.lax.scan(step, (init_centroids,
                                             jnp.zeros((cfg.k,), jnp.float32)),
                                      keys)
    assign, mind = _assign_points_jnp(x, cents, cfg.metric)
    return ClusterResult(cents, assign, mind.sum(), jnp.int32(n_steps), visits)


# ---------------------------------------------------------------------------
# Quality metrics / model selection (paper §4, Table 3)
# ---------------------------------------------------------------------------


def simplified_silhouette(x, cents, assign):
    """Simplified silhouette (centroid-based): (b - a) / max(a, b).  This is
    the 'avgBMP' style per-sample quality score the paper's optimal-k loop
    averages."""
    dist = pairwise_dist(x, cents, "l2")
    k = cents.shape[0]
    a = jnp.take_along_axis(dist, assign[:, None], axis=1)[:, 0]
    masked = dist.at[jnp.arange(x.shape[0]), assign].set(jnp.inf)
    b = jnp.min(masked, axis=1)
    s = (b - a) / jnp.maximum(jnp.maximum(a, b), 1e-30)
    return s.mean()


def select_k(x, kmin: int, kmax: int, cfg: ClusterConfig):
    """Paper §4: sweep k in [kmin, kmax], call k-means, compute avgBMP(k),
    return (k_opt, scores).  Python loop — k changes shapes."""
    scores = []
    for k in range(kmin, kmax + 1):
        c = dataclasses.replace(cfg, k=k)
        res = jax.jit(partial(fit, cfg=c, use_kernel=False))(x)
        scores.append(float(simplified_silhouette(x, res.centroids, res.assign)))
    k_opt = kmin + int(jnp.argmax(jnp.asarray(scores)))
    return k_opt, scores


def recognition_rate(assign, labels, k: int, n_classes: int):
    """Paper Table 3 protocol: each cluster adopts its majority true label;
    report the fraction of points whose cluster-label matches their own."""
    conf = jnp.zeros((k, n_classes), jnp.float32)
    conf = conf.at[assign, labels].add(1.0)
    cluster_label = jnp.argmax(conf, axis=1)
    pred = cluster_label[assign]
    return (pred == labels).mean()
