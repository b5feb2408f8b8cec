"""What every architecture's plain reference shares: the memory
manager's state of one served request, the attention over it, and the
correctness comparison's inputs and gaps.  The forward of each
architecture is in ``bench/arch/<model_type>.py`` and builds on these.

It imports nothing of the program.  Given a request's prompt and the
tokens the engine served, the reference recomputes every position's
logits in float32 at ``highest`` matmul precision.  For every layer that
holds clustered memory state, that state is rebuilt from the request's
own schedule: the prompt streams in ``prefill_chunk`` pieces; before a
piece whose positions would overrun the exact ring of ``kv_keep_recent``
positions, the aged ring entries are absorbed into the centroids (dead
centroid rows first re-seeded by farthest-point selection); after the
prompt, coverage catches up to ``t - ring + refresh``; every
``kv_refresh_every`` decode tokens a compaction folds the entries that
aged past the new frontier.  Each fold is a weighted k-medians over [old
centroids weighted by their counts ⊕ the ring entries being folded]:
squared-L2 assignment, per-dimension weighted lower median on a
``kmedians_bits`` fixed-point grid (power-of-two scale per dimension), at
most ``kmedians_iters`` Lloyd rounds, values averaged per cluster.  Each
position attends over [centroids of the state it saw, with a
+log(count) bias, ⊕ the exact keys from the coverage frontier up to
itself].

``fake_quant`` with ``weight_quant`` ("int8" or "fp8", or "bf16" for a
float32 configuration) rounds a weight matrix per output channel: that
is the control, one precision step below the configured one.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
NEG = -1e30
TOL = 1e-4          # Lloyd stops once no centroid coordinate moves more


@dataclasses.dataclass(frozen=True)
class Memory:
    chunk: int
    ring: int
    refresh: int
    clusters: int
    iters: int
    bits: int


def memory(conf: dict) -> Memory:
    """The memory manager's settings of a configuration file."""
    s = conf["serving"]
    return Memory(chunk=s["prefill_chunk"], ring=s["kv_keep_recent"],
                  refresh=min(s["kv_refresh_every"], s["kv_keep_recent"]),
                  clusters=s["kv_clusters"], iters=s["kmedians_iters"],
                  bits=s["kmedians_bits"])


# ---------------------------------------------------------------------------
# schedule of one request (host)
# ---------------------------------------------------------------------------

ABSORB, COMPACT = 1, 2


def schedule(prompt_len: int, n_out: int, mem: Memory):
    """Memory events of one request and, for every fed position, how many
    events came before its query.

    Returns (events (E, 3) int [kind, ring length, new frontier],
    state_of (prompt_len + n_out - 1,) int)."""
    P, R, rf = prompt_len, mem.ring, mem.refresh
    events = []
    state_of = np.zeros(P + n_out - 1, np.int64)
    cov = fed = 0
    while fed < P:
        cl = min(mem.chunk, P - fed)
        if fed + cl - cov > R:
            new = min(max(cov, fed + cl - R + rf), fed)
            events.append((ABSORB, fed, new))
            cov = new
        state_of[fed:fed + cl] = len(events)
        fed += cl
    end = max(0, min(P, P - R + rf))
    if cov < end:
        events.append((ABSORB, P, end))
        cov = end
    since = 0
    for i in range(1, n_out):
        state_of[P + i - 1] = len(events)
        since += 1
        if since >= rf and i + 1 < n_out:
            pos = P + i
            new = max(cov, min(max(pos - R + rf, 0), pos))
            events.append((COMPACT, pos, new))
            cov = new
            since = 0
    return np.asarray(events, np.int64).reshape(-1, 3), state_of


def max_events(max_prompt: int, max_out: int, mem: Memory) -> int:
    return -(-max_prompt // mem.chunk) + 1 + max_out // mem.refresh + 1


# ---------------------------------------------------------------------------
# weighted k-medians on a fixed-point grid
# ---------------------------------------------------------------------------


def _sqdist(x, c):
    """x (N, D), c (K, D) → (N, K) squared L2."""
    return jnp.sum((x[:, None, :] - c[None, :, :]) ** 2, axis=-1)


def seed_dead(x, cents, live, w):
    """Replace dead rows, in row order, by the positively weighted point
    farthest from every centroid placed so far (first such point on ties;
    a flat field when no row is live)."""
    mind = jnp.min(jnp.where(live[None, :], _sqdist(x, cents), jnp.inf), 1)
    mind = jnp.where(jnp.isfinite(mind), mind, 1.0)

    def body(i, carry):
        cents, mind = carry
        pick = x[jnp.argmax(jnp.where(w > 0, mind, -1.0))]
        ci = jnp.where(live[i], cents[i], pick)
        cents = cents.at[i].set(ci)
        return cents, jnp.minimum(mind, _sqdist(x, ci[None])[:, 0])

    return jax.lax.fori_loop(0, cents.shape[0], body, (cents, mind))[0]


def kmedians(x, vals, w, init, *, iters: int, bits: int):
    """Weighted k-medians of points x (N, D) with weights w (N,) from
    ``init`` (K, D).  Returns (key centroids, value means, counts)."""
    k = init.shape[0]
    absmax = jnp.maximum(jnp.max(jnp.abs(jnp.where(w[:, None] > 0, x, 0.0)),
                                 axis=0), 1e-30)
    scale = jnp.exp2(jnp.minimum(jnp.floor((bits - 3) - jnp.log2(absmax)),
                                 126.0))
    lim = 2.0 ** (bits - 1)
    qx = jnp.clip(jnp.round(x * scale), -lim, lim - 1)
    order = jnp.argsort(qx, axis=0)                        # (N, D)
    qs = jnp.take_along_axis(qx, order, axis=0)

    def members(a):
        return (a[:, None] == jnp.arange(k)[None, :]) * w[:, None]  # (N, K)

    def medians(a, prev):
        m = members(a)
        total = m.sum(0)                                   # (K,)
        cum = jnp.cumsum(jnp.take(m, order, axis=0), axis=0)   # (N, D, K)
        first = jnp.argmax(cum * 2.0 >= total, axis=0)     # (D, K)
        med = qs[first, jnp.arange(qs.shape[1])[:, None]].T / scale  # (K, D)
        return jnp.where(total[:, None] > 0, med, prev)

    def cond(s):
        return (s[2] < iters) & (s[3] > TOL)

    def body(s):
        cents = s[0]
        a = jnp.argmin(_sqdist(x, cents), axis=1)
        new = medians(a, cents)
        return new, a, s[2] + 1, jnp.max(jnp.abs(new - cents))

    cents, a, _, _ = jax.lax.while_loop(
        cond, body, (init, jnp.zeros(x.shape[0], jnp.int32), jnp.int32(0),
                     jnp.float32(jnp.inf)))
    m = members(a)
    counts = m.sum(0)
    vmean = jnp.einsum("nk,nd->kd", m, vals, precision=HI)
    return cents, vmean / jnp.maximum(counts, 1.0)[:, None], counts


def ring_positions(ring: int, length):
    s = jnp.arange(ring)
    wrapped = length - ring + jnp.mod(s - length, ring)
    return jnp.where(length <= ring, s, wrapped)


def cluster_states(k, v, events, mem: Memory):
    """Centroid states of one layer: k/v (L, H, D) for every position,
    events (E, 3).  Returns (kc (E+1, C, H, D), vc, counts (E+1, C, H),
    cov (E+1,)); state e is the one seen after e events."""
    L, H, D = k.shape
    C, R = mem.clusters, mem.ring
    zero = (jnp.zeros((C, H, D), jnp.float32), jnp.zeros((C, H, D),
            jnp.float32), jnp.zeros((C, H), jnp.float32), jnp.int32(0))

    def fold(carry, ev):
        kc, vc, cnt, cov = carry
        kind, length, new = ev[0], ev[1], ev[2]
        rp = ring_positions(R, length)
        kr = k[jnp.clip(rp, 0, L - 1)]
        vr = v[jnp.clip(rp, 0, L - 1)]
        wt = ((rp >= cov) & (rp < new)).astype(jnp.float32)
        absorb = kind == ABSORB

        def head(kch, vch, cnth, krh, vrh):
            x = jnp.concatenate([kch, krh], 0)
            vals = jnp.concatenate([vch, vrh], 0)
            w = jnp.concatenate([cnth, wt], 0)
            init = jax.lax.cond(
                absorb, lambda: seed_dead(x, kch, cnth > 0, w), lambda: kch)
            return kmedians(x, vals, w, init, iters=mem.iters,
                            bits=mem.bits)

        nk, nv, nc = jax.vmap(head, in_axes=(1, 1, 1, 1, 1),
                              out_axes=(1, 1, 1))(kc, vc, cnt, kr, vr)
        changed = (kind > 0) & (new > cov)
        out = (jnp.where(changed, nk, kc), jnp.where(changed, nv, vc),
               jnp.where(changed, nc, cnt), jnp.where(changed, new, cov))
        return out, out

    _, states = jax.lax.scan(fold, zero, events)
    return tuple(jnp.concatenate([z[None], s], 0)
                 for z, s in zip(zero, states))


# ---------------------------------------------------------------------------
# shared layers
# ---------------------------------------------------------------------------


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def fake_quant(w, kind, axis):
    """Round ``w`` to bf16, or to int8 or fp8 (e4m3) with one scale per
    slice along ``axis`` reduced (per output channel), back in float32.
    The float roundings go through ``reduce_precision``: a round trip
    through a narrower dtype may be folded away by the compiler (XLA does
    so for float8 on the TPU)."""
    w = w.astype(jnp.float32)
    if kind is None:
        return w
    if kind == "bf16":
        return jax.lax.reduce_precision(w, exponent_bits=8, mantissa_bits=7)
    amax = jnp.maximum(jnp.max(jnp.abs(w), axis=axis, keepdims=True), 1e-30)
    if kind == "int8":
        s = amax / 127.0
        return jnp.clip(jnp.round(w / s), -127, 127) * s
    if kind == "fp8":
        # 4 exponent and 3 mantissa bits, largest normal 240 in this form
        s = amax / 240.0
        return jax.lax.reduce_precision(w / s, exponent_bits=4,
                                        mantissa_bits=3) * s
    raise ValueError(f"unknown weight_quant {kind!r}")


def attend(q, k, v, states, state_of, pos):
    """q (L, Hq, D), k/v (L, Hkv, D): each position over the centroids of
    the state it saw plus exact keys in [its frontier, itself], scaled by
    1/sqrt(D).  ``states`` from ``cluster_states``."""
    kc, vc, cnt, cov = (s[state_of] for s in states)    # per position
    L, Hq, D = q.shape
    Hkv = k.shape[1]
    qg = q.reshape(L, Hkv, Hq // Hkv, D)
    sc = 1.0 / math.sqrt(D)
    s_c = jnp.einsum("lhgd,lchd->lhgc", qg, kc, precision=HI) * sc
    c = cnt.transpose(0, 2, 1)[:, :, None, :]             # (L, H, 1, C)
    s_c = jnp.where(c > 0, s_c + jnp.log(jnp.maximum(c, 1e-9)), NEG)
    s_t = jnp.einsum("lhgd,mhd->lhgm", qg, k, precision=HI) * sc
    ok = (pos[None, :] <= pos[:, None]) & (pos[None, :] >= cov[:, None])
    s_t = jnp.where(ok[:, None, None, :], s_t, NEG)
    s = jnp.concatenate([s_c, s_t], -1)
    p = jax.nn.softmax(s, axis=-1)
    C = kc.shape[1]
    out = (jnp.einsum("lhgc,lchd->lhgd", p[..., :C], vc, precision=HI)
           + jnp.einsum("lhgm,mhd->lhgd", p[..., C:], v, precision=HI))
    return out.reshape(L, Hq * D)


def request_inputs(prompt, served, mem: Memory, *, max_len: int,
                   max_out: int, n_events: int):
    """Padded arrays for an architecture's ``logits_at`` of one request:
    its prompt followed by every served token but the last fed back."""
    P, T = len(prompt), len(served)
    events, state_of = schedule(P, T, mem)
    if len(events) > n_events or P + T - 1 > max_len or T > max_out:
        raise ValueError(f"request ({P}, {T}) exceeds the reference's "
                         f"padded sizes")
    tokens = np.zeros(max_len, np.int32)
    tokens[:P] = prompt
    tokens[P:P + T - 1] = served[:-1]
    ev = np.zeros((n_events, 3), np.int32)
    ev[:len(events)] = events
    so = np.zeros(max_len, np.int32)
    so[:P + T - 1] = state_of
    out_pos = np.full(max_out, P + T - 2, np.int32)
    out_pos[:T] = np.arange(P - 1, P + T - 1)
    return tokens, ev, so, out_pos


def served_gaps(ref_logits, served):
    """By how much each served token's reference logit lies below the
    reference's best at that position."""
    lg = np.asarray(ref_logits, np.float32)[:len(served)]
    return lg.max(-1) - lg[np.arange(len(served)), np.asarray(served)]
