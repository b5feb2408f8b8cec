"""Model assembly: decoder-only / encoder–decoder stacks over the sub-layer
zoo (GQA global/local attention, MLA, MoE, Mamba2 SSD, RG-LRU), with
``lax.scan`` over homogeneous layer groups (compile time stays O(1) in
depth), remat for training, chunked cross-entropy (full logits are never
materialized), KV/state caches for serving, and DeepSeek-style MTP.

Layer layout: ``prefix`` (unrolled, e.g. DeepSeek's 3 dense layers) →
``scan`` (n_rep repeats of the layer_pattern group) → ``tail`` (pattern
remainder, unrolled).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import layer_state
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import rglru as rg_mod
from repro.models import ssm as ssm_mod
from repro.models.config import ModelConfig
from repro.models.layers import (apply_frontend, apply_mlp, apply_norm,
                                 cdtype, dense_init, embed_tokens,
                                 init_embed, init_frontend, init_mlp,
                                 init_norm, lm_logits, residual, rng_for,
                                 sinusoidal_pos)
from repro.runtime.telemetry import scope
from repro.sharding import annotate


# ---------------------------------------------------------------------------
# Layer-count bookkeeping
# ---------------------------------------------------------------------------


def layout(cfg: ModelConfig):
    """(n_prefix, n_rep, tail_kinds) for the decoder stack."""
    n_prefix = cfg.moe.n_dense_layers if cfg.moe else 0
    rest = cfg.n_layers - n_prefix
    plen = len(cfg.layer_pattern)
    n_rep = rest // plen
    tail = [cfg.layer_pattern[i % plen] for i in range(n_rep * plen, rest)]
    return n_prefix, n_rep, tail


# ---------------------------------------------------------------------------
# Single sub-layer (params + apply in all three modes)
# ---------------------------------------------------------------------------


def init_sublayer(rng, cfg: ModelConfig, kind: str, use_moe: bool,
                  d_ff: Optional[int] = None, cross: bool = False):
    p = {"norm1": init_norm(rng, cfg, cfg.d_model)}
    if kind in ("G", "L"):
        if cfg.attn_kind == "mla":
            p["attn"] = attn.init_mla(rng_for(rng, "attn"), cfg)
        else:
            p["attn"] = attn.init_attn(rng_for(rng, "attn"), cfg)
        if cfg.post_norms:
            p["post_attn_norm"] = init_norm(rng, cfg, cfg.d_model)
        if cross:
            p["xnorm"] = init_norm(rng, cfg, cfg.d_model)
            p["xattn"] = attn.init_cross_attn(rng_for(rng, "xattn"), cfg)
        p["norm2"] = init_norm(rng, cfg, cfg.d_model)
        if use_moe:
            p["moe"] = moe_mod.init_moe(rng_for(rng, "moe"), cfg)
        else:
            p["mlp"] = init_mlp(rng_for(rng, "mlp"), cfg,
                                d_ff or cfg.d_ff)
        if cfg.post_norms:
            p["post_mlp_norm"] = init_norm(rng, cfg, cfg.d_model)
    elif kind == "M":
        p["ssm"] = ssm_mod.init_ssm(rng_for(rng, "ssm"), cfg)
        if cfg.recurrent_ffn:
            p["norm2"] = init_norm(rng, cfg, cfg.d_model)
            if use_moe:
                p["moe"] = moe_mod.init_moe(rng_for(rng, "moe"), cfg)
            else:
                p["mlp"] = init_mlp(rng_for(rng, "mlp"), cfg,
                                    d_ff or cfg.d_ff)
    elif kind == "R":
        p["rg"] = rg_mod.init_rglru(rng_for(rng, "rg"), cfg)
        p["norm2"] = init_norm(rng, cfg, cfg.d_model)
        p["mlp"] = init_mlp(rng_for(rng, "mlp"), cfg, d_ff or cfg.d_ff)
    else:
        raise ValueError(kind)
    return p


def _ffn(p, h, cfg: ModelConfig):
    """norm2 → (moe|mlp) → residual (+sandwich norm).  Returns (h,
    metrics): the MoE layer's metrics (``moe.apply_moe``), or none."""
    with scope("mlp"):
        x = apply_norm(p["norm2"], h, cfg)
        if "moe" in p:
            y, metrics = moe_mod.apply_moe(p["moe"], x, cfg)
        else:
            y = apply_mlp(p["mlp"], x, cfg)
            metrics = {}
        if cfg.post_norms:
            y = apply_norm(p["post_mlp_norm"], y, cfg)
        return residual(h, y, cfg), metrics


def _ffn_aux(p, h, cfg: ModelConfig):
    """``_ffn`` where the sublayer has one ('R', and 'M' with
    ``recurrent_ffn``; always for 'G'/'L').  Returns (h, aux_loss)."""
    if "norm2" not in p:
        return h, jnp.float32(0.0)
    h, metrics = _ffn(p, h, cfg)
    return h, metrics.get("aux_loss", jnp.float32(0.0))


def sublayer_train(p, h, cfg: ModelConfig, kind: str, *, positions,
                   kv_repeat: int, causal: bool = True, enc_kv=None):
    """Full-sequence forward. Returns (h, aux_loss)."""
    aux = jnp.float32(0.0)
    if kind in ("G", "L"):
        x = apply_norm(p["norm1"], h, cfg)
        if cfg.attn_kind == "mla":
            y = attn.mla_train(p["attn"], x, cfg, positions=positions)
        else:
            y = attn.attn_train(p["attn"], x, cfg, layer_kind=kind,
                                positions=positions, kv_repeat=kv_repeat,
                                causal=causal)
        if cfg.post_norms:
            y = apply_norm(p["post_attn_norm"], y, cfg)
        h = residual(h, y, cfg)
        if enc_kv is not None:
            x = apply_norm(p["xnorm"], h, cfg)
            h = residual(h, attn.cross_attn_apply(p["xattn"], x, enc_kv,
                                                  cfg), cfg)
        h, aux = _ffn_aux(p, h, cfg)
    elif kind == "M":
        x = apply_norm(p["norm1"], h, cfg)
        h = residual(h, ssm_mod.ssm_train(p["ssm"], x, cfg), cfg)
        h, aux = _ffn_aux(p, h, cfg)
    elif kind == "R":
        x = apply_norm(p["norm1"], h, cfg)
        h = residual(h, rg_mod.rglru_train(p["rg"], x, cfg), cfg)
        h, aux = _ffn_aux(p, h, cfg)
    return h, aux


def init_sublayer_cache(cfg: ModelConfig, kind: str, batch: int,
                        max_seq: int, kv_repeat: int,
                        kv_mode: str = "exact", kv_clusters: int = 512,
                        kv_tail: int = 256, kv_pool_blocks: int = 0,
                        kv_block_size: int = 0):
    if kind in ("G", "L"):
        if cfg.attn_kind == "mla":
            return attn.init_cache_mla(cfg, batch, max_seq)
        if kind == "G" and kv_mode == "clustered":
            return attn.init_cache_attn_clustered(
                cfg, batch, n_clusters=kv_clusters, tail=kv_tail,
                kv_repeat=kv_repeat, pool_blocks=kv_pool_blocks,
                block_size=kv_block_size)
        return attn.init_cache_attn(cfg, kind, batch, max_seq, kv_repeat,
                                    quantized=(kv_mode == "int8"))
    if kind == "M":
        return ssm_mod.init_cache_ssm(cfg, batch)
    if kind == "R":
        return rg_mod.init_cache_rglru(cfg, batch)
    raise ValueError(kind)


def sublayer_prefill(p, h, cfg: ModelConfig, kind: str, *, positions,
                     kv_repeat: int, max_seq: int, enc_kv=None,
                     recurrent_mode: str = "scan"):
    """Returns (h, cache, aux).

    ``recurrent_mode`` selects how recurrent-state layers ('M'/'R')
    compute the prefill: "scan" (default) uses the parallel forms —
    chunked SSD / log-depth associative scan — which are mathematically
    exact but not *bitwise* equal to stepping the one-token decode;
    "sequential" steps the decode recurrence position by position, so a
    prefill is bit-identical to feeding the prompt through the decode
    path one token at a time.  The serving engine uses "sequential":
    its chunked admission advances recurrent state token-by-token inside
    the mixed launch, and blocking admission must match it bitwise.
    """
    aux = jnp.float32(0.0)
    if kind in ("G", "L"):
        x = apply_norm(p["norm1"], h, cfg)
        if cfg.attn_kind == "mla":
            y, cache = attn.mla_prefill(p["attn"], x, cfg,
                                        positions=positions, max_seq=max_seq)
        else:
            y, cache = attn.attn_prefill(p["attn"], x, cfg, layer_kind=kind,
                                         positions=positions,
                                         kv_repeat=kv_repeat)
            # pad non-window caches out to max_seq for decode
            if cache["k"].shape[1] < max_seq and kind == "G":
                padn = max_seq - cache["k"].shape[1]
                cache = {
                    "k": jnp.pad(cache["k"],
                                 ((0, 0), (0, padn), (0, 0), (0, 0))),
                    "v": jnp.pad(cache["v"],
                                 ((0, 0), (0, padn), (0, 0), (0, 0))),
                }
        if cfg.post_norms:
            y = apply_norm(p["post_attn_norm"], y, cfg)
        h = residual(h, y, cfg)
        if enc_kv is not None:
            x = apply_norm(p["xnorm"], h, cfg)
            h = residual(h, attn.cross_attn_apply(p["xattn"], x, enc_kv,
                                                  cfg), cfg)
        h, aux = _ffn_aux(p, h, cfg)
        return h, cache, aux
    if kind == "M":
        # prefill == train pass + terminal state via the sequential tail:
        # run chunked SSD for outputs; rebuild the state with a short
        # decode burn-in is wasteful, so recompute final state directly.
        x = apply_norm(p["norm1"], h, cfg)
        if recurrent_mode == "sequential":
            y, cache = _recurrent_prefill_sequential(
                lambda xt, c: ssm_mod.ssm_decode(p["ssm"], xt, cfg, c),
                x, ssm_mod.init_cache_ssm(cfg, x.shape[0]))
        else:
            y, cache = _ssm_prefill(p["ssm"], x, cfg)
        h, aux = _ffn_aux(p, residual(h, y, cfg), cfg)
        return h, cache, aux
    if kind == "R":
        x = apply_norm(p["norm1"], h, cfg)
        if recurrent_mode == "sequential":
            y, cache = _recurrent_prefill_sequential(
                lambda xt, c: rg_mod.rglru_decode(p["rg"], xt, cfg, c),
                x, rg_mod.init_cache_rglru(cfg, x.shape[0]))
        else:
            y, cache = rg_mod.rglru_prefill(p["rg"], x, cfg)
        h, aux = _ffn_aux(p, residual(h, y, cfg), cfg)
        return h, cache, aux
    raise ValueError(kind)


def _recurrent_prefill_sequential(step_fn, x, cache):
    """Prefill a recurrent layer by stepping its one-token decode.

    x (B, S, d) normed input; ``step_fn(xt (B,1,d), cache) -> (y, cache)``
    is the layer's decode recurrence.  Returns (y (B, S, d), cache) that
    is bit-identical — not just numerically close — to feeding the S
    positions through the decode path one at a time, which is what the
    chunked serving engine's mixed launch does.
    """

    def step(c, xt):
        y, c = step_fn(xt[:, None, :], c)
        return c, y[:, 0]

    cache, ys = jax.lax.scan(step, cache, x.transpose(1, 0, 2))
    return ys.transpose(1, 0, 2), cache


def _ssm_prefill(p, x, cfg: ModelConfig):
    """Chunked SSD forward + final (conv, ssm) state for decode."""
    s_cfg = cfg.ssm
    dt_ = cdtype(cfg)
    d_in, hh, conv_ch = ssm_mod._dims(cfg)
    gn = s_cfg.n_groups * s_cfg.d_state
    z, xbc_raw, dt_raw = ssm_mod._split(p, x, cfg)
    xbc = ssm_mod._conv_train(p, xbc_raw, cfg)
    b, s, _ = x.shape
    xh = xbc[..., :d_in].reshape(b, s, hh, s_cfg.head_dim)
    Bm = xbc[..., d_in:d_in + gn].reshape(b, s, s_cfg.n_groups, s_cfg.d_state)
    Cm = xbc[..., d_in + gn:].reshape(b, s, s_cfg.n_groups, s_cfg.d_state)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    y, final_state = ssm_mod.ssd_chunked(xh, dt, A, Bm, Cm, p["D"],
                                         s_cfg.chunk)
    y = y.reshape(b, s, d_in).astype(dt_)
    gated = y * jax.nn.silu(z)
    var = (gated.astype(jnp.float32) ** 2).mean(-1, keepdims=True)
    gated = (gated.astype(jnp.float32) * jax.lax.rsqrt(var + cfg.norm_eps)
             * p["norm"]).astype(dt_)
    out = gated @ p["out_proj"].astype(dt_)
    conv_tail = (xbc_raw[:, -(s_cfg.d_conv - 1):]
                 if s >= s_cfg.d_conv - 1 else
                 jnp.pad(xbc_raw, ((0, 0), (s_cfg.d_conv - 1 - s, 0), (0, 0))))
    cache = {"conv": conv_tail.astype(dt_), "ssm": final_state}
    return out, cache


def sublayer_decode(p, h, cfg: ModelConfig, kind: str, cache, t, *,
                    kv_repeat: int, enc_kv=None, chunk_len=None):
    """h (B,1,d) — or (B,L,d) mixed-mode with per-slot ``chunk_len``
    (chunked prefill interleaved with decode).  Ring-family layers
    stream the chunk into their KV at exact positions; recurrent-state
    layers ('M'/'R') advance their fixed-size state column by column
    with per-slot masking (:func:`_recurrent_mixed_advance`).
    Returns (h, cache')."""
    if kind in ("G", "L"):
        x = apply_norm(p["norm1"], h, cfg)
        if cfg.attn_kind == "mla":
            if chunk_len is not None:
                raise NotImplementedError(
                    "mixed-mode chunked decode is not wired for MLA "
                    "latent caches yet")
            y, cache = attn.mla_decode(p["attn"], x, cfg, cache=cache, t=t)
        else:
            y, cache = attn.attn_decode(p["attn"], x, cfg, layer_kind=kind,
                                        cache=cache, t=t,
                                        kv_repeat=kv_repeat,
                                        chunk_len=chunk_len)
        if cfg.post_norms:
            y = apply_norm(p["post_attn_norm"], y, cfg)
        h = residual(h, y, cfg)
        if enc_kv is not None:
            x = apply_norm(p["xnorm"], h, cfg)
            h = residual(h, attn.cross_attn_apply(p["xattn"], x, enc_kv,
                                                  cfg), cfg)
        h, _ = _ffn(p, h, cfg)
        return h, cache
    if kind == "M":
        x = apply_norm(p["norm1"], h, cfg)
        if chunk_len is None:
            y, cache = ssm_mod.ssm_decode(p["ssm"], x, cfg, cache)
        else:
            y, cache = _recurrent_mixed_advance(
                lambda xt, c: ssm_mod.ssm_decode(p["ssm"], xt, cfg, c),
                x, cache, chunk_len)
        h, _ = _ffn_aux(p, residual(h, y, cfg), cfg)
        return h, cache
    if kind == "R":
        x = apply_norm(p["norm1"], h, cfg)
        if chunk_len is None:
            y, cache = rg_mod.rglru_decode(p["rg"], x, cfg, cache)
        else:
            y, cache = _recurrent_mixed_advance(
                lambda xt, c: rg_mod.rglru_decode(p["rg"], xt, cfg, c),
                x, cache, chunk_len)
        h, _ = _ffn_aux(p, residual(h, y, cfg), cfg)
        return h, cache
    raise ValueError(kind)


def _recurrent_mixed_advance(step_fn, x, cache, chunk_len):
    """Advance recurrent state through a mixed prefill+decode launch.

    x (B, L, d) normed chunk columns; chunk_len (B,) valid columns per
    slot (decode slots carry 1).  Scans the L columns through the
    layer's one-token decode ``step_fn``, masking each slot's state
    update once its chunk is exhausted — so every slot's state advances
    by exactly its own tokens, in order, with per-step ops identical to
    the blocking decode path (bitwise-equal states by construction).
    Columns at/after chunk_len produce garbage outputs that the caller's
    last-valid-row gather never reads.
    """
    b, L, _ = x.shape
    cl = jnp.broadcast_to(jnp.asarray(chunk_len, jnp.int32), (b,))

    def col(c, xs):
        xt, i = xs
        y, c_new = step_fn(xt[:, None, :], c)            # (B, 1, d)
        keep = i < cl                                    # (B,)
        c = jax.tree.map(
            lambda new, old: jnp.where(
                keep.reshape((-1,) + (1,) * (new.ndim - 1)), new, old),
            c_new, c)
        return c, y[:, 0]

    cache, ys = jax.lax.scan(col, cache,
                             (x.transpose(1, 0, 2), jnp.arange(L)))
    return ys.transpose(1, 0, 2), cache


# ---------------------------------------------------------------------------
# Full-model params
# ---------------------------------------------------------------------------


def init_params(rng, cfg: ModelConfig):
    n_prefix, n_rep, tail = layout(cfg)
    use_moe = cfg.moe is not None
    p = {"embed": init_embed(rng_for(rng, "embed"), cfg)}
    fe = init_frontend(rng_for(rng, "frontend"), cfg)
    if fe is not None:
        p["frontend"] = fe

    cross = cfg.is_encdec
    p["prefix"] = [
        init_sublayer(rng_for(rng, f"prefix{i}"), cfg, "G", False,
                      d_ff=cfg.moe.d_ff_dense if cfg.moe else None,
                      cross=cross)
        for i in range(n_prefix)
    ]

    def group_init(r):
        return {
            f"sub{j}": init_sublayer(
                jax.random.fold_in(r, j), cfg, cfg.layer_pattern[j],
                use_moe and cfg.layer_pattern[j] in "GLM", cross=cross)
            for j in range(len(cfg.layer_pattern))
        }

    if n_rep > 0:
        p["scan"] = jax.vmap(group_init)(
            jax.random.split(rng_for(rng, "scan"), n_rep))
    p["tail"] = [
        init_sublayer(rng_for(rng, f"tail{i}"), cfg, k,
                      use_moe and k in "GLM", cross=cross)
        for i, k in enumerate(tail)
    ]
    p["final_norm"] = init_norm(rng, cfg, cfg.d_model)

    if cfg.is_encdec:
        enc = {}
        enc["scan"] = jax.vmap(
            lambda r: {"sub0": init_sublayer(r, cfg, "G", False)})(
                jax.random.split(rng_for(rng, "enc"), cfg.enc_layers))
        enc["final_norm"] = init_norm(rng, cfg, cfg.d_model)
        p["encoder"] = enc

    if cfg.mtp_depth > 0:
        p["mtp"] = {
            "proj": dense_init(rng_for(rng, "mtp/proj"),
                               (2 * cfg.d_model, cfg.d_model)),
            "norm_h": init_norm(rng, cfg, cfg.d_model),
            "norm_e": init_norm(rng, cfg, cfg.d_model),
            "layer": init_sublayer(rng_for(rng, "mtp/layer"), cfg, "G",
                                   use_moe),
            "final_norm": init_norm(rng, cfg, cfg.d_model),
        }
    return p


# matrices the compute reads in float32 (MoE routing, RG-LRU gates): their
# storage stays f32 so serving-dtype storage never changes a result
_F32_AT_USE = frozenset({"router", "wa_gate", "wi_gate"})


def init_params_serving(rng, cfg: ModelConfig, mesh=None):
    """``init_params`` with every weight matrix stored at ``cfg.dtype``.

    Compute already casts those matrices to ``cfg.dtype`` at use, so the
    model's outputs are unchanged; vectors (norm scales, biases, SSM
    constants) stay f32.  One jitted call draws and casts on the device:
    the f32 draws are compiler temporaries, so a model whose f32 tree
    would not fit (qwen3-4b: ~16 GB f32 vs ~8 GB bf16) is built in
    place.  With a serving ``mesh`` the leaves are built directly in the
    serving engine's placement, never whole on one device first."""
    dt = cdtype(cfg)

    def storage(path, x):
        stacked = any(getattr(k, "key", None) == "scan" for k in path)
        name = getattr(path[-1], "key", None)
        if x.ndim - stacked >= 2 and name not in _F32_AT_USE:
            return x.astype(dt)
        return x

    def build(r):
        return jax.tree_util.tree_map_with_path(storage, init_params(r, cfg))

    shardings = None
    if mesh is not None:
        from repro.sharding import serving_param_shardings
        shardings = serving_param_shardings(jax.eval_shape(build, rng), mesh)
    return jax.jit(build, out_shardings=shardings)(rng)


# ---------------------------------------------------------------------------
# Trunk forward (training)
# ---------------------------------------------------------------------------


def _embed_inputs(params, cfg: ModelConfig, tokens, frontend_embeds):
    h = embed_tokens(params["embed"], tokens, cfg)
    if frontend_embeds is not None:
        fe = apply_frontend(params["frontend"], frontend_embeds, cfg)
        h = jnp.concatenate([fe, h], axis=1)
    if cfg.pos_kind == "abs_sinusoidal":
        h = h + sinusoidal_pos(h.shape[1], cfg.d_model).astype(h.dtype)[None]
    return annotate(h, "batch", "seq", "d_model")


def encode(params, cfg: ModelConfig, enc_embeds):
    """Encoder stack over stub frame embeddings (B, S_enc, d)."""
    enc = params["encoder"]
    h = apply_frontend(params["frontend"], enc_embeds, cfg)
    if cfg.pos_kind == "abs_sinusoidal":
        h = h + sinusoidal_pos(h.shape[1], cfg.d_model).astype(h.dtype)[None]
    positions = jnp.arange(h.shape[1])

    def body(hh, lp):
        hh, _ = sublayer_train(lp["sub0"], hh, cfg, "G", positions=positions,
                               kv_repeat=1, causal=False)
        return hh, None

    h, _ = jax.lax.scan(body, h, enc["scan"])
    return apply_norm(enc["final_norm"], h, cfg)


def forward_trunk(params, cfg: ModelConfig, tokens, *, frontend_embeds=None,
                  enc_out=None, kv_repeat: int = 1, remat: bool = True,
                  positions=None):
    """Returns (h (B, S, d), aux_loss_sum)."""
    h = _embed_inputs(params, cfg, tokens, frontend_embeds)
    if positions is None:
        positions = jnp.arange(h.shape[1])
    enc_kv = None

    aux_total = jnp.float32(0.0)

    def run(p, h, kind, ekv):
        return sublayer_train(p, h, cfg, kind, positions=positions,
                              kv_repeat=kv_repeat, enc_kv=ekv)

    for i, lp in enumerate(params["prefix"]):
        ekv = _layer_enc_kv(lp, enc_out, cfg)
        h, aux = run(lp, h, "G", ekv)
        aux_total += aux

    if "scan" in params:
        def group_body(carry, lp):
            hh, aux_sum = carry
            for j, kind in enumerate(cfg.layer_pattern):
                ekv = _layer_enc_kv(lp[f"sub{j}"], enc_out, cfg)
                hh, aux = sublayer_train(lp[f"sub{j}"], hh, cfg, kind,
                                         positions=positions,
                                         kv_repeat=kv_repeat, enc_kv=ekv)
                aux_sum = aux_sum + aux
            return (hh, aux_sum), None

        body = jax.checkpoint(group_body) if remat else group_body
        (h, aux_total), _ = jax.lax.scan(body, (h, aux_total), params["scan"])

    _, _, tail = layout(cfg)
    for lp, kind in zip(params["tail"], tail):
        ekv = _layer_enc_kv(lp, enc_out, cfg)
        h, aux = run(lp, h, kind, ekv)
        aux_total += aux

    h = apply_norm(params["final_norm"], h, cfg)
    return h, aux_total


def _layer_enc_kv(lp, enc_out, cfg):
    if enc_out is None or "xattn" not in lp:
        return None
    return attn.cross_kv(lp["xattn"], enc_out, cfg)


# ---------------------------------------------------------------------------
# Loss (chunked cross-entropy; logits never materialized over full S)
# ---------------------------------------------------------------------------


def chunked_ce(params, cfg: ModelConfig, h, labels, chunk: int = 256):
    """h (B, S, d), labels (B, S) int32 (−1 = masked) → (sum_nll, n_valid).
    Frontend positions (if any) must already be stripped from h."""
    b, s, _ = h.shape
    pad = (-s) % chunk
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    nc = (s + pad) // chunk
    hc = h.reshape(b, nc, chunk, -1).transpose(1, 0, 2, 3)
    lc = labels.reshape(b, nc, chunk).transpose(1, 0, 2)

    def body(carry, xs):
        nll, nv = carry
        hh, ll = xs
        logits = lm_logits(params["embed"], hh, cfg)     # (B, C, V) fp32
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(ll, 0)[..., None], axis=-1)[..., 0]
        valid = (ll >= 0).astype(jnp.float32)
        nll = nll + ((logz - gold) * valid).sum()
        nv = nv + valid.sum()
        return (nll, nv), None

    (nll, nv), _ = jax.lax.scan(body, (jnp.float32(0.0), jnp.float32(0.0)),
                                (hc, lc))
    return nll, nv


def train_loss(params, cfg: ModelConfig, batch, *, kv_repeat: int = 1,
               remat: bool = True, loss_chunk: int = 256):
    """batch: {tokens (B,St), labels (B,St), frontend_embeds?, enc_embeds?}.
    Returns (loss, metrics)."""
    enc_out = None
    if cfg.is_encdec:
        enc_out = encode(params, cfg, batch["enc_embeds"])
        h, aux = forward_trunk(params, cfg, batch["tokens"], enc_out=enc_out,
                               kv_repeat=kv_repeat, remat=remat)
    else:
        h, aux = forward_trunk(params, cfg, batch["tokens"],
                               frontend_embeds=batch.get("frontend_embeds"),
                               kv_repeat=kv_repeat, remat=remat)
    if cfg.n_frontend_tokens and not cfg.is_encdec:
        h = h[:, cfg.n_frontend_tokens:]
    nll, nv = chunked_ce(params, cfg, h, batch["labels"], loss_chunk)
    loss = nll / jnp.maximum(nv, 1.0)
    metrics = {"nll": loss, "aux_loss": aux, "n_valid": nv}

    if cfg.mtp_depth > 0:
        mtp_loss = _mtp_loss(params, cfg, h, batch, kv_repeat, loss_chunk)
        metrics["mtp_loss"] = mtp_loss
        loss = loss + 0.3 * mtp_loss
    loss = loss + aux
    return loss, metrics


def _mtp_loss(params, cfg: ModelConfig, h, batch, kv_repeat, loss_chunk):
    """DeepSeek MTP depth-1: predict token t+2 from (h_t, emb(token_{t+1}))."""
    mtp = params["mtp"]
    tokens, labels = batch["tokens"], batch["labels"]
    h_in = apply_norm(mtp["norm_h"], h[:, :-1], cfg)
    e_in = apply_norm(mtp["norm_e"],
                      embed_tokens(params["embed"], tokens[:, 1:], cfg), cfg)
    x = jnp.concatenate([h_in, e_in], axis=-1) @ mtp["proj"].astype(
        cdtype(cfg))
    positions = jnp.arange(x.shape[1])
    x, _ = sublayer_train(mtp["layer"], x, cfg, "G", positions=positions,
                          kv_repeat=kv_repeat)
    x = apply_norm(mtp["final_norm"], x, cfg)
    # position t predicts labels[t+1] (i.e. token t+2); length S-1 matches x
    mtp_labels = labels[:, 1:]
    nll, nv = chunked_ce(params, cfg, x, mtp_labels, loss_chunk)
    return nll / jnp.maximum(nv, 1.0)


# ---------------------------------------------------------------------------
# Serving: prefill + decode
# ---------------------------------------------------------------------------


def _all_kinds(cfg: ModelConfig):
    n_prefix, n_rep, tail = layout(cfg)
    return n_prefix, n_rep, tail


def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               kv_repeat: int = 1, kv_mode: str = "exact",
               kv_clusters: int = 512, kv_tail: int = 256,
               kv_pool_blocks: int = 0, kv_block_size: int = 0):
    """``kv_pool_blocks``/``kv_block_size`` switch clustered tails to the
    paged block-pool layout (see runtime/kv_pool.py); one pool per layer
    leaf (scan-stacked leaves carry the layer dim), sharing the engine's
    single block table."""
    n_prefix, n_rep, tail = layout(cfg)
    mk = lambda kind: init_sublayer_cache(  # noqa: E731
        cfg, kind, batch, max_seq, kv_repeat, kv_mode, kv_clusters, kv_tail,
        kv_pool_blocks, kv_block_size)
    cache = {
        "prefix": [mk("G") for _ in range(n_prefix)],
        "tail": [mk(k) for k in tail],
    }
    if n_rep > 0:
        group = {f"sub{j}": mk(cfg.layer_pattern[j])
                 for j in range(len(cfg.layer_pattern))}
        cache["scan"] = jax.tree.map(
            lambda l: jnp.zeros((n_rep,) + l.shape, l.dtype), group)
    return cache


def clustered_slot_state(cache, j):
    """Snapshot slot ``j``'s per-slot state from every snapshot-bearing
    leaf of an engine cache:

    * clustered ring leaves — the summary rows (centroids, counts,
      coverage frontier; attention.CLUSTERED_SLOT_KEYS).  Tail payloads
      are NOT copied: in the paged engine they live in shared pool
      blocks that the prefix cache pins by ref count instead.
    * recurrent-state leaves ('M'/'R': {"conv","ssm"} / {"conv","h"}) —
      the *whole* fixed-size state.  For the recurrent family the state
      IS the checkpoint, so template-store prefix sharing and the
      preempt→swap→resume path carry it in this same snapshot format.

    Returns a cache-shaped pytree (other leaves dropped to None) that
    ``restore_clustered_slot_state`` writes back into any slot."""
    def leaf(node):
        stacked = node["k_cents"].ndim == 5       # scan: (L, B, ...)
        ax = 1 if stacked else 0
        return {k: jax.lax.dynamic_slice_in_dim(node[k], j, 1, axis=ax)
                for k in attn.CLUSTERED_SLOT_KEYS}

    def rleaf(node):
        ax = 1 if layer_state.recurrent_leaf_stacked(node) else 0
        return {k: jax.lax.dynamic_slice_in_dim(node[k], j, 1, axis=ax)
                for k in node}

    def walk(node):
        if isinstance(node, dict):
            if "k_cents" in node:
                return leaf(node)
            if layer_state.is_recurrent_leaf(node):
                return rleaf(node)
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, list):
            return [walk(v) for v in node]
        return None

    return walk(cache)


def restore_clustered_slot_state(cache, snap, j):
    """Write a ``clustered_slot_state`` snapshot into slot ``j`` of every
    snapshot-bearing leaf (prefix-sharing admission and swap-in resume:
    the reused prompt centroids + coverage frontier — and, for
    recurrent-state layers, the full (conv, ssm)/(conv, h) checkpoint —
    land in the fresh slot; ring tail blocks are adopted through the
    block table separately)."""
    def walk(node, s):
        if isinstance(node, dict):
            if "k_cents" in node:
                stacked = node["k_cents"].ndim == 5
                ax = 1 if stacked else 0
                return dict(node, **{
                    k: jax.lax.dynamic_update_slice_in_dim(
                        node[k], s[k].astype(node[k].dtype), j, axis=ax)
                    for k in attn.CLUSTERED_SLOT_KEYS})
            if layer_state.is_recurrent_leaf(node):
                ax = 1 if layer_state.recurrent_leaf_stacked(node) else 0
                return dict(node, **{
                    k: jax.lax.dynamic_update_slice_in_dim(
                        node[k], s[k].astype(node[k].dtype), j, axis=ax)
                    for k in node})
            return {k: walk(v, s[k]) for k, v in node.items()}
        if isinstance(node, list):
            return [walk(v, sv) for v, sv in zip(node, s)]
        return node

    return walk(cache, snap)


def prefill(params, cfg: ModelConfig, tokens, *, max_seq: int,
            frontend_embeds=None, enc_embeds=None, kv_repeat: int = 1,
            last_pos=None, recurrent_mode: str = "scan"):
    """Full-sequence prefill.  Returns (last_logits (B, V), cache).

    ``last_pos`` (traced scalar ok) selects which position's logits to
    return — needed when prompts are right-padded to a bucket length (the
    continuous batcher): the causal mask makes position last_pos exact
    regardless of the padding behind it.

    ``recurrent_mode`` (see :func:`sublayer_prefill`): the serving
    engine passes "sequential" so recurrent-state layers prefill by
    stepping their decode recurrence — bit-identical to chunked
    admission through the mixed launch; "scan" keeps the parallel
    chunked-SSD / associative-scan forms for training-style use."""
    enc_out = None
    cross_cache = None
    if cfg.is_encdec:
        enc_out = encode(params, cfg, enc_embeds)
    h = _embed_inputs(params, cfg, tokens, frontend_embeds)
    positions = jnp.arange(h.shape[1])

    caches = {"prefix": [], "tail": []}
    cross = {"prefix": [], "tail": []}
    for lp in params["prefix"]:
        ekv = _layer_enc_kv(lp, enc_out, cfg)
        h, c, _ = sublayer_prefill(lp, h, cfg, "G", positions=positions,
                                   kv_repeat=kv_repeat, max_seq=max_seq,
                                   enc_kv=ekv, recurrent_mode=recurrent_mode)
        caches["prefix"].append(c)
        cross["prefix"].append(ekv)

    if "scan" in params:
        def group_body(hh, lp):
            cs = {}
            for j, kind in enumerate(cfg.layer_pattern):
                ekv = _layer_enc_kv(lp[f"sub{j}"], enc_out, cfg)
                hh, c, _ = sublayer_prefill(
                    lp[f"sub{j}"], hh, cfg, kind, positions=positions,
                    kv_repeat=kv_repeat, max_seq=max_seq, enc_kv=ekv,
                    recurrent_mode=recurrent_mode)
                cs[f"sub{j}"] = c
                if ekv is not None:
                    cs[f"xkv{j}"] = ekv
            return hh, cs

        h, scan_caches = jax.lax.scan(group_body, h, params["scan"])
        caches["scan"] = scan_caches

    _, _, tail = layout(cfg)
    for lp, kind in zip(params["tail"], tail):
        ekv = _layer_enc_kv(lp, enc_out, cfg)
        h, c, _ = sublayer_prefill(lp, h, cfg, kind, positions=positions,
                                   kv_repeat=kv_repeat, max_seq=max_seq,
                                   enc_kv=ekv, recurrent_mode=recurrent_mode)
        caches["tail"].append(c)
        cross["tail"].append(ekv)

    if cfg.is_encdec:
        caches["cross_prefix"] = [c for c in cross["prefix"]]
        caches["cross_tail"] = [c for c in cross["tail"]]

    h = apply_norm(params["final_norm"], h, cfg)
    h_last = (h[:, -1:] if last_pos is None
              else jax.lax.dynamic_slice_in_dim(h, last_pos, 1, axis=1))
    logits = lm_logits(params["embed"], h_last, cfg)[:, 0]
    return logits, caches


def decode_step(params, cfg: ModelConfig, cache, tokens, t, *,
                kv_repeat: int = 1, chunk_len=None):
    """One decode step.  tokens (B, 1), t scalar int32 (current position).
    Returns (logits (B, V), cache').

    Mixed mode (chunked prefill interleaved with decode): tokens (B, L)
    with per-slot ``chunk_len`` (B,) valid columns and ``t`` (B,) the
    slot's cache length before the step.  Decode slots carry their one
    pending token (chunk_len 1); a slot admitting a prompt carries a
    whole chunk whose K/V stream straight into its cache at exact
    positions t..t+chunk_len-1.  The returned logits are each slot's LAST
    valid row — the next-token distribution for decode slots, and the
    first-generated-token distribution when a slot's final prompt chunk
    lands.  Covers both layer-state families (ring-KV attention and
    'M'/'R' recurrent state); MLA latent caches and encoder-decoder
    remain unsupported."""
    if chunk_len is not None and cfg.is_encdec:
        raise NotImplementedError("mixed-mode chunked decode is "
                                  "decoder-only")
    h = embed_tokens(params["embed"], tokens, cfg)
    if cfg.embed_scale:
        pass  # already applied in embed_tokens
    if cfg.pos_kind == "abs_sinusoidal":
        # t may be scalar or per-slot (B,) under continuous batching
        tb = jnp.broadcast_to(jnp.asarray(t), (h.shape[0],))
        pe = jax.vmap(lambda ti: sinusoidal_pos(h.shape[1], cfg.d_model,
                                                offset=ti))(tb)   # (B, L, d)
        h = h + pe.astype(h.dtype)
    h = annotate(h, "batch", "seq", "d_model")

    new_cache = {"prefix": [], "tail": []}
    for lp, c in zip(params["prefix"], cache["prefix"]):
        ekv = cache.get("cross_prefix", [None] * len(params["prefix"]))
        h, c2 = sublayer_decode(lp, h, cfg, "G", c, t, kv_repeat=kv_repeat,
                                enc_kv=ekv[len(new_cache["prefix"])]
                                if cfg.is_encdec else None,
                                chunk_len=chunk_len)
        new_cache["prefix"].append(c2)

    if "scan" in params:
        def group_body(hh, xs):
            lp, cs = xs
            cs2 = dict(cs)
            for j, kind in enumerate(cfg.layer_pattern):
                ekv = cs.get(f"xkv{j}")
                hh, cnew = sublayer_decode(lp[f"sub{j}"], hh, cfg, kind,
                                           cs[f"sub{j}"], t,
                                           kv_repeat=kv_repeat, enc_kv=ekv,
                                           chunk_len=chunk_len)
                cs2[f"sub{j}"] = cnew
            return hh, cs2

        h, scan_caches = jax.lax.scan(group_body, h,
                                      (params["scan"], cache["scan"]))
        new_cache["scan"] = scan_caches

    _, _, tail = layout(cfg)
    for i, (lp, kind) in enumerate(zip(params["tail"], tail)):
        ekv = (cache.get("cross_tail", [None] * len(tail))[i]
               if cfg.is_encdec else None)
        h, c2 = sublayer_decode(lp, h, cfg, kind, cache["tail"][i], t,
                                kv_repeat=kv_repeat, enc_kv=ekv,
                                chunk_len=chunk_len)
        new_cache["tail"].append(c2)

    if cfg.is_encdec:
        new_cache["cross_prefix"] = cache["cross_prefix"]
        new_cache["cross_tail"] = cache["cross_tail"]

    h = apply_norm(params["final_norm"], h, cfg)
    if chunk_len is not None:
        # each slot's last valid row carries its next-token distribution;
        # gather before the vocab projection so the L× logits are never
        # materialized
        idx = (jnp.broadcast_to(jnp.asarray(chunk_len, jnp.int32),
                                (h.shape[0],)) - 1)[:, None, None]
        h = jnp.take_along_axis(h, idx, axis=1)
    logits = lm_logits(params["embed"], h, cfg)[:, 0]
    return logits, new_cache


def _sublayer_decode_packed(p, h, cfg: ModelConfig, cache, *, row_slot,
                            row_pos, row_tw, block_tables, block_size,
                            width, kv_repeat):
    """One 'G' sublayer over packed rows (paged clustered KV).  h
    (N, 1, d); every non-attention op is row-wise, so rows stand in for
    the batch axis exactly.  Returns (h, cache, FFN metrics)."""
    x = apply_norm(p["norm1"], h, cfg)
    y, cache = attn.attn_decode_clustered_packed(
        p["attn"], x, cfg, cache=cache, row_slot=row_slot, row_pos=row_pos,
        row_tw=row_tw, block_tables=block_tables, block_size=block_size,
        kv_repeat=kv_repeat, width=width)
    if cfg.post_norms:
        y = apply_norm(p["post_attn_norm"], y, cfg)
    h, metrics = _ffn(p, residual(h, y, cfg), cfg)
    return h, cache, metrics


def _sublayer_decode_window_packed(p, h, cfg: ModelConfig, cache, *,
                                   row_slot, row_pos, row_cidx, width,
                                   kv_repeat):
    """One 'L' sublayer over packed rows: WindowRetention's dense ring,
    written in row_cidx order (attention.attn_decode_window_packed)."""
    x = apply_norm(p["norm1"], h, cfg)
    y, cache = attn.attn_decode_window_packed(
        p["attn"], x, cfg, cache=cache, row_slot=row_slot, row_pos=row_pos,
        row_cidx=row_cidx, width=width, kv_repeat=kv_repeat)
    if cfg.post_norms:
        y = apply_norm(p["post_attn_norm"], y, cfg)
    h, metrics = _ffn(p, residual(h, y, cfg), cfg)
    return h, cache, metrics


def recurrent_steps(n_slots: int, n_later: int) -> int:
    """One-token recurrent steps a packed launch runs per recurrent
    sublayer (:func:`_sublayer_decode_recurrent_packed`): the first
    round over every slot plus one step per later chunk row."""
    return n_slots + n_later


def _sublayer_decode_recurrent_packed(p, h, cfg: ModelConfig, cache, kind,
                                      *, row_slot, row_pos, row_cidx,
                                      width, layer=None):
    """One recurrent sublayer ('M'/'R') over packed rows.

    Recurrent state is slot-indexed and fixed-size, and must advance one
    token at a time in position order.  A slot's rows within a packed
    step carry distinct chunk indices (row_cidx 0..chunk_len-1).  The
    first round steps every slot at once, each from its row with
    row_cidx 0 (its decode row or its chunk's first row; a slot with no
    such row keeps its state); then the chunks' later rows step one at a
    time, in row_cidx order, in a rolled loop (``ssm_advance``).  Each
    step runs the one-token recurrence of the dense decode
    (``ssm_recur`` / ``rglru_recur``); the projections in and out are
    row-wise and run once over all rows, so every row is bit-identical to
    the dense one-token decode.  Padding rows (row_pos < 0) never write
    state.  With ``layer``, the cache leaves are stacked over scanned
    layer groups and this sublayer's state is ``[layer]``.
    """
    pp, recur_in, recur, recur_out = (
        (p["ssm"], ssm_mod.ssm_in, ssm_mod.ssm_recur, ssm_mod.ssm_out)
        if kind == "M" else
        (p["rg"], rg_mod.rglru_in, rg_mod.rglru_recur, rg_mod.rglru_out))
    x = apply_norm(p["norm1"], h, cfg)                   # (N, 1, d)
    ins, side = recur_in(pp, x, cfg)
    n = x.shape[0]
    st = cache if layer is None else jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, False), cache)
    n_slots = st["conv"].shape[0]
    real = row_pos >= 0
    with scope("ssm_advance"):
        first = real & (row_cidx == 0)
        match = ((row_slot[None, :] == jnp.arange(n_slots)[:, None])
                 & first[None, :])                       # (slots, N)
        has = match.any(1)
        src = jnp.argmax(match, 1)                       # the slot's row
        mid_s, new = recur(pp, jax.tree.map(lambda a: a[src], ins), cfg, st)
        st = jax.tree.map(
            lambda u, a: jnp.where(has.reshape((-1,) + (1,) * (a.ndim - 1)),
                                   u.astype(a.dtype), a), new, st)
        mid = jnp.where(first[:, None, None], mid_s[row_slot], 0).astype(
            mid_s.dtype)
        if width > 1:
            later = real & (row_cidx >= 1)
            order = jnp.argsort(jnp.where(later, row_cidx, width),
                                stable=True)

            def one(a, i):
                return jax.lax.dynamic_slice_in_dim(a, i, 1)

            def step(i, carry):
                s_all, m = carry
                r = order[i]
                sl = row_slot[r]
                y_r, st_r = recur(pp, jax.tree.map(lambda a: one(a, r), ins),
                                  cfg, jax.tree.map(lambda a: one(a, sl),
                                                    s_all))
                s_all = jax.tree.map(
                    lambda a, u: jax.lax.dynamic_update_slice_in_dim(
                        a, u.astype(a.dtype), sl, 0), s_all, st_r)
                return s_all, jax.lax.dynamic_update_slice_in_dim(m, y_r, r,
                                                                  0)

            st, mid = jax.lax.fori_loop(0, later.sum(), step, (st, mid))
    cache = st if layer is None else jax.tree.map(
        lambda a, u: jax.lax.dynamic_update_index_in_dim(a, u, layer, 0),
        cache, st)
    h = residual(h, recur_out(pp, mid, side, cfg), cfg)
    if "norm2" in p:
        h, metrics = _ffn(p, h, cfg)
        return h, cache, metrics
    return h, cache, {}


def decode_step_packed(params, cfg: ModelConfig, cache, tokens, row_slot,
                       row_pos, row_tw, row_cidx, block_tables, *,
                       block_size: int, width: int = 1,
                       kv_repeat: int = 1):
    """Packed ragged engine step for the paged clustered-KV path.

    Instead of the dense launch's (slots, width) token grid — every slot
    paying ``width`` rows of trunk compute — each *real* (slot, position)
    pair is one row: tokens (N,), row_slot (N,) physical slot, row_pos
    (N,) absolute position (−1 ⇒ padding row), row_tw (N,) the slot's
    ring watermark t + chunk_len this step, row_cidx (N,) the row's index
    within its admission chunk (decode rows 0; ``width`` = static max
    chunk length, sequencing sliding-window ring commits), block_tables
    (B, T) global physical tail-block ids.  Returns (logits (N, V),
    cache'): every row's next-token distribution — the engine reads each
    slot's last valid row (decode slots: their one row; an admitting
    slot's final chunk row carries its first generated token).
    Decoder-only models whose layers all carry a layer-state family
    ('G' clustered/quota + 'L' sliding-window rings, 'M'/'R' recurrent
    state — the paged engine's gate); MLP / norms / embeddings are
    position-independent, so treating rows as batch is exact, and
    per-row outputs are bit-identical to the dense launch.

    A model with MoE layers also returns int32 (2,): top-k routes of the
    real rows that landed on a held expert, and all of their top-k
    routes, summed over the MoE layers."""
    tokens = jnp.where(row_pos >= 0, tokens, 0)[:, None]   # (N, 1)
    h = embed_tokens(params["embed"], tokens, cfg)
    if cfg.pos_kind == "abs_sinusoidal":
        pe = jax.vmap(lambda ti: sinusoidal_pos(1, cfg.d_model,
                                                offset=ti))(row_pos)
        h = h + pe.astype(h.dtype)
    h = annotate(h, "batch", "seq", "d_model")
    routes = jnp.zeros(2, jnp.int32) if cfg.moe is not None else None

    def tally(acc, metrics):
        if acc is None or "held_routes" not in metrics:
            return acc
        real = row_pos >= 0
        held = jnp.where(real, metrics["held_routes"][:, 0], 0).sum()
        return acc + jnp.stack([held, cfg.moe.top_k * real.sum()]).astype(
            jnp.int32)

    def step(p, hh, c, kind):
        if kind in ("M", "R"):
            return _sublayer_decode_recurrent_packed(
                p, hh, cfg, c, kind, row_slot=row_slot, row_pos=row_pos,
                row_cidx=row_cidx, width=width)
        if kind == "L":
            return _sublayer_decode_window_packed(
                p, hh, cfg, c, row_slot=row_slot, row_pos=row_pos,
                row_cidx=row_cidx, width=width, kv_repeat=kv_repeat)
        return _sublayer_decode_packed(
            p, hh, cfg, c, row_slot=row_slot, row_pos=row_pos,
            row_tw=row_tw, block_tables=block_tables,
            block_size=block_size, width=width, kv_repeat=kv_repeat)

    new_cache = {"prefix": [], "tail": []}
    for lp, c in zip(params["prefix"], cache["prefix"]):
        h, c2, metrics = step(lp, h, c, "G")
        routes = tally(routes, metrics)
        new_cache["prefix"].append(c2)

    if "scan" in params:
        # recurrent state rides the carry, each sublayer's state updated
        # in place: as the scan's input and output it would be held twice
        # (2.5 GB at granite's 32 slots)
        rec = {f"sub{j}": cache["scan"][f"sub{j}"]
               for j, kind in enumerate(cfg.layer_pattern)
               if kind in layer_state.RECURRENT_KINDS}
        rest = {k: v for k, v in cache["scan"].items() if k not in rec}

        def group_body(carry, xs):
            hh, acc, rec, i = carry
            lp, cs = xs
            rec, cs2 = dict(rec), dict(cs)
            for j, kind in enumerate(cfg.layer_pattern):
                key = f"sub{j}"
                if key in rec:
                    hh, rec[key], metrics = _sublayer_decode_recurrent_packed(
                        lp[key], hh, cfg, rec[key], kind, row_slot=row_slot,
                        row_pos=row_pos, row_cidx=row_cidx, width=width,
                        layer=i)
                else:
                    hh, cs2[key], metrics = step(lp[key], hh, cs[key], kind)
                acc = tally(acc, metrics)
            return (hh, acc, rec, None if i is None else i + 1), cs2

        (h, routes, rec, _), rest = jax.lax.scan(
            group_body, (h, routes, rec, jnp.int32(0) if rec else None),
            (params["scan"], rest))
        new_cache["scan"] = {**rest, **rec}

    _, _, tail_kinds = layout(cfg)
    for i, (lp, kind) in enumerate(zip(params["tail"], tail_kinds)):
        h, c2, metrics = step(lp, h, cache["tail"][i], kind)
        routes = tally(routes, metrics)
        new_cache["tail"].append(c2)

    with scope("lm_head"):
        h = apply_norm(params["final_norm"], h, cfg)
        logits = lm_logits(params["embed"], h, cfg)[:, 0]
    if routes is None:
        return logits, new_cache
    return logits, new_cache, routes
