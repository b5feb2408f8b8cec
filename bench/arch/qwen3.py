"""Qwen3 (``model_type`` "qwen3"): a dense GQA decoder.

Every layer is RMSNorm, q/k/v projections, an optional per-head RMSNorm
of q and k, half-split RoPE, attention over the clustered memory state,
the output projection, then RMSNorm and a SwiGLU MLP; the head is tied to
the embedding table or its own matrix.  The contract of this module is in
``bench/arch/__init__.py``.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from bench.reference import HI, Memory, attend, cluster_states, fake_quant, rms


@dataclasses.dataclass(frozen=True)
class Model:
    n_heads: int
    n_kv_heads: int
    head_dim: int
    eps: float
    theta: float
    qk_norm: bool
    tied: bool
    matmul_params: int
    attn_layers: int


def program_config(conf: dict):
    """The configuration file as the program's ``ModelConfig`` (every
    published key it reads maps to one field)."""
    from repro.models.config import ModelConfig

    act = conf["hidden_act"]
    if act != "silu":
        raise ValueError(f"hidden_act {act!r}: only silu (SwiGLU) is served")
    return ModelConfig(
        name=conf["name"], family="dense",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
        layer_pattern="G", mlp_kind="swiglu", norm_eps=conf["rms_norm_eps"],
        qk_norm=conf["qk_norm"], rope_theta=float(conf["rope_theta"]),
        tie_embeddings=conf["tie_word_embeddings"],
        dtype=conf["serving"]["dtype"]).validate()


def reference_model(conf: dict) -> Model:
    """Sizes of the reference.  One token passes through every layer's
    q, k, v, o, gate, up and down projections and the output head; every
    layer reads the paged clustered kernel."""
    d, hq = conf["hidden_size"], conf["num_attention_heads"]
    hkv, dh = conf["num_key_value_heads"], conf["head_dim"]
    n_layers = conf["num_hidden_layers"]
    per_layer = (d * hq * dh * 2 + 2 * d * hkv * dh
                 + 3 * d * conf["intermediate_size"])
    return Model(n_heads=hq, n_kv_heads=hkv, head_dim=dh,
                 eps=float(conf["rms_norm_eps"]),
                 theta=float(conf["rope_theta"]), qk_norm=conf["qk_norm"],
                 tied=conf["tie_word_embeddings"],
                 matmul_params=n_layers * per_layer + d * conf["vocab_size"],
                 attn_layers=n_layers)


def _rope(x, pos, theta):
    """x (L, H, D) rotated by half-split RoPE at positions pos (L,)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("m", "mem", "weight_quant"))
def logits_at(params, tokens, events, state_of, out_pos, *, m: Model,
              mem: Memory, weight_quant=None):
    """Logits (len(out_pos), vocab) of one request.  tokens (L,) the
    prompt then the served tokens fed back (padding past the request is
    never attended by a real position); events/state_of from
    ``schedule`` padded with kind-0 events."""
    if params["prefix"] or params["tail"]:
        raise ValueError("the reference expects one scanned layer group")
    fq = functools.partial(fake_quant, kind=weight_quant)
    table = params["embed"]["table"]
    L = tokens.shape[0]
    pos = jnp.arange(L)
    h = fq(table[tokens], axis=-1)
    H, Hkv, D = m.n_heads, m.n_kv_heads, m.head_dim

    def layer(h, lp):
        a, f = lp["attn"], lp["mlp"]
        x = rms(h, lp["norm1"]["scale"], m.eps)
        q = jnp.matmul(x, fq(a["wq"], axis=0), precision=HI).reshape(L, H, D)
        k = jnp.matmul(x, fq(a["wk"], axis=0), precision=HI).reshape(L, Hkv, D)
        v = jnp.matmul(x, fq(a["wv"], axis=0), precision=HI).reshape(L, Hkv, D)
        if m.qk_norm:
            q = rms(q, a["q_norm"], m.eps)
            k = rms(k, a["k_norm"], m.eps)
        q, k = _rope(q, pos, m.theta), _rope(k, pos, m.theta)
        states = cluster_states(k, v, events, mem)
        o = attend(q, k, v, states, state_of, pos)
        h = h + jnp.matmul(o, fq(a["wo"], axis=0), precision=HI)
        x = rms(h, lp["norm2"]["scale"], m.eps)
        gate = jnp.matmul(x, fq(f["w_gate"], axis=0), precision=HI)
        up = jnp.matmul(x, fq(f["w_up"], axis=0), precision=HI)
        h = h + jnp.matmul(jax.nn.silu(gate) * up, fq(f["w_down"], axis=0),
                           precision=HI)
        return h, None

    h, _ = jax.lax.scan(layer, h, params["scan"]["sub0"])
    hf = rms(h[out_pos], params["final_norm"]["scale"], m.eps)
    if m.tied:
        return jnp.einsum("td,vd->tv", hf, fq(table, axis=-1), precision=HI)
    return jnp.matmul(hf, fq(params["embed"]["head"], axis=0), precision=HI)
