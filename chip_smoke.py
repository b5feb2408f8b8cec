"""Chip smoke test: serve full-width qwen3-4b on one TPU through the paged
clustered-KV engine.

    python chip_smoke.py              # one chip: paged engine vs dense engine
    python chip_smoke.py --chips 4    # 2x2 serving mesh vs one chip

One chip: random bf16 weights from ``--seed`` at qwen3-4b's published
widths (36 layers, d_model 2560, 32/8 heads of 128, d_ff 9728, vocab
151936), served through ``Server``/``ServerConfig`` exactly as
``repro.launch.serve`` does: continuous batching with 64-token chunked
prefill, the paged block pool (16-position blocks) with packed ragged
``paged_clustered_decode`` launches, clustered KV (32 centroids, a
128-position exact ring, compaction every 16 decode tokens) so streaming
absorb and weighted bit-serial k-medians compaction both run.  Twelve
requests of 64-1024 prompt tokens, 32 new tokens each, 8 slots, max_seq
2048.  The same requests are then served on the dense (non-paged) engine
at the same chunk, which the CPU tests pin bit-identical to the paged
one.  Each engine serves the queue twice: the first (cold) serve
includes compilation, the second is warm and must repeat its tokens.

``--chips 4`` runs only the mesh path and its comparison: the paged
serve on one chip (tokens plus prefill first-token logits), then the
same on ``make_serving_mesh("2x2")`` — slots over ``data``, heads over
``model`` through the paged kernel's shard_map island.

The script runs in one process and exits non-zero, printing no result,
unless JAX's first device is a TPU.  It fails if any phase raises, any
logit is non-finite, a request's first token differs between the two
runs compared, or compaction/absorb never ran.  Its last line is one
JSON object naming the device.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import configs  # noqa: E402
from repro.core import kv_compress  # noqa: E402
from repro.core.request_cluster import Request  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_serving_mesh  # noqa: E402
from repro.models import transformer as tfm  # noqa: E402
from repro.runtime.kv_pool import PagedKVConfig  # noqa: E402
from repro.runtime.server import Server, ServerConfig  # noqa: E402
from repro.sharding import Rules, default_table, use_rules  # noqa: E402


@dataclasses.dataclass(frozen=True)
class Settings:
    """Engine and traffic settings of one smoke run."""
    batch: int = 8
    max_seq: int = 2048
    chunk: int = 64
    block: int = 16
    kv: kv_compress.KVCompressConfig = kv_compress.KVCompressConfig(
        n_clusters=32, iters=4, keep_recent=128, refresh_every=16)
    n_requests: int = 12
    prompt_min: int = 64
    prompt_max: int = 1024
    max_new: int = 32


def make_requests(cfg, st: Settings, seed: int):
    """Seeded queue: prompt lengths span [prompt_min, prompt_max] (both
    ends included), so prompts longer than the ring stream through
    absorb."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(st.prompt_min, st.prompt_max + 1, st.n_requests)
    lens[:2] = (st.prompt_min, st.prompt_max)
    reqs = [Request(i, int(n), st.max_new) for i, n in enumerate(lens)]
    prompts = {r.uid: rng.integers(0, cfg.vocab, r.prompt_len).astype(
        np.int32) for r in reqs}
    return reqs, prompts


def server_config(st: Settings, *, paged: bool, mesh=None) -> ServerConfig:
    return ServerConfig(
        batch_size=st.batch, max_seq=st.max_seq, prefill_chunk=st.chunk,
        kv_compress=st.kv, mesh=mesh,
        paged=PagedKVConfig(block_size=st.block) if paged else None)


def serve_twice(cfg, params, scfg: ServerConfig, reqs, prompts) -> dict:
    """Build a Server and serve the queue cold, then warm."""
    t0 = time.perf_counter()
    srv = Server(cfg, scfg, params)
    cold = {o.uid: o.tokens for o in srv.serve(reqs, prompts)}
    t1 = time.perf_counter()
    warm = {o.uid: o.tokens for o in srv.serve(reqs, prompts)}
    t2 = time.perf_counter()
    return {"tokens": cold, "repeat_equal": warm == cold,
            "stats": dict(srv.last_stats), "cold_s": t1 - t0,
            "warm_s": t2 - t1}


def check_run(name: str, run: dict, st: Settings) -> list:
    """Failures of one engine run: short completions, non-finite logits,
    a warm serve that changed tokens, compaction or absorb never run."""
    bad = []
    short = [u for u, t in run["tokens"].items() if len(t) != st.max_new]
    if short:
        bad.append(f"{name}: requests {short} did not get {st.max_new} "
                   f"tokens")
    s = run["stats"]
    if s["logits_nonfinite"]:
        bad.append(f"{name}: {s['logits_nonfinite']:.0f} non-finite logits")
    if not run["repeat_equal"]:
        bad.append(f"{name}: warm serve changed tokens")
    for key in ("kv_absorbs", "kv_compactions"):
        if not s[key]:
            bad.append(f"{name}: {key} is 0")
    return bad


def compare_tokens(a: dict, b: dict) -> tuple:
    """(uids whose first token differs, share of all tokens equal)."""
    first = sorted(u for u in a if a[u][:1] != b[u][:1])
    same = sum(int(x == y) for u in a for x, y in zip(a[u], b[u]))
    return first, same / max(sum(len(t) for t in a.values()), 1)


def smoke_one_chip(cfg, params, reqs, prompts, st: Settings) -> tuple:
    """Serve the queue on the paged engine, then on the dense engine at
    the same chunk.  Returns (report lines, failures, runs by engine)."""
    lines, bad = [], []
    runs = {}
    for name, paged in (("paged", True), ("dense", False)):
        run = serve_twice(cfg, params, server_config(st, paged=paged), reqs,
                          prompts)
        runs[name] = run
        s = run["stats"]
        lines.append(
            f"{name}: set-up+cold serve {run['cold_s']:.3f} s, warm serve "
            f"{run['warm_s']:.3f} s, {s['gen_tokens']:.0f} tokens, "
            f"{s['prefill_chunks']:.0f} prefill chunks, "
            f"{s['kv_absorbs']:.0f} absorbs, {s['kv_compactions']:.0f} "
            f"compactions, {s['logits_nonfinite']:.0f} non-finite logits, "
            f"warm repeats cold: {run['repeat_equal']}")
        bad += check_run(name, run, st)
    first, share = compare_tokens(runs["paged"]["tokens"],
                                  runs["dense"]["tokens"])
    lines.append(f"paged vs dense: first tokens equal for "
                 f"{len(reqs) - len(first)}/{len(reqs)} requests, "
                 f"{share:.4f} of all tokens equal")
    if first:
        bad.append(f"first token differs paged vs dense for uids {first}")
    return lines, bad, runs


def prefill_logits(cfg, params, prompts, st: Settings, rules=None):
    """First-token logits of every prompt through the model's prefill,
    right-padded to one bucket (one compile) → (n, vocab) host array."""
    def fn(p, tk, last):
        return tfm.prefill(p, cfg, tk, max_seq=st.max_seq, last_pos=last)[0]

    step = jax.jit(fn)
    out = []
    for uid in sorted(prompts):
        p = prompts[uid]
        tk = np.zeros((1, st.prompt_max), np.int32)
        tk[0, :len(p)] = p
        if rules is None:
            lg = step(params, jnp.asarray(tk), jnp.int32(len(p) - 1))
        else:
            with use_rules(rules):
                lg = step(params, jnp.asarray(tk), jnp.int32(len(p) - 1))
        out.append(np.asarray(lg[0]))
    return np.stack(out)


def peak_bytes(dev) -> int:
    return int(dev.memory_stats()["peak_bytes_in_use"])


def param_report(params) -> str:
    leaves = jax.tree.leaves(params)
    n = sum(x.size for x in leaves)
    nbytes = sum(x.size * x.dtype.itemsize for x in leaves)
    return f"{n} parameters, {nbytes} bytes"


def lowers_to_mosaic(cfg, st: Settings) -> bool:
    """Whether the paged kernel, called as the engine calls it (interpret
    resolved by ``ops.interpret_default``), lowers to a Mosaic custom
    call."""
    hkv, dh = cfg.n_kv_heads, cfg.head_dim
    nb, t_blocks = 4, st.kv.keep_recent // st.block
    bf = jnp.dtype(cfg.dtype)
    shapes = [((8, cfg.n_heads, dh), bf), ((2, 4, hkv, dh), bf),
              ((2, 4, hkv, dh), bf), ((2, 4, hkv), jnp.float32),
              ((nb, st.block, hkv, dh), bf), ((nb, st.block, hkv, dh), bf),
              ((8,), jnp.int32), ((8, t_blocks), jnp.int32),
              ((8,), jnp.int32), ((8,), jnp.int32), ((8,), jnp.int32)]
    text = jax.jit(lambda *a: ops.paged_clustered_decode(
        *a, scale=dh ** -0.5)).lower(
            *[jax.ShapeDtypeStruct(s, d) for s, d in shapes]).as_text()
    return "tpu_custom_call" in text


def run_one_chip(cfg, st: Settings, seed: int) -> list:
    dev = jax.devices()[0]
    mosaic = lowers_to_mosaic(cfg, st)
    print(f"[smoke] interpret_default() = {ops.interpret_default()}, paged "
          f"kernel lowers to tpu_custom_call: {mosaic}", flush=True)
    bad = [] if mosaic else ["paged kernel did not lower to Mosaic"]
    t0 = time.perf_counter()
    params = tfm.init_params_serving(jax.random.PRNGKey(seed), cfg)
    jax.block_until_ready(params)
    print(f"[smoke] {cfg.name}: {param_report(params)} built in "
          f"{time.perf_counter() - t0:.3f} s; peak_bytes_in_use "
          f"{peak_bytes(dev)}", flush=True)
    reqs, prompts = make_requests(cfg, st, seed)
    print(f"[smoke] {len(reqs)} requests, prompt lengths "
          f"{sorted(r.prompt_len for r in reqs)}, {st.max_new} new tokens "
          f"each", flush=True)
    lines, fails, _ = smoke_one_chip(cfg, params, reqs, prompts, st)
    for ln in lines:
        print(f"[smoke] {ln}", flush=True)
    print(f"[smoke] peak_bytes_in_use {peak_bytes(dev)}", flush=True)
    return bad + fails


def run_mesh(cfg, st: Settings, seed: int) -> list:
    if len(jax.devices()) < 4:
        raise SystemExit(f"--chips 4 needs 4 devices, found "
                         f"{len(jax.devices())}")
    reqs, prompts = make_requests(cfg, st, seed)
    key = jax.random.PRNGKey(seed)

    # one chip first; its server and weights are freed before the mesh
    # copy is built (both would not fit on chip 0)
    params = tfm.init_params_serving(key, cfg)
    t0 = time.perf_counter()
    srv = Server(cfg, server_config(st, paged=True), params)
    one = {o.uid: o.tokens for o in srv.serve(reqs, prompts)}
    one_stats = dict(srv.last_stats)
    one_logits = prefill_logits(cfg, params, prompts, st)
    print(f"[smoke] 1 chip: {time.perf_counter() - t0:.3f} s, "
          f"{one_stats['kv_absorbs']:.0f} absorbs, "
          f"{one_stats['kv_compactions']:.0f} compactions", flush=True)
    del srv, params
    gc.collect()

    mesh = make_serving_mesh("2x2")
    params = tfm.init_params_serving(key, cfg, mesh=mesh)
    t0 = time.perf_counter()
    srv = Server(cfg, server_config(st, paged=True, mesh=mesh), params)
    four = {o.uid: o.tokens for o in srv.serve(reqs, prompts)}
    four_stats = dict(srv.last_stats)
    rules = Rules(mesh, default_table("pod" in mesh.axis_names))
    four_logits = prefill_logits(cfg, params, prompts, st, rules=rules)
    print(f"[smoke] 2x2 mesh: {time.perf_counter() - t0:.3f} s, "
          f"{four_stats['kv_absorbs']:.0f} absorbs, "
          f"{four_stats['kv_compactions']:.0f} compactions, "
          f"{four_stats.get('n_data_shards', 1):.0f} data shards",
          flush=True)

    first, share = compare_tokens(one, four)
    same_all = sorted(u for u in one if one[u] != four[u])
    diff = np.abs(one_logits - four_logits)
    print(f"[smoke] 1 chip vs 2x2: first tokens equal for "
          f"{len(reqs) - len(first)}/{len(reqs)} requests, {share:.4f} of "
          f"all tokens equal, requests with any token differing: "
          f"{same_all}", flush=True)
    print(f"[smoke] prefill first-token logits: max |diff| "
          f"{float(diff.max())}, argmax equal "
          f"{int((one_logits.argmax(-1) == four_logits.argmax(-1)).sum())}"
          f"/{len(reqs)}", flush=True)
    print("[smoke] per-device peak_bytes_in_use "
          + ", ".join(f"{d.id}:{peak_bytes(d)}" for d in jax.devices()),
          flush=True)
    bad = check_run("1 chip", {"tokens": one, "stats": one_stats,
                               "repeat_equal": True}, st)
    bad += check_run("2x2", {"tokens": four, "stats": four_stats,
                             "repeat_equal": True}, st)
    if first:
        bad.append(f"first token differs 1 chip vs 2x2 for uids {first}")
    if not (np.isfinite(one_logits).all() and np.isfinite(four_logits).all()):
        bad.append("non-finite prefill logits")
    return bad


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX's first device is "
              f"{dev.platform!r}", file=sys.stderr)
        return 2
    print(f"[smoke] device {dev.device_kind} x{len(jax.devices())}",
          flush=True)
    print(f"[smoke] compile cache {enable_compile_cache()}", flush=True)
    cfg = configs.get_config("qwen3-4b")
    st = Settings()
    t0 = time.perf_counter()
    bad = (run_mesh(cfg, st, args.seed) if args.chips == 4
           else run_one_chip(cfg, st, args.seed))
    print(f"[smoke] total {time.perf_counter() - t0:.3f} s", flush=True)
    if bad:
        for b in bad:
            print(f"chip_smoke: FAILED: {b}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
