"""chip_smoke.py's serving path, run on CPU at reduced size with
interpret-mode kernels, and the serving-dtype parameter storage it
relies on.

The script serves the same queue on the paged and the dense clustered
engine at one prefill chunk and fails on any first-token difference;
here the pair must agree on every token (the CPU pin), with absorb and
compaction both exercised.  The script itself refuses any device that
is not a TPU."""

import dataclasses
import importlib.util
import os
import pathlib
import subprocess
import sys
import warnings

import jax
import numpy as np
import pytest

from repro import configs
from repro.core import kv_compress
from repro.core.request_cluster import Request
from repro.models import transformer as tfm
from repro.runtime.server import Server, ServerConfig

ROOT = pathlib.Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
smoke = importlib.util.module_from_spec(_spec)
sys.modules["chip_smoke"] = smoke     # dataclasses resolve their module
_spec.loader.exec_module(smoke)

SMALL = smoke.Settings(
    batch=2, max_seq=128, chunk=8, block=4,
    kv=kv_compress.KVCompressConfig(n_clusters=4, iters=2, keep_recent=16,
                                    refresh_every=4),
    n_requests=4, prompt_min=8, prompt_max=48, max_new=8)


def test_paged_and_dense_engines_agree_at_reduced_size():
    cfg = configs.get_reduced("qwen3-4b")
    params = tfm.init_params_serving(jax.random.PRNGKey(0), cfg)
    reqs, prompts = smoke.make_requests(cfg, SMALL, 0)
    assert max(r.prompt_len for r in reqs) > SMALL.kv.keep_recent
    lines, bad, runs = smoke.smoke_one_chip(cfg, params, reqs, prompts,
                                            SMALL)
    assert bad == [], bad
    assert runs["paged"]["tokens"] == runs["dense"]["tokens"]
    for run in runs.values():
        assert run["stats"]["kv_absorbs"] > 0
        assert run["stats"]["kv_compactions"] > 0
        assert run["stats"]["logits_nonfinite"] == 0
    assert any("first tokens equal for 4/4" in ln for ln in lines), lines


def test_engine_steps_take_weights_as_arguments():
    """A jitted step that closed over the weights would embed every
    weight byte in its program as a constant: 8 GB per step program at
    qwen3-4b, which exhausts the host while compiling.  Serve with the
    capture warning set below the model's weight bytes."""
    cfg = configs.get_reduced("qwen3-4b")
    params = tfm.init_params_serving(jax.random.PRNGKey(0), cfg)
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    reqs, prompts = smoke.make_requests(cfg, SMALL, 1)
    was = jax.config.jax_captured_constants_warn_bytes
    jax.config.update("jax_captured_constants_warn_bytes", nbytes // 2)
    try:
        with warnings.catch_warnings(record=True) as seen:
            warnings.simplefilter("always")
            for paged in (True, False):
                Server(cfg, smoke.server_config(SMALL, paged=paged),
                       params).serve(reqs, prompts)
    finally:
        jax.config.update("jax_captured_constants_warn_bytes", was)
    captured = [str(w.message) for w in seen
                if "constants were captured" in str(w.message)]
    assert captured == [], captured


def test_script_refuses_a_non_tpu_device():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


@pytest.mark.parametrize("arch", ["qwen3-4b", "qwen2-moe-a2.7b",
                                  "recurrentgemma-9b", "mamba2-2.7b"])
def test_serving_dtype_storage_keeps_greedy_tokens(arch):
    """Weight matrices stored at cfg.dtype give the tokens f32 storage
    gives: compute casts every such matrix at use, and the ones it reads
    in f32 (MoE router, RG-LRU gates) stay f32."""
    cfg = dataclasses.replace(configs.get_reduced(arch), dtype="bfloat16")
    key = jax.random.PRNGKey(0)
    p32 = tfm.init_params(key, cfg)
    p16 = tfm.init_params_serving(key, cfg)
    for a, b in zip(jax.tree.leaves(p32), jax.tree.leaves(p16)):
        np.testing.assert_array_equal(np.asarray(a.astype(b.dtype)),
                                      np.asarray(b))
    assert p16["embed"]["table"].dtype == cfg.dtype
    assert p16["final_norm"]["scale"].dtype == np.float32
    rng = np.random.default_rng(0)
    reqs = [Request(i, n, 4) for i, n in enumerate([5, 11, 7])]
    prompts = {r.uid: rng.integers(0, cfg.vocab, r.prompt_len).astype(
        np.int32) for r in reqs}

    def tokens(params):
        srv = Server(cfg, ServerConfig(batch_size=2, max_seq=32,
                                       engine="static",
                                       use_clustered_batching=False), params)
        return {o.uid: o.tokens for o in srv.serve(reqs, prompts)}

    assert tokens(p16) == tokens(p32)
