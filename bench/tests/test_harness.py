"""The harness end to end on the CPU at a small size: the chip check
skipped, the rest of a run driven, healthy and with the timed path
broken underneath."""

import json
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import calibrate, config, counts, weights
from bench import run as harness

HERE = Path(__file__).resolve().parent
TINY = json.loads((HERE / "tiny.json").read_text())
MIX = json.loads((HERE / "tiny_mix.json").read_text())
CHECK = {"max_logit_gap": 0.001}
LIMIT = CHECK["max_logit_gap"]
E2E = config.load_benchmark()["end_to_end"]
SEED = 2**33 + 5


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    from repro.launch import compile_cache
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "off")


def drive(trace=False, per_layer=(), conf=TINY):
    return harness.run(conf, MIX, CHECK, workload="tiny", seed=SEED,
                       seconds=0.1, trace=trace, e2e=E2E,
                       per_layer=list(per_layer), devices=jax.devices(),
                       t_start=time.perf_counter())


def test_healthy_run_is_correct(capsys):
    res = drive()
    assert res["correct"] is True
    assert list(res)[-1] == "check"
    assert res["check"]["logit_gap"]["value"] <= LIMIT
    assert res["failed"] == 0 and res["attempted"] == MIX["requests_per_job"]
    assert set(res["metrics"]) == {m["name"] for m in E2E}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    err = capsys.readouterr().err.strip().splitlines()
    assert err[-2].startswith("check: logit_gap")
    assert err[-1] == "check: incomplete_requests 0 limit 0"


def test_token_altered_where_produced_is_caught(monkeypatch):
    from repro.runtime import server
    real = server._greedy
    calls = [0]

    def altered(logits):
        nxt, bad = real(logits)
        calls[0] += 1
        if calls[0] % 7 == 0:
            nxt = (nxt + 1) % TINY["vocab_size"]
        return nxt, bad

    monkeypatch.setattr(server, "_greedy", altered)
    res = drive()
    assert res["correct"] is False
    assert res["check"]["logit_gap"]["value"] > LIMIT


@pytest.mark.parametrize("step", ["_absorb_paged_impl",
                                  "_compact_paged_impl"])
def test_memory_step_returning_its_state_unchanged_is_caught(monkeypatch,
                                                              step):
    """Absorb or compaction that hands back the cache it was given."""
    from repro.runtime.server import Server
    monkeypatch.setattr(Server, step, lambda self, cache, *a: cache)
    res = drive()
    assert res["correct"] is False
    assert res["check"]["logit_gap"]["value"] > LIMIT


def test_control_fails_the_limit():
    """The reference one precision step below the configuration's, put in
    the program's place, is judged not correct by the harness's own
    comparison; the program is judged correct."""
    from repro.runtime.server import Server

    arch = config.arch_for(TINY)
    cfg = arch.program_config(TINY)
    srv = Server(cfg, config.server_config(TINY, MIX["slots"]),
                 weights.build(cfg, SEED, TINY["initializer_range"], arch))
    r = calibrate.readings(srv, arch, TINY, MIX, LIMIT, SEED)
    assert r["program_correct"] is True and r["bf16_correct"] is False
    assert r["program"] <= LIMIT < r["bf16"]


def test_trace_run_reports_what_it_can_read():
    bench = config.load_benchmark()
    res = drive(trace=True, per_layer=bench["per_layer"])
    assert res["correct"] is True
    names = {m["name"] for m in bench["per_layer"]}
    assert set(res["metrics"]) <= names
    # counters need no device trace; trace-read metrics stay silent on CPU
    assert {"launch_pad_frac", "kv_compactions_per_ktok"} <= set(res["metrics"])
    assert "device_idle_share" not in res["metrics"]


def test_no_chip_no_result(capsys):
    assert harness.main(["--workload", "qwen3-4b.chat_short", "--seed", "1",
                         "--seconds", "1", "--trace", "0"]) != 0
    assert capsys.readouterr().out == ""


# an architecture brought by files alone: qwen3's forward under a made-up
# model_type, with counts and a leaf rule of its own
PLUGIN = """
import dataclasses

from bench.arch import qwen3
from bench.arch.qwen3 import logits_at, program_config

MATMUL_PARAMS = 123_456_789
ATTN_LAYERS = 2
leaf_rules = {"q_norm": "zeros"}


def reference_model(conf):
    return dataclasses.replace(qwen3.reference_model(conf),
                               matmul_params=MATMUL_PARAMS,
                               attn_layers=ATTN_LAYERS)
"""


def _bench_files():
    return {p: p.read_bytes() for p in sorted(config.BENCH_DIR.rglob("*"))
            if p.is_file() and p.suffix in (".py", ".json")}


def test_architecture_module_is_found_by_file(tmp_path, monkeypatch):
    """``harness.run`` takes the program's configuration, the reference,
    the counts and the leaf rules from the module the configuration's
    ``model_type`` names, in a directory no bench file lists."""
    (tmp_path / "toy_hybrid.py").write_text(PLUGIN)
    monkeypatch.setattr(config, "ARCH_DIR", tmp_path)
    before = _bench_files()
    built, contexts = [], []
    real_build = weights.build

    def build(*a):
        built.append(real_build(*a))
        return built[-1]

    class Context(harness.Context):
        def __init__(self, *a):
            super().__init__(*a)
            contexts.append(self)

    monkeypatch.setattr(weights, "build", build)
    monkeypatch.setattr(harness, "Context", Context)
    conf = {**TINY, "model_type": "toy_hybrid"}
    res = drive(trace=True, per_layer=config.load_benchmark()["per_layer"],
                conf=conf)
    assert res["correct"] is True

    layers = built[0]["scan"]["sub0"]["attn"]
    assert not np.asarray(layers["q_norm"]).any()           # the module's rule
    assert (np.asarray(layers["k_norm"]) == 1).all()        # the shared rule
    ctx, = contexts
    assert (ctx.model.matmul_params, ctx.model.attn_layers) == (123_456_789, 2)
    real = int((ctx.rows[:, 0] > 0).sum())
    flops_one, _ = counts.paged_decode_work(ctx.rows, ctx.model)
    per_layer = (4.0 * TINY["num_attention_heads"] * TINY["head_dim"]
                 * counts.attended(ctx.rows).sum())
    assert flops_one == 2 * per_layer
    assert counts.step_flops(ctx.rows, ctx.model) == \
        2.0 * 123_456_789 * real + 2 * per_layer
    assert _bench_files() == before


def test_unknown_model_type_exits_naming_the_path():
    with pytest.raises(SystemExit) as e:
        config.arch_for({**TINY, "model_type": "no_such_arch"})
    assert str(config.ARCH_DIR / "no_such_arch.py") in str(e.value)
