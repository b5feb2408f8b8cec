"""The harness end to end on the CPU at a small size: the chip check
skipped, the rest of a run driven, healthy and with the timed path
broken underneath."""

import json
import time
from pathlib import Path

import jax
import pytest

from bench import calibrate, config, reference, weights
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


def drive(trace=False, per_layer=()):
    return harness.run(TINY, MIX, CHECK, workload="tiny", seed=SEED,
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

    cfg = config.model_config(TINY)
    m, mem = reference.from_config(TINY)
    srv = Server(cfg, config.server_config(TINY, MIX["slots"]),
                 weights.build(cfg, SEED, TINY["initializer_range"]))
    r = calibrate.readings(srv, cfg, TINY, MIX, LIMIT, SEED, m, mem)
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
