"""Find a cell's pieces by name and turn its configuration file into the
program's objects.

Everything that belongs to one configuration, one traffic mix or one cell
lives in a data file of its own: ``bench/configs/<config>.json``,
``bench/traffic/<mix>.json`` and the cell's check limits
``bench/checks/<workload>.json``.  The harness reads them by the names
``BENCHMARK.json`` gives, so a new cell needs new files and entries only.
"""

from __future__ import annotations

import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(bench: dict, workload: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise SystemExit(f"no workload named {workload!r} in BENCHMARK.json")


def load_config(name: str) -> dict:
    return json.loads((BENCH_DIR / "configs" / f"{name}.json").read_text())


def load_mix(name: str) -> dict:
    return json.loads((BENCH_DIR / "traffic" / f"{name}.json").read_text())


def load_check(workload: str) -> dict:
    """The limits of a cell's correctness check, set from readings on the
    chip: ``bench/checks/<workload>.json``."""
    return json.loads((BENCH_DIR / "checks" / f"{workload}.json").read_text())


def model_config(conf: dict):
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


def server_config(conf: dict, slots: int, *, telemetry=None):
    """``ServerConfig`` of the paged clustered continuous engine."""
    from repro.core.kv_compress import KVCompressConfig
    from repro.runtime.kv_pool import PagedKVConfig
    from repro.runtime.server import ServerConfig

    s = conf["serving"]
    kv = KVCompressConfig(n_clusters=s["kv_clusters"],
                          iters=s["kmedians_iters"], bits=s["kmedians_bits"],
                          keep_recent=s["kv_keep_recent"],
                          refresh_every=s["kv_refresh_every"])
    return ServerConfig(batch_size=slots, max_seq=s["max_seq"],
                        engine="continuous",
                        prefill_chunk=s["prefill_chunk"],
                        kv_compress=kv,
                        paged=PagedKVConfig(block_size=s["block_size"]),
                        telemetry=telemetry)
