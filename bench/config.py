"""Find a cell's pieces by name and turn its configuration file into the
program's objects.

Everything that belongs to one configuration, one traffic mix or one cell
lives in a data file of its own: ``bench/configs/<config>.json``,
``bench/traffic/<mix>.json`` and the cell's check limits
``bench/checks/<workload>.json``; what belongs to one architecture lives
in ``bench/arch/<model_type>.py`` (its contract is in
``bench/arch/__init__.py``).  The harness reads them by the names
``BENCHMARK.json`` and the configuration file give, so a new cell needs
new files and entries only.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
ARCH_DIR = BENCH_DIR / "arch"


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


def arch_for(conf: dict):
    """The architecture module of a configuration file: ``<ARCH_DIR>/<its
    model_type>.py``, loaded once per process (its jitted reference then
    compiles once).  Exits naming the path when there is none."""
    path = ARCH_DIR / f"{conf.get('model_type')}.py"
    if not path.is_file():
        raise SystemExit(f"configuration {conf.get('name')!r}: no "
                         f"architecture module {path} for its model_type")
    name = f"bench_arch_{path.stem}"
    mod = sys.modules.get(name)
    if mod is None or mod.__file__ != str(path):
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod     # dataclasses look their module up here
        spec.loader.exec_module(mod)
    return mod


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
