#!/usr/bin/env python3
"""Readings that set the limit of the correctness check, on the chip.

    python3 bench/calibrate.py --workload <name> --seeds 11 12 ... [--out F]

In one process, for every seed: weights drawn from the seed, the
window's first job served through the timed entry, the same sample of
finished requests a run compares, and for each of them

* the program's reading: the widest gap between a served token's
  reference logit and the reference's best;
* the control's readings: the reference with every weight matrix rounded
  one precision step below the configured one (int8 and fp8 e4m3 per
  output channel for bf16), put in the program's place.  At each position
  of the same prompts and served tokens the token the control ranks first
  is judged by the harness's own comparison (``run.widest_gap``,
  ``run.is_correct``), so it needs no decode of its own.

The benchmark's own runs never run this.  It prints one JSON line per seed
and writes all of them to ``--out``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))

import numpy as np  # noqa: E402

from bench import config, loadgen, reference, weights  # noqa: E402
from bench import run as harness  # noqa: E402

# one precision step below what a configuration states
CONTROLS = {"bfloat16": ("int8", "fp8"), "float32": ("bf16",)}


def readings(srv, arch, conf, mix, limit, seed):
    """The program's and the controls' widest gaps on one seed, and the
    harness's verdict on each under ``limit``."""
    controls = CONTROLS[conf["serving"]["dtype"]]
    m, mem = arch.reference_model(conf), reference.memory(conf)
    srv.params = None          # free the last seed's weights first
    params = weights.build(arch.program_config(conf), seed,
                           conf["initializer_range"], arch)
    srv.params = params
    job = harness.serve_job(srv, *loadgen.job(mix, conf["vocab_size"], seed,
                                              0))
    s = job["stats"]
    failed = sum(1 for r in job["requests"] if r[5] or len(r[3]) != r[2])
    picked = harness.sample([job], seed)
    served = [t for _, t in picked]
    refs = [harness.reference_logits(arch, params, p, t, mix, m, mem)
            for p, t in picked]
    out = {"seed": seed, "job_s": job["wall_s"],
           "absorbs": s["kv_absorbs"], "compactions": s["kv_compactions"],
           "served_tokens": sum(len(t) for t in served),
           "program": harness.widest_gap(refs, served),
           "program_mean": mean_gap(refs, served)}
    out["program_correct"] = harness.is_correct(out["program"], failed,
                                                limit)
    for c in controls:
        firsts = [np.asarray(harness.reference_logits(
            arch, params, p, t, mix, m, mem,
            weight_quant=c))[:len(t)].argmax(-1)
            for p, t in picked]
        out[c] = harness.widest_gap(refs, firsts)
        out[f"{c}_mean"] = mean_gap(refs, firsts)
        out[f"{c}_correct"] = harness.is_correct(out[c], 0, limit)
        out[f"{c}_off_share"] = float(np.mean(np.concatenate(
            [f != np.asarray(t) for f, t in zip(firsts, served)])))
    return out


def mean_gap(refs, judged) -> float:
    """Mean gap of the judged tokens below the reference's best: printed
    beside the widest gap, to show how far the two readings lie apart."""
    return float(np.mean(np.concatenate(
        [reference.served_gaps(r, t) for r, t in zip(refs, judged)])))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    bench = config.load_benchmark()
    cell = config.find_cell(bench, args.workload)
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    from repro.runtime.server import Server

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"calibrate: needs a TPU, found {dev.platform!r}",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    conf = config.load_config(cell["config"])
    mix = config.load_mix(cell["traffic"])
    arch = config.arch_for(conf)
    cfg = arch.program_config(conf)
    limit = config.load_check(args.workload)["max_logit_gap"]
    srv = Server(cfg, config.server_config(conf, mix["slots"]),
                 weights.build(cfg, args.seeds[0],
                               conf["initializer_range"], arch))
    rows = []
    for seed in args.seeds:
        t0 = time.perf_counter()
        r = readings(srv, arch, conf, mix, limit, seed)
        r["seconds"] = time.perf_counter() - t0
        rows.append(r)
        print(json.dumps(r), flush=True)
    summary = {"workload": args.workload, "device": dev.device_kind,
               "program_max": max(r["program"] for r in rows),
               **{f"{c}_min": min(r[c] for r in rows)
                  for c in CONTROLS[conf["serving"]["dtype"]]}}
    print(json.dumps(summary), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps({"rows": rows,
                                              "summary": summary}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
