#!/usr/bin/env python3
"""Serving benchmark: one cell of ``BENCHMARK.json`` on the chip.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the cell's configuration with weights drawn on the device
from ``--seed``, turns on the persistent compilation cache, and serves one
warm-up job of the cell's own traffic (``loadgen.warm_job``: the window
jobs' prompts with shorter outputs), which builds every program the window
runs.  The window then serves one job after another, each a fresh seeded
queue handed to ``Server.serve`` at once, until ``--seconds`` have passed;
it ends with the job that crosses the mark.  Rates are taken over all jobs
and the whole window, tails over every request and every inter-token gap.
After the window a seeded sample of finished requests (the longest first,
``SAMPLE_TOKENS`` served tokens) is recomputed by the plain reference
(the configuration's ``bench/arch/<model_type>.py`` over
``bench/reference.py``), and the widest gap between a served token's
reference logit and the reference's best, against the cell's limit in
``bench/checks/<workload>.json``, decides ``correct``.

``--trace 1`` records a profiler trace of the window and reports the
cell's per-layer metrics instead of its end-to-end ones; each metric is
read by ``bench/metrics/<name>.py``.

The last line of standard output is one JSON object.  The run exits
non-zero and prints no result when JAX's first device is not a TPU or
there are fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))

import numpy as np  # noqa: E402

from bench import config, counts, loadgen, reference, trace_reduce  # noqa: E402
from bench import weights  # noqa: E402

OUT = ROOT / "bench" / "out"
SAMPLE_TOKENS = 512   # served tokens the reference recomputes per run


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


class CompileCounter:
    """Programs built in this process: backend compiles plus loads from
    the persistent cache."""

    def __init__(self):
        import jax.monitoring as mon
        self.n = 0
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.n += 1

    def _duration(self, name, _secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def serve_job(srv, reqs, prompts):
    """One job through the timed entry.  Returns its record."""
    from repro.core.request_cluster import Request

    t0 = time.perf_counter()
    outs = srv.serve([Request(u, p, o) for u, p, o in reqs], prompts)
    wall = time.perf_counter() - t0
    by_uid = {o.uid: o for o in outs}
    stats = dict(srv.last_stats)
    return {
        "wall_s": wall,
        "requests": [(u, p, o, list(by_uid[u].tokens) if u in by_uid else [],
                      by_uid[u].prefill_ms if u in by_uid else None,
                      bool(by_uid[u].shed) if u in by_uid else True)
                     for u, p, o in reqs],
        "prompts": prompts,
        "itl_s": list(srv.metrics.histogram("itl").samples),
        "stats": stats,
    }


def load_reader(name: str):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Context:
    """What a per-layer metric reader may read: the cell's model and
    memory settings, the window's jobs, every fed row of the window, the
    reduced trace and the chip's peaks."""

    def __init__(self, model, mem, jobs, window_s, trace, peaks):
        self.model, self.mem, self.jobs = model, mem, jobs
        self.window_s, self.trace, self.peaks = window_s, trace, peaks
        rows = []
        for job in jobs:
            for _, plen, _, toks, _, _ in job["requests"]:
                if toks:
                    ev, so = reference.schedule(plen, len(toks), mem)
                    rows.append(counts.request_rows(ev, so, mem.clusters,
                                                    plen, mem.chunk))
        self.rows = (np.concatenate(rows) if rows
                     else np.zeros((0, 4), np.int64))


def sample(jobs, seed):
    """A seeded sample of the window's finished requests, the longest
    (prompt plus output) first, until they hold ``SAMPLE_TOKENS`` served
    tokens: [(prompt tokens, served tokens)]."""
    done = [(job["prompts"][r[0]], r[3]) for job in jobs
            for r in job["requests"] if len(r[3]) == r[2] and not r[5]]
    if not done:
        return []
    longest = max(range(len(done)),
                  key=lambda i: len(done[i][0]) + len(done[i][1]))
    rest = [i for i in range(len(done)) if i != longest]
    rng = np.random.default_rng([int(seed) & ((1 << 64) - 1), 0xC0FFEE])
    pick, n = [longest], len(done[longest][1])
    for i in rng.permutation(rest):
        if n >= SAMPLE_TOKENS:
            break
        pick.append(int(i))
        n += len(done[int(i)][1])
    return [done[i] for i in pick]


def reference_logits(arch, params, prompt, served, mix, m, mem,
                     weight_quant=None):
    """The architecture's reference logits of one request fed its prompt
    and served tokens, at the mix's padded sizes."""
    pmax, omax = mix["prompt_len"]["max"], mix["output_len"]["max"]
    ins = reference.request_inputs(
        prompt, served, mem, max_len=pmax + omax, max_out=omax,
        n_events=reference.max_events(pmax, omax, mem))
    return np.asarray(arch.logits_at(params, *ins, m=m, mem=mem,
                                     weight_quant=weight_quant))


def widest_gap(refs, judged):
    """Widest gap, over the picked requests, between a judged token's
    reference logit and the reference's best at its position.  ``refs``
    are the reference's logits of each request, fed its prompt and the
    tokens it was served; ``judged`` are the tokens judged at those
    positions: the served ones, or for a control the ones it ranks
    first."""
    widest = None
    for ref, toks in zip(refs, judged):
        gaps = reference.served_gaps(ref, toks)
        widest = max(float(gaps.max()), widest if widest is not None
                     else -np.inf)
        log(f"check: {len(toks)} tokens: widest gap {float(gaps.max())}, "
            f"tokens at gap 0: {int((gaps == 0).sum())}")
    return widest


def is_correct(widest, failed: int, limit) -> bool:
    """Every request finished and no judged token lies further below the
    reference's best than the configuration's limit."""
    return (failed == 0 and widest is not None and limit is not None
            and widest <= limit)


def run(conf: dict, mix: dict, check: dict, *, workload: str, seed: int,
        seconds: float, trace: bool, e2e, per_layer, devices,
        t_start: float) -> dict:
    """Set up, serve the window, check, and return the result object."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    from repro.runtime.server import Server
    from repro.runtime.telemetry import TelemetryConfig

    dev = devices[0]
    compiles = CompileCounter()
    log(f"compile cache {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    arch = config.arch_for(conf)
    cfg = arch.program_config(conf)
    m, mem = arch.reference_model(conf), reference.memory(conf)
    vocab = conf["vocab_size"]

    t0 = time.perf_counter()
    params = weights.build(cfg, seed, conf["initializer_range"], arch)
    n_par, n_bytes = weights.param_count(params)
    log(f"{cfg.name}: {n_par} parameters, {n_bytes} bytes, built in "
        f"{time.perf_counter() - t0:.3f} s")
    tele = TelemetryConfig(jax_profiler=True) if trace else None
    srv = Server(cfg, config.server_config(conf, mix["slots"], telemetry=tele),
                 params)
    t1 = time.perf_counter()
    warm = serve_job(srv, *loadgen.warm_job(mix, vocab, seed))
    setup_s = time.perf_counter() - t_start
    log(f"warm-up {time.perf_counter() - t1:.3f} s (warm job "
        f"{warm['wall_s']:.3f} s); set-up {setup_s:.3f} s; "
        f"{compiles.n} programs built")

    tdir = OUT / "trace" / f"{workload}-{seed}"
    if trace:
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tdir), profiler_options=opts)
    c0 = compiles.n
    jobs = []
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
        w0 = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("bench_job"):
                jobs.append(serve_job(srv, *loadgen.job(mix, vocab, seed,
                                                        len(jobs))))
            if time.perf_counter() - w0 >= seconds:
                break
        window_s = time.perf_counter() - w0
    if trace:
        jax.profiler.stop_trace()
    in_window = compiles.n - c0
    for i, job in enumerate(jobs):
        s = job["stats"]
        log(f"job {i}: {len(job['requests'])} requests, "
            f"{s['gen_tokens']:.0f} tokens in {job['wall_s']:.3f} s, "
            f"{s['decode_steps']:.0f} launches, {s['prefill_chunks']:.0f} "
            f"prefill chunks, {s['kv_absorbs']:.0f} absorbs, "
            f"{s['kv_compactions']:.0f} compactions, "
            f"{s['logits_nonfinite']:.0f} non-finite logits")
    log(f"window {window_s:.3f} s, {len(jobs)} jobs, {in_window} programs "
        f"built inside the window")

    reqs = [r for job in jobs for r in job["requests"]]
    attempted = len(reqs)
    failed = sum(1 for r in reqs if r[5] or len(r[3]) != r[2])
    values = {
        "tokens_per_s": sum(len(r[3]) for r in reqs) / window_s,
        "ttft_p95_ms": float(np.percentile(
            [r[4] for r in reqs if r[4] is not None], 95)),
        "itl_p95_ms": float(np.percentile(
            [g for job in jobs for g in job["itl_s"]], 95)) * 1e3,
        "setup_s": setup_s,
    }
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}

    metrics = {}
    breakdown = None
    if trace:
        summary = None
        try:
            pd = jax.profiler.ProfileData.from_file(
                trace_reduce.find_xplane(str(tdir)))
            summary = trace_reduce.reduce(pd)
        except (FileNotFoundError, ValueError) as e:
            log(f"no device trace to read: {e}")
        shutil.rmtree(tdir, ignore_errors=True)
        if summary is not None:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
            breakdown = trace_reduce.breakdown(summary)
            for name, (sec, n) in sorted(summary.programs.items(),
                                         key=lambda kv: -kv[1][0])[:12]:
                log(f"program {name}: {sec:.6f} s in {n} launches")
        peaks = (counts.peaks_for(dev.device_kind) if summary is not None
                 else None)
        ctx = Context(m, mem, jobs, window_s, summary, peaks)
        for mdef in per_layer:
            v = load_reader(mdef["name"])(ctx)
            if v is not None:
                metrics[mdef["name"]] = {"value": float(v),
                                         "unit": mdef["unit"]}
    else:
        for mdef in e2e:
            metrics[mdef["name"]] = {"value": values[mdef["name"]],
                                     "unit": mdef["unit"]}
    log("end-to-end: " + ", ".join(f"{k} {v}" for k, v in values.items()))

    del srv, warm
    gc.collect()
    limit = check["max_logit_gap"]
    picked = sample(jobs, seed)
    widest = widest_gap([reference_logits(arch, params, p, t, mix, m, mem)
                         for p, t in picked], [t for _, t in picked])
    log(f"check: {len(picked)} requests, "
        f"{sum(len(t) for _, t in picked)} served tokens recomputed")
    correct = is_correct(widest, failed, limit)
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = {
        "logit_gap": {"value": widest, "limit": limit},
        "incomplete_requests": {"value": failed, "limit": 0},
    }
    print(f"check: logit_gap {widest} limit {limit}", file=sys.stderr)
    print(f"check: incomplete_requests {failed} limit 0", file=sys.stderr)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = config.load_benchmark()
    cell = config.find_cell(bench, args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} TPU chip(s); "
              f"JAX's first device is {devices[0].platform!r} "
              f"x{len(devices)}", file=sys.stderr)
        return 2
    log(f"device {devices[0].device_kind} x{len(devices)}")
    result = run(config.load_config(cell["config"]),
                 config.load_mix(cell["traffic"]),
                 config.load_check(args.workload), workload=args.workload,
                 seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
                 e2e=bench["end_to_end"], per_layer=bench["per_layer"],
                 devices=devices[:cell["chips"]], t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
