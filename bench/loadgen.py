"""One general generator of jobs from a traffic mix's parameters.

A job is one queue handed to ``Server.serve`` at once (offline batch
traffic).  Lengths are stratified: every job takes the same
``requests_per_job`` quantiles of the prompt- and output-length
distributions, paired and queued in one fixed order.  The seed and the
job's index draw only the token ids, so every job of every seed is the
same work: the engine's admissions, launches and compactions follow the
lengths alone, and the set-up job builds every program a window job runs.
"""

from __future__ import annotations

import math

import numpy as np

WARM_JOB = (1 << 32) - 1   # job index of the set-up job; windows count up from 0
SCHEDULE = 0x5EED          # draws the one pairing and order of the lengths
# what a mix may say; anything else (prefix sharing, sampling, arrivals)
# is not generated here and is refused rather than ignored
MIX_KEYS = {"name", "why", "slots", "requests_per_job", "prompt_len",
            "output_len", "warm_output_len"}


def _rng(seed: int, job: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & ((1 << 64) - 1), int(job)])


def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """The ``n`` mid-quantiles of a length distribution, as integers."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = spec["min"], spec["max"]
    if spec["dist"] == "loguniform":
        x = np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    elif spec["dist"] == "uniform":
        x = lo + u * (hi - lo)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def job(mix: dict, vocab: int, seed: int, index: int):
    """(requests as (uid, prompt_len, max_new), {uid: prompt tokens}),
    greedy, with no shared prefixes."""
    unknown = set(mix) - MIX_KEYS
    if unknown:
        raise ValueError(f"mix {mix.get('name')!r}: {sorted(unknown)} "
                         f"not implemented by this generator")
    n = mix["requests_per_job"]
    order = np.random.default_rng(SCHEDULE)
    plens = quantile_lengths(mix["prompt_len"], n)[order.permutation(n)]
    olens = quantile_lengths(mix["output_len"], n)[order.permutation(n)]
    rng = _rng(seed, index)
    reqs = [(i, int(p), int(o)) for i, (p, o) in enumerate(zip(plens, olens))]
    prompts = {uid: rng.integers(0, vocab, p).astype(np.int32)
               for uid, p, _ in reqs}
    return reqs, prompts


def warm_job(mix: dict, vocab: int, seed: int):
    """The set-up job: a window job's prompts, in its order, with token
    ids the window never uses, and every output ``warm_output_len`` tokens
    long where the mix gives that (a whole window job where it does not).
    The mix sets it short enough to serve quickly and long enough that the
    job still drives every packed-step shape a window job drives
    (``bench/tests/test_loadgen.py`` checks that on a small model)."""
    reqs, prompts = job(mix, vocab, seed, WARM_JOB)
    n = mix.get("warm_output_len")
    if n is not None:
        reqs = [(u, p, int(n)) for u, p, _ in reqs]
    return reqs, prompts
