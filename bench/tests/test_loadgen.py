"""The stratified job generator."""

import numpy as np
import pytest

from bench import config, loadgen

MIX = config.load_mix("decode_long")
RING_HEADROOM = 128 - 16      # keep_recent - refresh_every of the cells


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 2**40 + 3])
def test_a_seed_reproduces_its_queue(seed):
    a_reqs, a_prompts = loadgen.job(MIX, 1000, seed, 3)
    b_reqs, b_prompts = loadgen.job(MIX, 1000, seed, 3)
    assert a_reqs == b_reqs
    assert all(np.array_equal(a_prompts[u], b_prompts[u]) for u in a_prompts)


def test_seeds_and_jobs_share_the_schedule_not_the_tokens():
    jobs = [loadgen.job(MIX, 1000, s, j)
            for s, j in ((1, 0), (2, 0), (1, 5), (2**35, loadgen.WARM_JOB))]
    assert len({tuple(reqs) for reqs, _ in jobs}) == 1
    lens = [p for _, p, _ in jobs[0][0]]
    assert lens != sorted(lens) and lens != sorted(lens, reverse=True)
    firsts = {tuple(prompts[0][:8]) for _, prompts in jobs}
    assert len(firsts) == len(jobs)          # token ids differ


def test_lengths_span_the_mix():
    reqs, prompts = loadgen.job(MIX, 151936, 5, 0)
    assert len(reqs) == MIX["requests_per_job"]
    plens = [p for _, p, _ in reqs]
    olens = [o for _, _, o in reqs]
    assert min(plens) > RING_HEADROOM        # every prompt reaches absorb
    assert MIX["prompt_len"]["min"] <= min(plens)
    assert max(plens) <= MIX["prompt_len"]["max"]
    assert MIX["output_len"]["min"] <= min(olens)
    assert max(olens) <= MIX["output_len"]["max"]
    assert all(len(prompts[u]) == p for u, p, _ in reqs)
    assert all(int(prompts[u].max()) < 151936 for u in prompts)
    assert 10_500 < sum(plens) < 11_700 and 4_300 < sum(olens) < 4_900


def test_a_mix_asking_for_what_is_not_generated_is_refused():
    with pytest.raises(ValueError, match="prefix_share"):
        loadgen.job({**MIX, "prefix_share": 0.5}, 1000, 1, 0)


@pytest.mark.parametrize("cell", config.load_benchmark()["workloads"],
                         ids=lambda c: c["name"])
def test_warm_job_drives_every_shape_a_window_job_does(cell):
    """The engine's launches follow the lengths alone, so a small model
    under the cell's serving settings shows which packed-step shapes (rows,
    width) each job compiles: the warm-up job has to reach every one."""
    from repro.core.request_cluster import Request
    from repro.runtime.server import Server

    from bench import weights

    conf = dict(config.load_config(cell["config"]), hidden_size=64,
                intermediate_size=128, num_hidden_layers=1,
                num_attention_heads=2, num_key_value_heads=1, head_dim=32,
                vocab_size=256)
    conf["serving"] = dict(conf["serving"], dtype="float32")
    mix = config.load_mix(cell["traffic"])
    arch = config.arch_for(conf)
    cfg = arch.program_config(conf)
    srv = Server(cfg, config.server_config(conf, mix["slots"]),
                 weights.build(cfg, 1, 0.02, arch))
    seen, real = set(), srv._decode_packed

    def packed(*a):
        seen.add((int(a[2].shape[0]), a[8]))
        return real(*a)

    srv._decode_packed = packed
    shapes = []
    for reqs, prompts in (loadgen.job(mix, 256, 3, 0),
                          loadgen.warm_job(mix, 256, 3)):
        seen.clear()
        srv.serve([Request(u, p, o) for u, p, o in reqs], prompts)
        shapes.append(set(seen))
    assert shapes[1] == shapes[0]
