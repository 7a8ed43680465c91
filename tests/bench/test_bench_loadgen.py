"""The load generator: every seed gets the same lengths per block in
another order, the greedy share is exact, and lengths stay in range."""

import pytest

import bench_cpu
from bench import loadgen

MIX = {"prompt_tokens": {"dist": "lognormal", "median": 256, "sigma": 0.5,
                         "min": 128, "max": 1024},
       "output_tokens": {"dist": "uniform", "min": 16, "max": 64},
       "greedy_share": 0.5, "sampled": {"temperature": 0.8, "top_p": 0.95}}


def take(mix, slots, seed, n):
    g = loadgen.requests(mix, slots=slots, seed=seed, vocab=1000)
    return [next(g) for _ in range(n)]


@pytest.mark.parametrize("block", [None, 4, 1])
def test_same_work_per_block_for_every_seed(block):
    mix = dict(MIX) if block is None else dict(MIX, block=block)
    b = block or 6
    a, z = take(mix, 6, 2 ** 31 + 1, 4 * b), take(mix, 6, 12345, 4 * b)
    for k in range(0, 4 * b, b):
        for field in (lambda r: len(r.prompt), lambda r: r.max_new_tokens,
                      lambda r: r.greedy):
            assert sorted(map(field, a[k:k + b])) == \
                sorted(map(field, z[k:k + b]))
    assert sum(r.greedy for r in a) == 2 * b
    assert all(128 <= len(r.prompt) <= 1024 and 16 <= r.max_new_tokens <= 64
               for r in a)
    assert [r.prompt for r in a] != [r.prompt for r in z]


def test_block_of_one_fixes_the_order():
    mix = dict(MIX, block=1)
    a, z = take(mix, 16, 1, 20), take(mix, 16, 2, 20)
    assert [(len(r.prompt), r.max_new_tokens, r.greedy) for r in a] == \
        [(len(r.prompt), r.max_new_tokens, r.greedy) for r in z]
    assert [r.greedy for r in a[:4]] == [False, True, False, True]


def test_sampled_requests_take_the_mix_settings():
    r = [x for x in take(MIX, 4, 3, 8) if not x.greedy][0]
    assert (r.temperature, r.top_p, r.top_k) == (0.8, 0.95, 0)


def test_quantile():
    d = {"dist": "uniform", "min": 16, "max": 64}
    assert loadgen.quantile(d, 0.0) == 16 and loadgen.quantile(d, 0.999) == 64
    ln = MIX["prompt_tokens"]
    assert loadgen.quantile(ln, 0.5) == 256


def _mean(dist, n=20000):
    return sum(loadgen.quantile(dist, (j + 0.5) / n) for j in range(n)) / n


@pytest.mark.parametrize("mix,key,stat,value", [
    ("decode", "prompt_tokens", "mean", 161.31),     # ShareGPT, vLLM Fig. 11
    ("decode", "output_tokens", "mean", 337.99),
    ("prefill", "prompt_tokens", "median", 1500),    # Splitwise, coding
    ("prefill", "output_tokens", "median", 13)])
def test_committed_mixes_match_their_sources(mix, key, stat, value):
    """Each committed mix's lengths keep the published statistic its
    file cites: the clipped mean or the median, within 1 %."""
    import json
    import os
    traffic = json.load(open(os.path.join(bench_cpu.ROOT, "bench",
                                          "traffic", f"{mix}.json")))
    dist = traffic[key]
    got = _mean(dist) if stat == "mean" else loadgen.quantile(dist, 0.5)
    assert got == pytest.approx(value, rel=0.01)
    assert f"{value} tokens" in traffic["source"]["lengths"]
