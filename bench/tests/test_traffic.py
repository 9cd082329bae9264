"""The traffic generator: same seed, same requests; stated medians and
clips; every burst the same multiset of sizes."""

import statistics

import numpy as np
import pytest

from bench import traffic

MIXES = ("code-burst", "chat-burst", "doc-qa-4k", "doc-qa-16k")


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_bursts(name):
    mix = traffic.load_mix(name)
    a = traffic.Generator(mix, 1000, 2**31 + 17)
    b = traffic.Generator(mix, 1000, 2**31 + 17)
    for _ in range(2):
        for ra, rb in zip(a.burst(), b.burst()):
            assert np.array_equal(ra.prompt, rb.prompt)
            assert (ra.n_out, ra.temperature, ra.sample_seed) == (
                rb.n_out, rb.temperature, rb.sample_seed)
    c = traffic.Generator(mix, 1000, 5).burst()
    assert any(not np.array_equal(x.prompt, y.prompt)
               for x, y in zip(traffic.Generator(mix, 1000, 6).burst(), c))


@pytest.mark.parametrize("name", MIXES)
def test_medians_clips_and_fixed_order_of_work(name):
    mix = traffic.load_mix(name)
    doc = mix.get("document", {}).get("tokens", 0)
    sizes = []
    for seed in (1, 2):
        gen = traffic.Generator(mix, 50_000, seed)
        for _ in range(2):
            reqs = gen.burst()
            assert len(reqs) == mix["burst_size"]
            sizes.append(([len(r.prompt) - doc for r in reqs],
                          [r.n_out for r in reqs]))
            for r in reqs:
                assert mix["prompt"]["min"] <= len(r.prompt) - doc <= mix["prompt"]["max"]
                assert mix["output"]["min"] <= r.n_out <= mix["output"]["max"]
                assert r.prompt.dtype == np.int32 and r.prompt.max() < 50_000
                if doc:
                    assert np.array_equal(r.prompt[:doc], gen.document)
            greedy = sum(r.greedy for r in reqs)
            assert greedy == -(-mix["burst_size"] // mix["greedy_every"])
    assert all(s == sizes[0] for s in sizes)  # every burst, every seed, in order
    prompts, outs = sizes[0]
    for spec, got in ((mix["prompt"], prompts), (mix["output"], outs)):
        med = statistics.median(got)
        assert abs(med - spec["median"]) <= 0.05 * spec["median"] + 1


def test_stratified_lengths_clip():
    spec = {"median": 384, "sigma": 0.9, "min": 32, "max": 2048}
    got = traffic.stratified_lengths(spec, 32)
    assert got == sorted(got)
    assert got[-1] == 2048 and got[0] > 32
    assert traffic.stratified_lengths(dict(spec, min=100), 32)[0] == 100
