"""The traffic generator is a pure function of the seed, and every seed
offers the same sizes."""

import numpy as np
import pytest

import bench_paths  # noqa: F401
from benchmark.lib import traffic as tr
from benchmark.lib.registry import Registry

SPEC = {
    "n_samples": 8, "prompt_len": {"lo": 64, "hi": 256}, "prompt_strata": 8,
    "output_len": {"dist": "lognormal", "median": 256, "sigma": 0.7, "lo": 16, "hi": 1024},
    "first_cohort_min_scale": 0.1,
}
BIG_SEED = 2**31 + 12345  # the driver's seeds exceed 32 signed bits


def test_output_lengths_are_the_mid_quantiles():
    # 256 * exp(0.7 * z) at z = Phi^-1((i + .5) / 8): -1.534, -0.887, -0.489,
    # -0.157 and their mirror images, worked by hand
    assert tr.output_lengths(SPEC["output_len"], 8) == [87, 138, 182, 229, 286, 360, 476, 749]
    assert tr.prompt_lengths(SPEC["prompt_len"], 8) == [76, 100, 124, 148, 172, 196, 220, 244]
    clipped = tr.output_lengths(dict(SPEC["output_len"], lo=100, hi=400), 8)
    assert min(clipped) == 100 and max(clipped) == 400


def _groups(seed, n=16):
    t = tr.Traffic(SPEC, 151936, seed)
    return [t.group(i) for i in range(n)]


@pytest.mark.parametrize("seed", [0, 7, BIG_SEED])
def test_same_seed_same_requests(seed):
    a, b = _groups(seed), _groups(seed)
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt) and x.output_lens == y.output_lens


def test_another_seed_other_requests_same_sizes():
    a, b = _groups(1), _groups(BIG_SEED)
    assert any(not np.array_equal(x.prompt[:32], y.prompt[:32]) for x, y in zip(a, b))
    assert [g.output_lens for g in a] != [g.output_lens for g in b]  # other order
    for groups in (a, b):
        # every group holds the whole set of output lengths; every cycle of
        # 8 groups the whole set of prompt lengths
        assert all(sorted(g.output_lens) == tr.output_lengths(SPEC["output_len"], 8) for g in groups)
        for c in (0, 8):
            assert sorted(len(g.prompt) for g in groups[c:c + 8]) == tr.prompt_lengths(SPEC["prompt_len"], 8)
    assert all(1 <= g.prompt.min() and g.prompt.max() < 151936 for g in a)


@pytest.mark.parametrize("seed", [3, BIG_SEED])
def test_an_epoch_holds_the_whole_distribution_tail_and_clip_included(seed):
    spec = dict(SPEC, epoch_groups=32, prompt_strata=32)
    population = tr.output_lengths(spec["output_len"], 256)
    # Phi^-1(0.5/256) = -2.886: 256 * exp(-2.020) = 34; P(x > 1024) = 1 - Phi(ln 4 / 0.7) = 2.4%: six of 256
    assert population[0] == 34 and population[-6:] == [1024] * 6 and population[-7] < 1024
    t = tr.Traffic(spec, 151936, seed)
    for epoch in (0, 1):
        groups = [t.group(i) for i in range(32 * epoch, 32 * epoch + 32)]
        assert sorted(n for g in groups for n in g.output_lens) == population
        # one length from each eighth of the distribution in every group
        for g in groups:
            assert all(n in population[32 * j:32 * j + 32] for j, n in enumerate(sorted(g.output_lens)))
        assert sorted(len(g.prompt) for g in groups) == tr.prompt_lengths(spec["prompt_len"], 32)
    other = tr.Traffic(spec, 151936, seed + 1)
    assert [max(t.group(i).output_lens) for i in range(32)] != [max(other.group(i).output_lens) for i in range(32)]


def test_scaled_groups_and_the_first_cohorts_scales():
    t = tr.Traffic(SPEC, 151936, 3)
    plain, short = t.group(2), t.group(2, scale=0.1)
    assert (plain.prompt == short.prompt).all()
    assert short.output_lens == [max(16, int(n * 0.1)) for n in plain.output_lens]
    scales = t.cohort_scales(4)
    # the mid-quantiles of uniform [0.1, 1] in seeded order; the same set for every seed
    assert sorted(scales) == pytest.approx([0.2125, 0.4375, 0.6625, 0.8875])
    assert sorted(tr.Traffic(SPEC, 151936, BIG_SEED).cohort_scales(4)) == pytest.approx(sorted(scales))
    assert t.cohort_scales(4) == scales


@pytest.mark.parametrize("seed", [5, BIG_SEED])
def test_train_batches_same_seed_same_batch_and_fixed_shape(seed):
    t = tr.Traffic(SPEC, 151936, seed)
    b0, again, b1 = t.train_batch(0, 8), t.train_batch(0, 8), t.train_batch(1, 8)
    assert all(np.array_equal(b0[k], again[k]) for k in b0)
    assert not np.array_equal(b0["input_ids"], b1["input_ids"])
    # the multiset of lengths, and so every padded shape, is the same for
    # every batch of every seed: 8 x (sum of outputs) + 8 x (sum of prompts)
    want = 8 * sum(tr.output_lengths(SPEC["output_len"], 8)) + 8 * sum(tr.prompt_lengths(SPEC["prompt_len"], 8))
    assert want == 30296
    for b in (b0, b1):
        assert sum(tr.batch_lengths(b)) == want
        assert sorted(tr.batch_lengths(b)) == sorted(tr.batch_lengths(tr.Traffic(SPEC, 151936, 99).train_batch(3, 8)))
        n_in = b["begin_of_answer"]
        assert (b["loss_mask"].sum(1) + n_in == b["attention_mask"].sum(1)).all()
        assert (b["versions"][b["loss_mask"] > 0] == 0).all()


def test_every_traffic_file_of_the_benchmark_loads_and_generates():
    reg = Registry()
    for w in reg.bench["workloads"]:
        cell = reg.cell(w["name"])
        t = tr.Traffic(cell["traffic_file"], cell["config_file"]["vocab_size"], BIG_SEED)
        g = t.group(0)
        ctx = cell["experiment"].get("decode", {}).get("context_length")
        if ctx:  # every request fits the cell's context
            assert len(g.prompt) + max(g.output_lens) <= ctx
            assert cell["traffic_file"]["prompt_len"]["hi"] + cell["traffic_file"]["output_len"]["hi"] <= ctx
