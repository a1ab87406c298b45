"""Traffic generator, order statistics and the table of peaks."""
import collections

import numpy as np
import pytest

from cbench import peaks, stats, traffic

BIG_SEED = 2**31 + 977


def draw(mix, seed, n, vocab):
    s = traffic.Stream(mix, seed, vocab)
    return [s[i] for i in range(n)]


def test_percentile_nearest_rank():
    vals = list(range(1, 101))
    assert stats.percentile(vals, 95) == 95.0
    assert stats.percentile(vals, 99.9) == 100.0
    assert stats.percentile([3.0], 50) == 3.0
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_peaks_known_chip_and_refusal():
    p = peaks.chip_peaks("TPU v5 lite")
    assert (p.bf16_flops, p.hbm_bw) == (197e12, 819e9)
    with pytest.raises(ValueError):
        peaks.chip_peaks("TPU v9 imaginary")


MIX = {"loop": "closed", "clients": 4, "max_slots": 4, "max_len": 1024,
       "prompt_len": {"values": [128, 256, 384, 512],
                      "weights": [0.4, 0.3, 0.2, 0.1]},
       "output_len": {"uniform": [128, 384]}}


def test_draws_follow_the_seed_and_the_mix():
    a = draw(MIX, BIG_SEED, 2000, 50304)
    b = draw(MIX, BIG_SEED, 2000, 50304)
    c = draw(MIX, BIG_SEED + 1, 2000, 50304)
    lens = lambda ds: [len(d.prompt) for d in ds]
    outs = lambda ds: [d.max_new for d in ds]
    # the same seed gives the same requests; another seed other sizes
    assert lens(a) == lens(b) and outs(a) == outs(b)
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    assert lens(a) != lens(c) and outs(a) != outs(c)
    # sizes are independent draws from the stated shares and range
    share = collections.Counter(lens(a))
    for v, w in zip(MIX["prompt_len"]["values"], MIX["prompt_len"]["weights"]):
        assert share[v] / 2000 == pytest.approx(w, abs=0.04)
    assert min(outs(a)) == 128 and max(outs(a)) == 384
    assert np.mean(outs(a)) == pytest.approx(256, rel=0.03)
    assert all(d.prompt.max() < 50304 and d.prompt.dtype == np.int32 for d in a)


def test_open_loop_arrivals_are_poisson():
    mix = dict(MIX, loop="open", rate=5.0)
    ds = draw(mix, BIG_SEED, 4000, 1000)
    t = np.array([d.offset_s for d in ds])
    gaps = np.diff(np.concatenate([[0.0], t]))
    assert (gaps > 0).all()
    # exponential gaps: mean 1/rate, as wide as their mean
    assert gaps.mean() == pytest.approx(0.2, rel=0.05)
    assert gaps.std() == pytest.approx(0.2, rel=0.08)
    # counts in 2 s windows spread as a Poisson count does (mean = var = 10)
    counts = np.bincount((t // 2.0).astype(int))[:-1]
    assert counts.var() == pytest.approx(counts.mean(), rel=0.25)
    # another seed, another arrival pattern
    other = [d.offset_s for d in draw(mix, BIG_SEED + 1, 20, 1000)]
    assert other != list(t[:20])
    assert traffic.sizes(mix["prompt_len"]) == [128, 256, 384, 512]


def test_weights_must_sum_to_one():
    with pytest.raises(ValueError):
        draw(dict(MIX, prompt_len={"values": [1, 2], "weights": [0.5, 0.6]}), 1, 4, 10)
