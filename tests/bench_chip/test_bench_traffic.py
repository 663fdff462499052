"""The seeded traffic generator: same seed, same stream; every seed the
same work in each stretch, at Poisson times; lengths within their
bounds."""
import json
import statistics
from collections import Counter

import numpy as np

from benchpath import BENCH
from benchlib import traffic as gen

CHAT = json.loads((BENCH / "traffic" / "chat.json").read_text())
TRAIN = json.loads((BENCH / "traffic" / "train.json").read_text())
BIG_SEED = 2 ** 31 + 12345
STRETCHES = (CHAT["warmup_s"], 40.0, CHAT["drain_limit_s"])


def take(seed, stretches=STRETCHES, spec=CHAT):
    return gen.open_loop(spec, seed, stretches)


def in_stretch(reqs, k, stretches=STRETCHES):
    lo = sum(stretches[:k])
    return [a for a in reqs if lo <= a.due_s < lo + stretches[k]]


def test_same_seed_same_stream():
    assert take(BIG_SEED) == take(BIG_SEED)
    assert take(BIG_SEED) != take(BIG_SEED + 1)


def test_lengths_within_bounds_and_arrivals_increase():
    reqs = take(7)
    p, o = CHAT["prompt"], CHAT["output"]
    assert all(p["min"] <= a.prompt_len <= p["max"] for a in reqs)
    assert all(o["min"] <= a.output_len <= o["max"] for a in reqs)
    assert all(b.due_s >= a.due_s for a, b in zip(reqs, reqs[1:]))
    assert reqs[-1].due_s < sum(STRETCHES)
    assert {a.client for a in reqs} == set(range(CHAT["clients"]))


def test_every_seed_gets_the_same_work_per_block():
    # each stretch (warm-up, window, drain) holds rate x length requests
    # and the same lengths for every seed
    a, b = take(1), take(BIG_SEED)
    for k, length in enumerate(STRETCHES):
        sa, sb = in_stretch(a, k), in_stretch(b, k)
        assert len(sa) == len(sb) == round(CHAT["rate_per_s"] * length)
        assert Counter(x.prompt_len for x in sa) == \
            Counter(x.prompt_len for x in sb)
        assert Counter(x.output_len for x in sa) == \
            Counter(x.output_len for x in sb)
    assert [x.due_s for x in in_stretch(a, 1)] != \
        [x.due_s for x in in_stretch(b, 1)]


def test_arrivals_within_a_stretch_are_poisson():
    # the count in a quarter of the window swings from seed to seed as a
    # Poisson stream's does, given the window's count (binomial), and the
    # gaps are exponential (coefficient of variation near 1)
    window = (0.0, 40.0, 0.0)
    n = round(CHAT["rate_per_s"] * 40.0)
    counts, cvs = [], []
    for seed in range(200):
        due = [a.due_s for a in take(BIG_SEED + seed, window)]
        counts.append(sum(1 for t in due if t < 10.0))
        gaps = np.diff(due)
        cvs.append(gaps.std() / gaps.mean())
    var = statistics.pvariance(counts)
    assert 0.7 * n * 0.25 * 0.75 < var < 1.3 * n * 0.25 * 0.75
    assert abs(statistics.mean(counts) - n / 4) < 1.0
    assert 0.9 < statistics.mean(cvs) < 1.1


def test_lognormal_quantiles_median_and_clip():
    q = gen.lognormal_quantiles(101, 512, 0.9, 32, 1792)
    assert q == sorted(q) and q[50] == 512
    assert q[0] >= 32 and q[-1] == 1792


def test_prompt_tokens_seeded_and_in_vocabulary():
    t = gen.prompt_tokens(BIG_SEED, 3, 50, 1000)
    assert t == gen.prompt_tokens(BIG_SEED, 3, 50, 1000)
    assert t != gen.prompt_tokens(BIG_SEED, 4, 50, 1000)
    assert len(t) == 50 and all(0 <= x < 1000 for x in t)


def test_packed_rows_shift_labels_and_separate_documents():
    spec = dict(TRAIN, batch=2, seq_len=256)
    a = gen.packed_rows(spec, BIG_SEED, 0, 151936)
    b = gen.packed_rows(spec, BIG_SEED, 0, 151936)
    c = gen.packed_rows(spec, BIG_SEED, 1, 151936)
    assert a["tokens"].shape == (2, 256) and a["tokens"].dtype == np.int32
    assert np.array_equal(a["tokens"], b["tokens"])
    assert not np.array_equal(a["tokens"], c["tokens"])
    assert np.array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
    assert (a["tokens"] == TRAIN["eos_id"]).any()
    assert a["tokens"].max() < 151936
