import math

import numpy as np
import pytest

import loadgen

MIX = {"arrivals": {"process": "poisson", "rate_per_s": 4.0},
       "shared_prefix": {"count": 8, "tokens": 256, "zipf_s": 1.1},
       "prompt": {"median": 160, "sigma": 0.8, "min": 16, "max": 768},
       "output": {"median": 96, "sigma": 0.8, "min": 16, "max": 384},
       "temperature": 0.0, "max_len": 1536}


def _key(arr):
    return [(a.due_s, a.prompt.tobytes(), a.max_new, a.system_id)
            for a in arr]


def test_same_seed_same_schedule():
    a = loadgen.schedule(MIX, 2 ** 31 + 11, 30, 151552)
    b = loadgen.schedule(MIX, 2 ** 31 + 11, 30, 151552)
    assert _key(a) == _key(b)


def test_seeds_share_the_work_and_its_times():
    a = loadgen.schedule(MIX, 1, 30, 151552)
    b = loadgen.schedule(MIX, 2, 30, 151552)
    assert _key(a) != _key(b)                       # other token ids
    assert [(x.due_s, len(x.prompt), x.max_new, x.system_id) for x in a] \
        == [(x.due_s, len(x.prompt), x.max_new, x.system_id) for x in b]
    assert len(a) == 120
    # the sizes are the stated distributions' quantiles, shuffled
    assert sorted(x.max_new for x in a) == sorted(
        loadgen.lognormal_sizes(MIX["output"], 120))


def test_arrivals_fill_the_window_at_the_rate():
    a = loadgen.schedule(MIX, 3, 30, 151552)
    due = np.array([x.due_s for x in a])
    assert due[0] == 0.0 and np.all(np.diff(due) > 0)
    assert 25 < due[-1] < 30


def test_sizes_respect_the_clips_and_system_prompts():
    a = loadgen.schedule(MIX, 4, 30, 151552)
    lo, hi = loadgen.prompt_range(MIX)
    assert all(lo <= len(x.prompt) <= hi for x in a)
    assert all(16 <= x.max_new <= 384 for x in a)
    by_sys = {}
    for x in a:
        by_sys.setdefault(x.system_id, set()).add(x.prompt[:256].tobytes())
    assert all(len(v) == 1 for v in by_sys.values())
    counts = loadgen.zipf_counts(8, 1.1, len(a))
    assert counts.sum() == len(a) and counts[0] == counts.max()


def test_mix_must_fit_max_len():
    loadgen.check_fits(MIX)
    with pytest.raises(ValueError, match="max_len"):
        loadgen.check_fits(dict(MIX, max_len=1024))
    # 480 + 24 fits 512, but not once the prompt is padded to its bucket
    mix = dict(MIX, shared_prefix=None, max_len=512,
               prompt=dict(MIX["prompt"], max=480),
               output=dict(MIX["output"], max=24))
    loadgen.check_fits(mix)
    with pytest.raises(ValueError, match="max_len"):
        loadgen.check_fits(mix, bucket=lambda n: 1 << (n - 1).bit_length())


def test_percentile_sorts_failures_last():
    assert loadgen.percentile([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 90) == 9
    assert loadgen.percentile([1] * 9 + [math.inf], 90) == 1
    assert math.isinf(loadgen.percentile([1] * 8 + [math.inf] * 2, 90))
