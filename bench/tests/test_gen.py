"""The generator offers every seed the same work with other contents."""

import json

import numpy as np
import pytest

import gen
import run

MIX = {**json.load(open(run.BENCH / "traffic" / "chat.json")),
       "rate_rps": 0.72}


def _window(seed, W=51.0):
    reqs = gen.serve_requests(MIX, seed, 151936, W, W + 60.0, 4096)
    return [r for r in reqs if r.due < W]


def test_seeds_replay_one_schedule_with_other_tokens():
    a, b = _window(2**31 + 101), _window(2**31 + 102)
    assert len(a) == len(b) == round(0.72 * 51)
    assert [(r.due, len(r.prompt), r.max_new, r.system) for r in a] == \
        [(r.due, len(r.prompt), r.max_new, r.system) for r in b]
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    again = _window(2**31 + 101)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, again))


def test_schedule_is_stratified():
    """The window holds the same quantiles of every size, whatever the
    schedule seed."""
    a = _window(5)
    b = gen.serve_requests({**MIX, "schedule_seed": 1}, 5, 151936, 51.0,
                           51.0, 4096)
    assert len(a) == len(b)
    for size in (lambda r: len(r.prompt) - 512 * (r.system >= 0),
                 lambda r: r.max_new, lambda r: r.system >= 0):
        assert sorted(map(size, a)) == sorted(map(size, b))
    assert a[-1].due == pytest.approx(b[-1].due)


def test_lengths_follow_the_mix():
    reqs = _window(2**31 + 7, W=2000.0)
    prompts = np.array([len(r.prompt) for r in reqs])
    outs = np.array([r.max_new for r in reqs])
    assert np.median(prompts) == 1024          # the trace's median is 1020
    assert abs(np.median(outs) - 129) <= 2
    assert 0.88 <= np.mean([r.system >= 0 for r in reqs]) <= 0.92
    assert outs.min() >= 16 and (prompts + outs).max() <= 4096
