"""The generator is a pure function of the seed, and sends only the
prompt lengths the cell warms."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import traffic as T  # noqa: E402

SERVED = ("reasoning", "chat")


def mix(name):
    with open(os.path.join(ROOT, "bench", "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", SERVED)
def test_same_seed_same_requests(name):
    m = mix(name)
    a = T.requests(m, 2 ** 40 + 3, 200, 1000)
    b = T.requests(m, 2 ** 40 + 3, 200, 1000)
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new, x.due_s) == (y.max_new, y.due_s)
    c = T.requests(m, 7, 200, 1000)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("name", SERVED)
def test_prompt_lengths_from_the_warmed_set(name):
    m = mix(name)
    allowed = set(T.allowed_prompt_lengths(m))
    for seed in (0, 1, 2 ** 33):
        assert {len(r.prompt) for r in T.requests(m, seed, 300, 1000)} \
            <= allowed


@pytest.mark.parametrize("name", SERVED)
def test_every_seed_offers_the_same_work(name):
    """Whole blocks hold the same sizes and gaps for every seed."""
    m = mix(name)
    blk = m["block"]
    pblk = m["prompt_tokens"].get("block", blk)
    np_ = (2 * blk // pblk) * pblk
    work = []
    for seed in (11, 2 ** 35 + 1):
        rs = T.requests(m, seed, 2 * blk, 1000)
        gaps = np.diff([0.0] + [r.due_s for r in rs])
        work.append((sorted(len(r.prompt) for r in rs[:np_]),
                     sorted(r.max_new for r in rs[:blk]),
                     sorted(r.max_new for r in rs[blk:2 * blk]),
                     sorted(np.round(gaps[:2 * blk], 9))))
    assert work[0] == work[1]


def test_chat_rounding_and_clipping():
    m = mix("chat")
    rs = T.requests(m, 5, 640, 1000)
    assert {len(r.prompt) for r in rs} <= {128, 256, 512, 1024, 2048}
    assert all(16 <= r.max_new <= 512 for r in rs)
    due = np.array([r.due_s for r in rs])
    assert np.all(np.diff(due) > 0)
    rate = len(rs) / due[-1]
    assert abs(rate / m["rate_per_s"] - 1) < 0.05


def test_reasoning_first_wave_is_a_residual():
    m = mix("reasoning")
    rs = T.requests(m, 9, 2 * m["clients"], 1000)
    first, rest = rs[:m["clients"]], rs[m["clients"]:]
    assert min(r.max_new for r in first) < 1024
    assert all(1024 <= r.max_new <= 4096 for r in rest)


def test_a_replayed_schedule_varies_only_the_tokens():
    m = mix("chat")
    assert "schedule_seed" in m
    a = T.requests(m, 1, 100, 1000)
    b = T.requests(m, 2 ** 40 + 9, 100, 1000)
    assert [(len(x.prompt), x.max_new, x.due_s) for x in a] == \
        [(len(y.prompt), y.max_new, y.due_s) for y in b]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
