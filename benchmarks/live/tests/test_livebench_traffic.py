"""The traffic generator: a seed fixes the schedule, and every seed gets
the same work in another order."""
import json
import sys
from pathlib import Path

import numpy as np

LIVE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(LIVE))

from livebench import traffic  # noqa: E402

MIX = json.loads((LIVE / "traffic" / "od_stream.json").read_text())
RQ = MIX["requests"]


def _key(s):
    return [(r.due_s, r.prompt.tobytes()) for r in s.requests]


def _blocks(s):
    """(sorted gaps, sorted length ranges) of each whole block."""
    m = RQ["block"]
    due = [r.due_s for r in s.requests]
    gaps = np.diff(due)
    bucket = [next(i for i, (lo, hi) in enumerate(RQ["prompt_lens"])
                   if lo <= len(r.prompt) <= hi) for r in s.requests]
    n = (len(gaps) // m) * m
    return ([np.round(sorted(gaps[i:i + m]), 9).tolist()
             for i in range(0, n, m)],
            [sorted(bucket[i:i + m]) for i in range(0, n, m)])


def test_same_seed_same_schedule_other_seed_same_work_reordered():
    a = traffic.make(MIX, 3_000_000_123, 40, 151655)
    b = traffic.make(MIX, 3_000_000_123, 40, 151655)
    c = traffic.make(MIX, 7, 40, 151655)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)
    assert [r.due_s for r in a.requests] != [r.due_s for r in c.requests]
    assert [len(r.prompt) for r in a.requests] != \
        [len(r.prompt) for r in c.requests]
    # every block of every seed holds the same gaps and length ranges
    ga, la = _blocks(a)
    gc, lc = _blocks(c)
    assert la == lc and all(x == la[0] for x in la)
    assert np.allclose(ga[1:-1], gc[1:-1])
    assert all(np.diff([r.due_s for r in a.requests]) > 0)


def test_stream_spans_the_hold_and_follows_the_weights():
    s = traffic.make(MIX, 11, 40, 151655)
    assert s.stream_s == MIX["ondemand"]["hold_s"] - RQ["tail_s"]
    assert len(s.requests) == round(RQ["rate_per_s"] * s.stream_s)
    assert 0 <= min(r.due_s for r in s.requests)
    assert abs(max(r.due_s for r in s.requests) - s.stream_s) < 1.0
    lens = [len(r.prompt) for r in s.requests]
    for (lo, hi), w in zip(RQ["prompt_lens"], RQ["weights"]):
        n = sum(lo <= x <= hi for x in lens)
        assert abs(n - w * len(lens)) <= RQ["block"]
    assert all(any(lo <= x <= hi for lo, hi in RQ["prompt_lens"])
               for x in lens)
    assert len(set(lens)) > 2 * len(RQ["prompt_lens"])   # not on buckets
    assert all(r.new_tokens == RQ["new_tokens"] for r in s.requests)


def test_mix_without_requests_makes_none():
    mix = json.loads((LIVE / "traffic" / "train_steady.json").read_text())
    s = traffic.make(mix, 1, 40, 151655)
    assert s.requests == [] and s.trainer == "step_all" and s.od_nodes == 0

    def pad16(n):
        return -(-n // 16) * 16
    assert traffic.shapes(mix, pad16) == []
    assert traffic.shapes(MIX, pad16) == [
        (b, s) for s in (256, 512, 1024, 2048)
        for b in range(1, RQ["max_batch"] + 1)]
