"""The reduction from a trace to busy time, kernel time and idle gaps,
on a synthetic trace and on a trace the profiler recorded."""
import sys
from pathlib import Path

import pytest

LIVE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(LIVE))

from livebench import tracing  # noqa: E402

S = 1_000_000_000


def test_reduce_synthetic():
    dev = {"/device:TPU:0": [
        ("fusion.1", 0 * S, 2 * S), ("flash_decode.3", 1 * S, 3 * S),
        ("fusion.1", 6 * S, 7 * S), ("flash_decode.3", 9 * S, 11 * S),
        ("flash_decode_gather.4", 9 * S, 10 * S)]}
    host = [("window", 0, 10 * S), ("serve_batch", 0, 3 * S),
            ("checkpoint", 3 * S, 6 * S), ("scheduler_pass", 2 * S, 9 * S),
            ("restore", 7 * S, 9 * S)]
    red = tracing.reduce(dev, host, seconds=10.0)
    assert red["window_s"] == 10.0
    assert red["busy_s"] == pytest.approx(3 + 1 + 1)   # [0,3] [6,7] [9,10]
    assert red["idle_gaps"] == [["checkpoint@3.0s", 3.0],
                                ["restore@7.0s", 2.0]]
    ops = dict((n, s) for n, s in red["device_ops"])
    assert ops == {"flash_decode.3": 3.0, "fusion.1": 3.0,
                   "flash_decode_gather.4": 1.0}
    secs, calls = tracing.kernel_seconds(red, "flash_decode")
    assert (secs, calls) == (4.0, 2.0)


def test_op_names():
    assert tracing.op_name("%flash_attention_fwd.21 = (bf16[1,14,2304,64]"
                           ") custom-call(s32[1] %get-tuple-element.2)") == \
        "flash_attention_fwd.21"
    assert tracing.op_name("fusion.3") == "fusion.3"


def test_window_ends_with_the_last_device_event():
    dev = {"/device:TPU:0": [("fusion.1", 0, 2 * S), ("fusion.2", 3 * S,
                                                      6 * S)]}
    host = [("window", 0, 10 * S), ("serve_batch", 0, 10 * S)]
    red = tracing.reduce(dev, host, seconds=10.0)
    assert red["window_s"] == 6.0 and red["busy_s"] == 5.0
    assert red["idle_gaps"] == [["serve_batch@2.0s", 1.0]]


def test_union_and_clip():
    assert tracing.union([("a", 0, 2), ("b", 1, 3), ("c", 5, 6)]) == \
        [(0, 3), (5, 6)]
    assert tracing.clip([(0, 3), (5, 6)], 1, 5.5) == [(1, 3), (5, 5.5)]


def test_host_spans_from_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    spans = tracing.Spans(traced=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.span("window"):
            with spans.span("serve_batch"):
                jnp.ones((64, 64)).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    dev, host = tracing.events(tracing.load(str(tmp_path)))
    names = {h[0] for h in host}
    assert {"window", "serve_batch"} <= names
    assert [n for n, _, _ in spans.rows] == ["serve_batch", "window"]
    w = [h for h in host if h[0] == "window"][0]
    s = [h for h in host if h[0] == "serve_batch"][0]
    assert w[1] <= s[1] <= s[2] <= w[2]
