"""ServeEngine's pipelined decode loop against a plain serial greedy loop.

The engine reads each step's tokens back one step late; the reference
below dispatches a step, reads its tokens and decides on the next, one
after the other.  Both must hand every request the same tokens.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.configs import get_config
from repro.configs.reduced import reduced
from repro.models import decode_step, init_params, prefill
from repro.serving import Request, ServeEngine
from repro.serving.engine import pad_batch

MAX_SEQ = 48
LENS = (9, 16, 30)                      # padded to 32: 16 decode slots left


def _cfg():
    """InternVL2-1B at the benchmark rehearsal's reduced CPU sizes."""
    return get_config("internvl2_1b").with_(
        n_layers=2, d_model=64, n_heads=4, n_kv=2, d_head=16, d_ff=128,
        vocab=512, n_patches=8, attn_block_q=64, attn_block_kv=64)


def _prompts():
    rng = np.random.default_rng(1)
    return [rng.integers(0, 512, n, dtype=np.int32) for n in LENS]


def _model(cfg):
    """The config, its weights, and the model's own prefill and decode
    step, each jitted once."""
    return (cfg, init_params(jax.random.PRNGKey(0), cfg),
            jax.jit(lambda p, t, st: prefill(p, t, cfg, kv_start=st)),
            jax.jit(lambda p, c, t, pos, st: decode_step(p, c, t, pos, cfg,
                                                         kv_start=st)))


@pytest.fixture(scope="module")
def model():
    return _model(_cfg())


def serial_greedy(model, prompts, max_new, eos_id=None):
    """Each request's greedy tokens, one step at a time: a row stops at
    its max_new-th token or at eos_id, and every row at the cache's end."""
    _, params, pre, step = model
    toks, kv_start = pad_batch(prompts)
    S = toks.shape[1]
    logits, cache = pre(params, toks, kv_start)
    cache = jax.tree.map(
        lambda c: jnp.pad(c, [(0, 0), (0, 0), (0, MAX_SEQ - S)]
                          + [(0, 0)] * (c.ndim - 3)), cache)
    tok = np.asarray(jnp.argmax(logits, axis=-1))
    out = [[int(t)] for t in tok]

    def done(i):
        return len(out[i]) >= max_new[i] or out[i][-1] == eos_id

    pos = S
    while pos < MAX_SEQ and not all(done(i) for i in range(len(out))):
        live = [i for i in range(len(out)) if not done(i)]
        logits, cache = step(params, cache, jnp.asarray(tok[:, None]), pos,
                             kv_start)
        tok = np.asarray(jnp.argmax(logits, axis=-1))
        for i in live:
            out[i].append(int(tok[i]))
        pos += 1
    return out, MAX_SEQ - S


def _eos_of(model, row, at, max_new):
    """The token the serial loop gives ``row`` at index ``at``."""
    out, _ = serial_greedy(model, _prompts(), max_new)
    return out[row][at]


@pytest.mark.parametrize("max_new, eos", [
    ((5, 5, 5), None),                  # mixed prompt lengths
    ((1, 4, 7), None),                  # unequal max_new_tokens
    ((8, 8, 8), (0, 2)),                # eos: row 0 stops early
    ((4, 4, 12), (2, 8)),               # eos ends the last live row
    ((30, 3, 30), None),                # the cache's end: pos >= max_seq
], ids=["mixed_lengths", "unequal_max_new", "eos_one_row", "eos_last_row",
        "cache_end"])
def test_pipelined_decode_matches_the_serial_loop(model, max_new, eos):
    wasted = _check(model, max_new, eos)
    assert wasted == (eos == (2, 8))    # only there does eos end the batch


def test_moe_family_pipelined_decode_matches_the_serial_loop():
    """MLA's absorbed decode and the grouped expert products under the
    same loop: it carries one token array and one position, whatever the
    family."""
    assert _check(_model(reduced("deepseek_v2_lite")), (4, 4, 12), (2, 6))


def _check(model, max_new, eos):
    eos_id = None if eos is None else _eos_of(model, *eos, max_new)
    want, n_cap = serial_greedy(model, _prompts(), max_new, eos_id)
    eng = ServeEngine(model[0], model[1], max_seq=MAX_SEQ, eos_id=eos_id)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=n)
            for i, (p, n) in enumerate(zip(_prompts(), max_new))]
    before = telemetry.counters()
    since = time.monotonic()
    eng.serve_batch(reqs)
    after = telemetry.counters()
    got = {k: after.get(k, 0) - before.get(k, 0) for k in after}

    assert [r.tokens_out for r in reqs] == want
    k = max(len(w) for w in want) - 1   # decode steps handed out
    if eos is None:
        assert all(len(w) == min(n, 1 + n_cap)
                   for w, n in zip(want, max_new))
    else:
        assert any(len(w) < n for w, n in zip(want, max_new))
    assert got["serve.decode_slots"] == len(LENS) * k
    assert got["serve.tokens_out"] == sum(len(w) for w in want)
    # step k+1 was dispatched past the end only if a row live before
    # step k still had steps to go, which only eos_id leaves undone
    wasted = int(k + 1 <= n_cap and any(
        len(w) == k + 1 and n - 1 >= k + 1 for w, n in zip(want, max_new)))
    assert got["serve.decode_discarded"] == wasted
    assert got["serve.decode_discarded"] <= (0 if eos is None else 1)
    assert got["serve.decode_ahead"] == k - 1 + wasted
    steps = telemetry.rows("serve.decode_step", since=since)
    assert len(steps) == k
    return wasted
