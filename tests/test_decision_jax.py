"""Parity suite: jitted padded decision kernels vs numpy references.

The numpy kernels in repro.core.decision are the bit-for-bit references;
under x64 every JAX port must match them *exactly* (same IEEE
expressions, same stable sort order), including the hardened boundary
semantics (empty running set, avail-covers-need, exact cumsum cover,
int64-overflow apportionment).  Under float32 the documented contract is
weaker: continuous outputs within FLOAT32_RTOL, discrete outputs checked
by structural invariants (exact sums, per-job caps).

Randomized cases draw padded lengths from a small fixed set so each
jitted wrapper compiles a handful of shapes, not one per example.
"""
import math

import numpy as np
import pytest

pytest.importorskip("jax")

from repro.core import decision as D
from repro.core import decision_jax as J
from repro.core.experiment import Experiment
from repro.core.policy import registered_mechanisms
from repro.core.workloads import WorkloadConfig

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # optional dep: requirements-dev only
    HAVE_HYPOTHESIS = False

# bounded pad shapes: the single-call wrappers trace once per (shape,
# dtype), so random examples reuse a handful of compiled programs
SIZES = (0, 1, 2, 3, 7, 16)


def _same_shadow(a, b):
    return (a == b) or (math.isinf(a[0]) and math.isinf(b[0])
                        and a[1] == b[1])


# ------------------------------------------------------------ exact parity
@pytest.mark.parametrize("seed", range(4))
def test_easy_shadow_parity_x64(seed):
    rng = np.random.default_rng(seed)
    for n in SIZES:
        for _ in range(8):
            avail = int(rng.integers(0, 50))
            need = int(rng.integers(1, 60))
            bases = rng.uniform(0.0, 100.0, n)
            sizes = rng.integers(1, 20, n)
            now = float(rng.uniform(0.0, 50.0))
            ref = D.easy_shadow(avail, need, bases, sizes, now)
            got = J.easy_shadow_jax(avail, need, bases, sizes, now)
            assert _same_shadow(ref, got), (avail, need, bases, sizes, now)


@pytest.mark.parametrize("seed", range(4))
def test_victims_parity_x64(seed):
    rng = np.random.default_rng(seed)
    for n in SIZES:
        for _ in range(8):
            sizes = rng.integers(1, 20, n)
            over = rng.uniform(0.0, 100.0, n)
            need = int(rng.integers(0, 80))
            assert D.select_preemption_victims(sizes, over, need) == \
                J.select_preemption_victims_jax(sizes, over, need)


@pytest.mark.parametrize("seed", range(4))
def test_apportion_parity_x64(seed):
    rng = np.random.default_rng(seed)
    for n in SIZES:
        for _ in range(8):
            mn = rng.integers(0, 10, n)
            cur = mn + rng.integers(0, 20, n)
            need = int(rng.integers(0, 60))
            assert D.apportion_shrink(cur, mn, need) == \
                J.apportion_shrink_jax(cur, mn, need)


@pytest.mark.parametrize("seed", range(4))
def test_backfill_filters_parity_x64(seed):
    rng = np.random.default_rng(seed)
    for n in SIZES:
        for _ in range(6):
            needs = np.where(rng.random(n) < 0.2, np.inf,
                             rng.integers(1, 30, n).astype(float))
            bound = float(rng.integers(0, 40))
            assert np.array_equal(D.backfill_prefilter(needs, bound),
                                  J.backfill_prefilter_jax(needs, bound))
    for k in SIZES:
        N = max(k, 1) + 3
        needs = rng.integers(1, 30, N).astype(float)
        ests = rng.uniform(0.0, 100.0, N)
        cand = np.sort(rng.choice(N, size=k, replace=False))
        budget = int(rng.integers(0, 40))
        now = float(rng.uniform(0.0, 50.0))
        ts = float(rng.uniform(0.0, 150.0))
        assert np.array_equal(
            D.backfill_shadow_filter(needs, ests, cand, budget, now, ts),
            J.backfill_shadow_filter_jax(needs, ests, cand, budget, now, ts))


# -------------------------------------------------------------- boundaries
def test_easy_shadow_boundaries():
    # empty running set, avail covers: the hardened (now, extra) path
    assert J.easy_shadow_jax(5, 3, [], [], 7.0) == (7.0, 2)
    assert J.easy_shadow_jax(3, 3, [], [], 0.0) == (0.0, 0)
    # empty running set, cannot cover
    t, extra = J.easy_shadow_jax(0, 1, [], [], 0.0)
    assert math.isinf(t) and extra == 0
    # exact cumsum cover at a release
    assert J.easy_shadow_jax(0, 30, [5.0, 9.0], [10, 20], 0.0) == (9.0, 0)
    # tied est-ends accumulate in ascending-size order
    assert J.easy_shadow_jax(0, 5, [7.0, 7.0], [20, 10], 0.0) == (7.0, 5)


def test_victims_and_apportion_boundaries():
    assert J.select_preemption_victims_jax([], [], 0) == ([], 0)
    assert J.select_preemption_victims_jax([100, 100], [1.0, 2.0], 100) \
        == ([0], 0)
    assert J.select_preemption_victims_jax([10, 20], [1.0, 2.0], 31) \
        == ([], 0)
    assert J.apportion_shrink_jax([10, 8], [4, 6], 8) == [6, 2]
    assert J.apportion_shrink_jax([10, 10], [10, 10], 1) == []
    assert J.apportion_shrink_jax([10, 10], [2, 2], 0) == [0, 0]


@pytest.mark.parametrize("cur, need", [
    ([65045927626, 68844673057], 52072923076),
    ([26978671376, 4097352393, 1652763552, 81327023920, 91275557727],
     124561354304),
])
def test_apportion_overflow_regression_parity(cur, need):
    # the int64-overflow regime exercises the guarded quota branch on
    # both sides; parity must survive it
    ref = D.apportion_shrink(cur, [0] * len(cur), need)
    got = J.apportion_shrink_jax(cur, [0] * len(cur), need)
    assert ref == got and sum(got) == need


# ------------------------------------------------------- float32 fallback
def test_float32_shadow_within_documented_tolerance():
    rng = np.random.default_rng(7)
    for _ in range(20):
        n = int(rng.choice([c for c in SIZES if c]))
        avail = int(rng.integers(0, 30))
        need = int(rng.integers(1, 50))
        bases = rng.uniform(0.0, 100.0, n)
        sizes = rng.integers(1, 20, n)
        now = float(rng.uniform(0.0, 50.0))
        ref_t, _ = D.easy_shadow(avail, need, bases, sizes, now)
        got_t, _ = J.easy_shadow_jax(avail, need, bases, sizes, now,
                                     dtype="float32")
        if math.isinf(ref_t):
            assert math.isinf(got_t)
        else:
            assert abs(got_t - ref_t) <= \
                J.FLOAT32_RTOL * max(abs(ref_t), 1.0)


def test_float32_apportion_invariants_hold():
    rng = np.random.default_rng(11)
    for _ in range(20):
        n = int(rng.choice([c for c in SIZES if c]))
        mn = rng.integers(0, 10, n)
        cur = mn + rng.integers(0, 20, n)
        slack = np.maximum(cur - mn, 0)
        supply = int(slack.sum())
        if supply == 0:
            continue
        need = int(rng.integers(1, supply + 1))
        got = J.apportion_shrink_jax(cur, mn, need, dtype="float32")
        assert sum(got) == need
        assert all(0 <= g <= s for g, s in zip(got, slack))


def test_bad_dtype_rejected():
    with pytest.raises(ValueError, match="dtype"):
        J.easy_shadow_jax(1, 1, [], [], 0.0, dtype="bfloat16")


# ----------------------------------------------------- hypothesis parity
if HAVE_HYPOTHESIS:
    @given(st.integers(0, 64), st.integers(1, 128),
           st.lists(st.tuples(st.floats(0, 1e4), st.integers(1, 32)),
                    min_size=0, max_size=16),
           st.floats(0, 1e4))
    @settings(max_examples=60, deadline=None)
    def test_hyp_easy_shadow_parity(avail, need, jobs, now):
        # pad every draw to one shape so hypothesis explores values, not
        # compile cache entries
        jobs = jobs + [(math.inf, 0)] * (16 - len(jobs))
        bases = [j[0] for j in jobs]
        sizes = [j[1] for j in jobs]
        ref = D.easy_shadow(avail, need, bases, sizes, now)
        got = J.easy_shadow_jax(avail, need, bases, sizes, now)
        assert _same_shadow(ref, got)

    @given(st.lists(st.integers(0, 10**11), min_size=8, max_size=8),
           st.data())
    @settings(max_examples=60, deadline=None)
    def test_hyp_apportion_parity_any_scale(slacks, data):
        need = data.draw(st.integers(0, sum(slacks)))
        assert D.apportion_shrink(slacks, [0] * 8, need) == \
            J.apportion_shrink_jax(slacks, [0] * 8, need)


# ----------------------------------------- batched grid: all mechanisms
def test_device_sweep_parity_across_all_registered_mechanisms():
    mechs = registered_mechanisms()
    exp = Experiment(mechanisms=mechs,
                     workloads=[WorkloadConfig(n_jobs=50, notice_mix="W3")],
                     seeds=(0,), processes=0,
                     device="jax", device_capture=64)
    res = exp.run()
    rep = res.device_report
    assert rep.n_cells == len(mechs)
    assert rep.n_programs == 1
    assert rep.n_calls > 0
    assert rep.parity_ok, rep.mismatches[:5]
    # the device replay is an overlay: metrics equal the plain fan-out
    base = Experiment(mechanisms=mechs, workloads=exp.workloads,
                      seeds=(0,), processes=0).run()
    assert [r.metrics.as_dict() for r in res] == \
        [r.metrics.as_dict() for r in base]


def test_capture_trace_survives_pickle_and_fanout_shape():
    import pickle

    with D.capture(limit=4) as tr:
        D.easy_shadow(5, 3, [], [], 7.0)
        D.apportion_shrink([4, 4], [1, 1], 3)
    tr2 = pickle.loads(pickle.dumps(tr))
    assert tr2.n_calls() == tr.n_calls() == 2
    cells = [("cell0", tr2)]
    rep = J.run_device_sweep(cells)
    assert rep.parity_ok and rep.n_calls == 2


def test_experiment_forks_no_worker_once_jax_holds_a_backend(caplog):
    """A forked worker cannot use its parent's device: once this process
    has initialised JAX, fan-out runs the cells here, with a warning."""
    import jax
    jax.devices()                                # initialise the backend
    kw = dict(mechanisms=["BASE", "CUA&SPAA"],
              workloads=[WorkloadConfig(n_jobs=20, notice_mix="W1")],
              seeds=(0,))
    with caplog.at_level("WARNING", logger="repro.core.experiment"):
        fanned = Experiment(processes=2, **kw).run()
    assert "running 2 run(s) serially" in caplog.text
    serial = Experiment(processes=0, **kw).run()
    assert [r.metrics.as_dict() for r in fanned] == \
        [r.metrics.as_dict() for r in serial]


def test_float64_replay_is_refused_on_a_tpu(monkeypatch):
    """A TPU emulates float64 and cannot keep the exact contract: the
    declared device dtype there is float32, and float64 raises before the
    sweep runs."""
    assert J.device_dtype() == "float64"          # this backend: exact
    monkeypatch.setattr(J.jax, "default_backend", lambda: "tpu")
    assert J.device_dtype() == "float32"
    with pytest.raises(ValueError, match="not exact on a TPU"):
        J.run_device_sweep([])
    exp = Experiment(mechanisms=["BASE"], seeds=(0,), processes=0,
                     workloads=[WorkloadConfig(n_jobs=20)], device="jax")
    with pytest.raises(ValueError, match="not exact on a TPU"):
        exp.run()
