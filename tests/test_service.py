"""repro.service: shadow-mode scheduler daemon over the policy engine.

Everything here is jax-free (tier-1): the service replays scenarios
through DryrunLauncher / NullLauncher on CPU.
"""
import json
import math
import time

import pytest

from repro.core import JobSpec, JobType, NoticeKind, SimConfig, Simulator
from repro.core.workloads import get_scenario
from repro.service import (AdmissionQueue, DecisionLog, DryrunLauncher,
                           NullLauncher, ReplayClock, SchedulerService,
                           ServiceConfig, ServiceCore, ShadowLaunchError,
                           SloMonitor, SloPolicy, decision_digest,
                           plan_requests, read_decision_log, shadow_fidelity)


def _jobs_small():
    """A hand-rolled hybrid mix exercising shrink, preempt, and notice."""
    return [
        JobSpec(jid=0, jtype=JobType.MALLEABLE, project="t", submit_time=0.0,
                size=6, t_estimate=9000.0, t_actual=6000.0, t_setup=30.0,
                n_min=2),
        JobSpec(jid=1, jtype=JobType.RIGID, project="t", submit_time=10.0,
                size=2, t_estimate=4000.0, t_actual=3000.0, t_setup=30.0),
        JobSpec(jid=2, jtype=JobType.ONDEMAND, project="od", submit_time=600.0,
                size=4, t_estimate=1200.0, t_actual=1200.0,
                notice_kind=NoticeKind.ACCURATE, notice_time=300.0,
                est_arrival=600.0),
        JobSpec(jid=3, jtype=JobType.RIGID, project="t", submit_time=700.0,
                size=3, t_estimate=2000.0, t_actual=1500.0, t_setup=30.0),
    ]


def _scenario_jobs(n_jobs=40, seed=0):
    return get_scenario("bursty-od", n_jobs=n_jobs).realize(seed)


# ------------------------------------------------------------- replay clock
def test_replay_clock_inf_never_sleeps():
    clock = ReplayClock()
    assert not clock.realtime
    t0 = time.monotonic()
    assert clock.sleep_until(1e12) == 0.0
    assert time.monotonic() - t0 < 0.05
    assert clock.now_sim() == math.inf


def test_replay_clock_scales_and_sleeps():
    clock = ReplayClock(speed=1000.0, origin=500.0)
    assert clock.realtime
    slept = clock.sleep_until(520.0)          # 20 sim-s = 20ms wall
    assert slept > 0.0
    assert clock.now_sim() >= 520.0


def test_replay_clock_rejects_bad_speed():
    for bad in (0.0, -1.0, float("nan")):
        with pytest.raises(ValueError):
            ReplayClock(speed=bad)


# ------------------------------------------------------------- decision log
def test_decision_log_jsonl_roundtrip_and_digest(tmp_path):
    path = str(tmp_path / "decisions.jsonl")
    rows = [{"seq": 0, "event": "start", "jid": 1, "t_sim": 0.0},
            {"seq": 1, "event": "end", "jid": 1, "t_sim": 9.5}]
    with DecisionLog(path) as log:
        log.append(rows[0], latency_ms=0.5)
        log.append(rows[1], latency_ms=1.5)
        digest = log.digest
    back = read_decision_log(path)
    assert len(back) == 2
    assert back[0]["event"] == "start" and "wall" in back[0]
    assert back[1]["latency_ms"] == 1.5
    # measurement fields are digest-excluded: re-digesting the file rows
    # (different wall/mono) reproduces the live digest
    assert decision_digest(back) == digest == decision_digest(rows)


def test_decision_log_latency_summary():
    log = DecisionLog()
    for ms in (1.0, 2.0, 3.0, 4.0):
        log.append({"seq": 0, "event": "x", "jid": 0}, latency_ms=ms)
    s = log.latency_summary()
    assert s["n"] == 4 and s["max_ms"] == 4.0
    assert 1.0 <= s["p50_ms"] <= 3.0 <= s["p99_ms"] <= 4.0
    assert DecisionLog().latency_summary()["n"] == 0


def test_digest_sensitive_to_order_and_content():
    a = [{"seq": 0, "event": "start", "jid": 1}]
    b = [{"seq": 0, "event": "start", "jid": 2}]
    assert decision_digest(a) != decision_digest(b)
    two = [{"seq": 0, "event": "s", "jid": 1}, {"seq": 1, "event": "e", "jid": 1}]
    assert decision_digest(two) != decision_digest(list(reversed(two)))


# -------------------------------------------------------------- slo monitor
def test_slo_monitor_gates_decision_latency():
    mon = SloMonitor(SloPolicy(decision_p99_ms=1.0))
    for _ in range(10):
        mon.add_decision_latency(0.2)
    assert mon.report().ok
    mon.add_decision_latency(500.0)   # >1% of samples: moves the p99
    rep = mon.report()
    assert not rep.ok and "decision p99" in rep.violations[0]


def test_slo_monitor_od_wait_gate():
    mon = SloMonitor(SloPolicy(od_wait_p99_s=10.0))
    sim = Simulator(SimConfig(n_nodes=8), _jobs_small(),
                    record_sink=mon.add_record)
    sim.run()
    rep = mon.report()
    assert rep.n_od == 1
    assert rep.ok  # CUA&SPAA starts the od instantly on this trace


# ----------------------------------------------------------- dryrun launcher
def test_dryrun_launcher_validates_transitions():
    lau = DryrunLauncher(n_nodes=4)
    od = JobSpec(jid=9, jtype=JobType.ONDEMAND, project="od", submit_time=0.0,
                 size=2, t_estimate=10.0, t_actual=10.0)
    with pytest.raises(ShadowLaunchError):
        lau.resize(od, 1)                     # resize before start
    lau.start_job(od, 2)
    with pytest.raises(ShadowLaunchError):
        lau.start_job(od, 2)                  # double start
    assert lau.counts["od_start"] == 1
    assert lau.request_plans[9] == plan_requests(od)
    big = JobSpec(jid=10, jtype=JobType.RIGID, project="t", submit_time=0.0,
                  size=3, t_estimate=10.0, t_actual=10.0)
    with pytest.raises(ShadowLaunchError):
        lau.start_job(big, 3)                 # 5 > 4 nodes: over-commit
    with pytest.raises(ShadowLaunchError):
        lau.close()                           # od still marked running


def test_plan_requests_deterministic_and_bounded():
    od = JobSpec(jid=3, jtype=JobType.ONDEMAND, project="od", submit_time=0.0,
                 size=20, t_estimate=10.0, t_actual=10.0)
    plan = plan_requests(od, max_batch=8)
    assert plan == plan_requests(od, max_batch=8)
    assert len(plan) == 8
    assert all(8 <= r["prompt_len"] < 64 for r in plan)


def test_plan_requests_per_node():
    od = JobSpec(jid=5, jtype=JobType.ONDEMAND, project="od", submit_time=0.0,
                 size=1, t_estimate=10.0, t_actual=10.0)
    assert len(plan_requests(od)) == 1
    plan = plan_requests(od, per_node=4)
    assert len(plan) == 4
    assert plan[0] == plan_requests(od)[0]
    assert len({r["rid"] for r in plan}) == 4
    assert len({r["prompt_len"] for r in plan}) == 4     # a ragged batch
    od3 = JobSpec(jid=5, jtype=JobType.ONDEMAND, project="od",
                  submit_time=0.0, size=3, t_estimate=10.0, t_actual=10.0)
    assert len(plan_requests(od3, per_node=4)) == 8      # max_batch caps it


# ------------------------------------------------------- core + replay loop
def test_service_core_decision_stream_matches_offline_reference():
    jobs, n_nodes = _scenario_jobs()
    cfg = ServiceConfig(n_nodes=n_nodes)
    svc = SchedulerService(cfg, list(jobs), launcher=DryrunLauncher(n_nodes))
    rep = svc.run_replay()
    ref = ServiceCore(cfg.sim_config(), list(jobs), launcher=NullLauncher())
    ref.run()
    assert rep.digest == decision_digest(ref.drain_decisions())
    assert rep.n_decisions > 0


def test_shadow_fidelity_job_for_job_all_mechanisms():
    jobs, n_nodes = _scenario_jobs(n_jobs=30, seed=1)
    for mech in ("BASE", "N&PAA", "CUA&SPAA", "CUP&STEAL"):
        cfg = ServiceConfig(n_nodes=n_nodes, mechanism=mech)
        rep = shadow_fidelity(jobs, cfg)
        assert rep.ok, (mech, rep.mismatched_jids)
        assert rep.digests_match and rep.records_match


def test_service_replay_writes_decision_log(tmp_path):
    jobs, n_nodes = _scenario_jobs(n_jobs=20, seed=8)
    path = str(tmp_path / "d.jsonl")
    cfg = ServiceConfig(n_nodes=n_nodes, decision_log_path=path)
    svc = SchedulerService(cfg, jobs, launcher=DryrunLauncher(n_nodes))
    rep = svc.run_replay()
    rows = read_decision_log(path)
    assert len(rows) == rep.n_decisions
    assert decision_digest(rows) == rep.digest
    assert all("latency_ms" in r and "wall" in r and "mono" in r
               for r in rows)
    starts = [r for r in rows if r["event"] == "start"]
    assert starts and all("size" in r and "jtype" in r for r in starts)


def test_service_realtime_pacing_spreads_decisions():
    jobs = _jobs_small()
    # 1000 sim-s per wall-s: the 700s trace span replays in ~0.7s wall
    cfg = ServiceConfig(n_nodes=8, speed=5000.0)
    svc = SchedulerService(cfg, jobs, launcher=DryrunLauncher(8))
    rep = svc.run_replay()
    assert rep.wall_s > 0.1               # actually slept between events
    assert rep.digest == shadow_fidelity(
        _jobs_small(), ServiceConfig(n_nodes=8)).digest_reference


def test_service_streaming_record_sink():
    jobs, n_nodes = _scenario_jobs(n_jobs=25, seed=3)
    seen = []
    cfg = ServiceConfig(n_nodes=n_nodes)
    svc = SchedulerService(cfg, jobs, launcher=DryrunLauncher(n_nodes),
                           record_sink=seen.append)
    rep = svc.run_replay()
    assert len(seen) == rep.n_jobs
    assert not svc.core.records              # everything retired


def test_shadow_report_is_json_serializable():
    jobs, n_nodes = _scenario_jobs(n_jobs=15, seed=4)
    rep = shadow_fidelity(jobs, ServiceConfig(n_nodes=n_nodes))
    json.dumps(rep.as_dict(), default=str)


# ---------------------------------------------------------------- live mode
def test_live_admission_end_to_end():
    cfg = ServiceConfig(n_nodes=8, speed=5000.0)
    adm = AdmissionQueue()
    svc = SchedulerService(cfg, [], launcher=DryrunLauncher(8))
    adm.submit_training(n_max=6, runtime_s=600.0, n_min=2)
    adm.submit_rigid(nodes=2, runtime_s=300.0)
    adm.submit_inference(nodes=4, hold_s=200.0, submit_time=100.0,
                         notice_lead_s=60.0)
    adm.close()
    rep = svc.run_live(adm)
    events = [r["event"] for r in svc.log.rows]
    assert events.count("admit") == 3
    assert "shrink" in events             # SPAA vacated the malleable
    assert "expand" in events             # lease repaid after od end
    assert rep.launcher_counts["od_start"] == 1
    assert rep.launcher_counts["finish"] == 3


def test_live_admission_clamps_past_times():
    core = ServiceCore(SimConfig(n_nodes=4), [], launcher=NullLauncher())
    core.step_until(0.0)
    core.now = 100.0
    spec = JobSpec(jid=7, jtype=JobType.RIGID, project="t", submit_time=5.0,
                   size=1, t_estimate=10.0, t_actual=10.0)
    admitted = core.admit(spec)
    assert admitted.submit_time == 100.0
    with pytest.raises(ValueError):
        core.admit(admitted)              # duplicate jid


def test_admit_rejected_on_trace_replaying_core():
    jobs, n_nodes = _scenario_jobs(n_jobs=10, seed=5)
    core = ServiceCore(SimConfig(n_nodes=n_nodes), iter(jobs))
    with pytest.raises(RuntimeError):
        core.admit(jobs[0])


def test_admission_queue_thread_safety_and_close():
    adm = AdmissionQueue(base_jid=50)
    s1 = adm.submit_training(n_max=2, runtime_s=10.0)
    s2 = adm.submit_inference(nodes=1, hold_s=5.0)
    assert (s1.jid, s2.jid) == (50, 51)
    assert len(adm) == 2
    got = adm.drain()
    assert [j.jid for j in got] == [50, 51] and len(adm) == 0
    adm.close()
    with pytest.raises(RuntimeError):
        adm.submit_rigid(nodes=1, runtime_s=1.0)


# ------------------------------------------------------------ incremental API
def test_step_until_partitioning_matches_single_run():
    jobs, n_nodes = _scenario_jobs(n_jobs=30, seed=6)
    cfg = SimConfig(n_nodes=n_nodes)
    ref = Simulator(cfg, list(jobs)).run()
    sim = Simulator(cfg, list(jobs))
    t = 0.0
    while True:
        nxt = sim.step_until(t)
        if nxt is None:
            break
        t = nxt + 1.0                     # arbitrary non-decreasing limits
    got = sim.records
    assert set(got) == set(ref)
    for jid in ref:
        assert got[jid].completion == ref[jid].completion
        assert got[jid].n_preempted == ref[jid].n_preempted


def test_next_event_time_monotone_nonperturbing():
    jobs, n_nodes = _scenario_jobs(n_jobs=10, seed=7)
    sim = Simulator(SimConfig(n_nodes=n_nodes), iter(list(jobs)))
    t1 = sim.next_event_time()
    assert t1 == sim.next_event_time()    # peeking is idempotent
    sim.step_until(t1)
    t2 = sim.next_event_time()
    assert t2 is None or t2 > t1
