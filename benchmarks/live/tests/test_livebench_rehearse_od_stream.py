"""CPU rehearsal of the od_stream mix (a cell left out of BENCHMARK.json
until its vacate is steady enough to bound): the trainer is preempted for
the on-demand job and restored bit for bit; every number compared holds,
and the float8 control fails one."""
import json

from livebench_rehearsal import control_fails, rehearse

CELL = "live1.internvl2_1b.od_stream"
ENTRY = {"name": CELL, "config": "live1.internvl2_1b", "traffic": "od_stream",
         "chips": 1, "why": "rehearsal"}


def test_od_stream_rehearsal_is_correct_and_its_control_is_not():
    out = rehearse(CELL, control=True, entry=ENTRY)
    assert out["correct"], out["checks"]
    assert set(out["checks"]) == {"decisions", "restore", "init", "loss",
                                  "grad", "update", "served"}
    assert out["checks"]["restore"] == [0, 0]
    assert control_fails(out), out["control"]
    assert out["rehearsal_values"] == {"setup_s":
                                       out["rehearsal_values"]["setup_s"]}
    assert out["failed"] == 0 and out["attempted"] > 0
    json.dumps(out)
