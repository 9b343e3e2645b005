"""CPU rehearsal of the serve_only cell, the shape of the result line,
and a served token altered where it is produced."""
import json

import pytest

from livebench_rehearsal import control_fails, rehearse

CELL = "live1.internvl2_1b.serve_only"


def test_serve_only_rehearsal_and_result_line():
    out = rehearse(CELL, control=True)
    assert out["correct"], out["checks"]
    assert control_fails(out), out["control"]
    keys = list(out)
    assert keys[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert keys[-1] == "checks"
    assert out["metrics"] == {}         # a CPU run reports no device metric
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert out["device"]["platform"] == "cpu"
    assert all(len(v) == 2 for v in out["checks"].values())
    assert set(out["rehearsal_values"]) == {"od_ttft_p95_s", "od_tpot_p95_ms",
                                            "setup_s"}
    json.loads(json.dumps(out))


@pytest.fixture
def altered_token(monkeypatch):
    from repro.serving import ServeEngine
    serve = ServeEngine.serve_batch

    def broken(self, requests):
        out = serve(self, requests)
        for r in out:
            r.tokens_out[2] = (r.tokens_out[2] + 1) % self.cfg.vocab
        return out
    monkeypatch.setattr(ServeEngine, "serve_batch", broken)


def test_an_altered_token_is_not_correct(altered_token):
    out = rehearse(CELL)
    assert not out["correct"]
    value, limit = out["checks"]["served"]
    assert value > limit
