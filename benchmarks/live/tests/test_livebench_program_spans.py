"""The per-layer metrics that read the program's own spans
(``repro.telemetry``): each reader on hand-made rows, and a CPU rehearsal
of each cell that finds nothing compiled inside the window."""
import math
import sys
from pathlib import Path

import pytest

LIVE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(LIVE))

import repro  # noqa: E402
from livebench import bench, stack  # noqa: E402
from livebench_rehearsal import rehearse  # noqa: E402
from repro import telemetry  # noqa: E402

SERVE = "live1.internvl2_1b.serve_only"
TRAIN = "live1.internvl2_1b.train_steady"
READERS = {"decode_host_ms": SERVE, "prefill_tokens_per_s": SERVE,
           "train_host_ms": TRAIN}


def _run(t0=100.0, t1=140.0):
    run = stack.Run(cell=SERVE, cfg={}, mix={}, sz={}, seconds=t1 - t0,
                    seed=1, chips=1, rehearse=False)
    run.t0, run.t1 = t0, t1
    return run


@pytest.fixture
def rec(monkeypatch):
    """A recorder of its own in the place of the process's."""
    r = telemetry.Recorder()
    monkeypatch.setattr(telemetry, "rows", r.rows)
    monkeypatch.setattr(telemetry, "dropped_since", r.dropped_since)
    return r


def _add(rec, rows):
    """(seq, name, t0, t1, parent, n) -> recorder rows."""
    for seq, name, t0, t1, parent, n in rows:
        rec._add((seq, name, t0, t1, parent, None, n))


def test_each_reader_is_listed_for_its_one_cell():
    b = bench.benchmark()
    for name, cell in READERS.items():
        (m,) = [m for m in b["per_layer"] if m["name"] == name]
        assert m["workloads"] == [cell] and m["source"] == "host_clock"
        for c in (SERVE, TRAIN):
            listed = [m["name"] for m in bench.cell_metrics(b, c, True)]
            assert (name in listed) == (c == cell)


def test_decode_host_ms(rec):
    _add(rec, [
        (1, "serve.decode_step", 99.0, 99.01, None, 8),     # before
        (2, "serve.token_sync", 99.005, 99.01, 1, 8),
        (3, "serve.decode_step", 101.0, 101.005, None, 8),
        (4, "serve.dispatch", 101.0, 101.002, 3, 0),
        (5, "serve.token_sync", 101.002, 101.004, 3, 8),
        (6, "serve.decode_step", 102.0, 102.006, None, 8),
        (7, "serve.token_sync", 102.001, 102.004, 6, 8),
        (8, "serve.decode_step", 103.0, 103.004, None, 8),
        (9, "serve.token_sync", 103.0, 103.003, 8, 8),
        (10, "serve.decode_step", 140.0, 140.1, None, 8),   # after
    ])
    # host parts 3, 3 and 1 ms
    assert bench.read_metric("decode_host_ms", _run()) == pytest.approx(3.0)


def test_prefill_tokens_per_s(rec):
    _add(rec, [
        (1, "serve.prefill", 99.9, 100.1, None, 5000),      # before
        (2, "serve.prefill", 101.0, 101.2, None, 1000),
        (3, "serve.token_sync", 101.15, 101.2, 2, 8),
        (4, "serve.prefill", 105.0, 105.3, None, 2000),
    ])
    assert bench.read_metric("prefill_tokens_per_s", _run()) == \
        pytest.approx(3000 / 0.5)


def test_train_host_ms(rec):
    _add(rec, [
        (1, "train.step", 110.0, 111.0, None, 2304),
        (2, "train.dispatch", 110.02, 110.03, 1, 0),
        (3, "train.sync", 110.03, 111.0, 1, 0),
        (4, "train.step", 111.0, 113.0, None, 2304),        # compiled
        (5, "train.dispatch", 111.01, 112.9, 4, 0),
        (6, "jax.compile", 111.02, 112.5, 5, 1),
        (7, "train.sync", 112.9, 113.0, 4, 0),
        (8, "train.step", 113.0, 114.0, None, 2304),
        (9, "train.sync", 113.04, 114.0, 8, 0),
        (10, "train.step", 114.0, 115.0, None, 2304),
        (11, "train.sync", 114.02, 115.0, 10, 0),
    ])
    # steps 1, 8 and 10 host 30, 40 and 20 ms; step 4 compiled
    assert bench.read_metric("train_host_ms", _run()) == pytest.approx(30.0)


@pytest.mark.parametrize("name", sorted(READERS))
def test_none_without_rows_or_after_a_drop(monkeypatch, name):
    r = telemetry.Recorder(maxlen=2)
    monkeypatch.setattr(telemetry, "rows", r.rows)
    monkeypatch.setattr(telemetry, "dropped_since", r.dropped_since)
    assert bench.read_metric(name, _run()) is None
    step, sync = {"decode_host_ms": ("serve.decode_step",
                                     "serve.token_sync"),
                  "prefill_tokens_per_s": ("serve.prefill",
                                           "serve.token_sync"),
                  "train_host_ms": ("train.step", "train.sync")}[name]
    _add(r, [(1, step, 101.0, 101.5, None, 10),
             (2, sync, 101.2, 101.5, 1, 10)])
    assert bench.read_metric(name, _run()) is not None
    _add(r, [(3, step, 102.0, 102.5, None, 10)])      # drops row 1
    assert r.dropped == 1
    assert bench.read_metric(name, _run()) is None
    assert bench.read_metric(name, _run(t0=101.6)) is not None


@pytest.mark.parametrize("name", sorted(READERS))
def test_none_for_a_program_without_the_recorder(monkeypatch, name):
    monkeypatch.delattr(repro, "telemetry")
    monkeypatch.setitem(sys.modules, "repro.telemetry", None)
    assert bench.read_metric(name, _run()) is None


@pytest.fixture
def window(monkeypatch):
    """The runs whose window closed, captured from ``Stack.window``."""
    runs = []
    orig = stack.Stack.window

    def wrapped(self):
        orig(self)
        runs.append(self.run)
    monkeypatch.setattr(stack.Stack, "window", wrapped)
    return runs


@pytest.mark.parametrize("cell", [SERVE, TRAIN])
def test_nothing_compiles_in_the_window(window, cell):
    out = rehearse(cell)
    assert out["correct"], out["checks"]
    (run,) = window
    rows = [r for r in telemetry.rows(since=run.t0) if r.t0 < run.t1]
    assert rows and not telemetry.dropped_since(run.t0)
    assert [r for r in rows if r.name == "jax.compile"] == []
    for name, c in READERS.items():
        v = bench.read_metric(name, run)
        assert (v is not None and math.isfinite(v) and v > 0) == (c == cell)
