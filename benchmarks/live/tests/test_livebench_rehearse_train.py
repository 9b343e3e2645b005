"""CPU rehearsal of the train_steady cell, and the faults a training cell
can have: a step that returns its state unchanged, and half of the batch
left out with the mean taken over the rest.  (No cell spans chips yet,
so no exchange between chips can be left out.)"""

from livebench_rehearsal import control_fails, rehearse

CELL = "live1.internvl2_1b.train_steady"


def test_train_steady_rehearsal_is_correct_and_its_control_is_not():
    out = rehearse(CELL, control=True)
    assert out["correct"], out["checks"]
    assert set(out["checks"]) == {"decisions", "init", "loss", "grad",
                                  "update"}
    assert control_fails(out), out["control"]


def _failed(out) -> set:
    return {k for k, (v, lim) in out["checks"].items() if not v <= lim}


def _break_step(monkeypatch, wrap):
    from repro.runtime import elastic
    make = elastic.make_train_step

    def broken(*a, **kw):
        return wrap(make(*a, **kw))
    monkeypatch.setattr(elastic, "make_train_step", broken)


def test_a_step_that_keeps_its_state_is_not_correct(monkeypatch):
    def wrap(step):
        def f(state, batch):
            _, metrics = step(state, batch)
            return state, metrics
        return f
    _break_step(monkeypatch, wrap)
    out = rehearse(CELL)
    assert not out["correct"]
    assert _failed(out) >= {"grad", "update"}


def test_half_the_batch_left_out_is_not_correct(monkeypatch):
    import jax
    import jax.numpy as jnp

    def wrap(step):
        def f(state, batch):
            def half(x):
                n = x.shape[0] // 2
                return jnp.concatenate([x[:n], x[:n]])
            return step(state, jax.tree.map(half, batch))
        return f
    _break_step(monkeypatch, wrap)
    out = rehearse(CELL)
    assert not out["correct"]
    assert _failed(out) & {"grad", "update"}, out["checks"]
