"""What decides ``correct``: the timed path's output against the plain
reference, each number beside its limit.

Numbers compared (each only where the cell has the layer):

- ``decisions``: 1 when the live service's placement decisions differ
  from a shadow replay of the same admitted jobs through the offline
  engine, else 0.  Exact: limit 0.
- ``restore``: leaves of the trainer's state whose bits differ between
  just before the preempt and just after the restore.  Exact: limit 0.
- ``init``: leaves whose bits differ between the trainer's weights
  before its first step and the reference's weights from the same seed.
  Exact: limit 0; it shows the two start from the same numbers.
- ``loss``: the largest relative gap between the trainer's loss and the
  reference's over the checked steps.
- ``grad``: the first step's gradient as the optimizer applies it (the
  program's worked out from its first moment after one step), worst leaf:
  |norm - reference norm| over the larger of the reference leaf's norm
  and the median leaf's.
- ``update``: the same for each leaf's change over the checked steps,
  each side from its own starting weights; leaves whose reference
  gradient is under a thousandth of the median leaf's move by round-off
  alone and are left out.
- ``served``: over a sample of finished requests drawn from the seed, the
  longest prompt among them, the widest gap by which a served token's
  logit lies below the reference's best at that position.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from .stack import Run, Stack, leaf_names

#: leaves whose reference gradient is below this share of the median
#: leaf's are left out of ``update``
STILL_LEAF = 1e-3
ADAM_B1 = 0.9


def decisions(stack: Stack) -> int:
    from repro.service import ServiceConfig, shadow_fidelity
    from repro.service.decisionlog import decision_digest
    fid = shadow_fidelity(stack.specs(), ServiceConfig(
        n_nodes=stack.svc_cfg.n_nodes, mechanism=stack.svc_cfg.mechanism))
    placed = [r for r in stack.svc.log.rows if r["event"] != "admit"]
    live = decision_digest([{**r, "seq": i} for i, r in enumerate(placed)])
    return int(not (fid.ok and live == fid.digest_reference))


def restore(run: Run) -> Optional[int]:
    a, b = run.train.pre_sum, run.train.post_sum
    if a is None:
        return None
    if b is None or a.shape != b.shape:
        return len(a)
    return int(np.sum(np.any(a != b, axis=-1)))


def _gap(prog: Dict[str, float], ref: Dict[str, float],
         keep: Optional[List[str]] = None) -> float:
    names = [n for n in ref if keep is None or n in keep]
    med = float(np.median([ref[n] for n in ref]))
    return max(abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30)
               for n in names)


def _norms(tree) -> Dict[str, float]:
    import jax
    return {n: float(np.sqrt(np.sum(np.square(np.asarray(x, np.float64)))))
            for n, x in zip(leaf_names(tree), jax.tree.leaves(tree))}


def _delta_norms(stack: Stack, after, before) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp
    diff = jax.tree.map(lambda a, b: jnp.asarray(a).astype(jnp.float32)
                        - jnp.asarray(b).astype(jnp.float32), after, before)
    return stack._leaf_norms(diff)


def training(stack: Stack, ref, sz: dict, control: bool,
             log=None) -> Tuple[dict, dict]:
    """(program's numbers, control's numbers) of the trainer's checked
    steps against the float32 reference.  ``log`` gets each leaf's
    readings."""
    run = stack.run
    t = run.trainer
    n = stack.n_check
    rec = run.train
    p0 = ref.init_weights(run.seed, sz)
    out = {"init": int(np.sum(np.any(stack._sums(p0) != rec.p0_sum,
                                      axis=-1)))}
    del p0                        # the reference's steps need the room
    r = ref.train(run.seed, n, t["batch"], t["text_len"], t["microbatches"],
                  sz)
    ref_grad = _norms(r["first_grad"])
    keep = [k for k, v in ref_grad.items()
            if v >= STILL_LEAF * np.median(list(ref_grad.values()))]
    p0 = ref.init_weights(run.seed, sz)
    ref_upd = _delta_norms(stack, r["params"], p0)
    del r["params"]
    losses = {s.idx: s.loss for s in rec.steps if s.idx <= n}
    loss_gap = max(abs(losses[i + 1] - lr) / abs(lr)
                   for i, lr in enumerate(r["losses"]))
    out["loss"] = loss_gap
    out["grad"] = _gap({k: v / (1 - ADAM_B1) for k, v in rec.grad_m.items()},
                       ref_grad)
    prog_upd = _delta_norms(stack, rec.p_check, rec.p0)
    out["update"] = _gap(prog_upd, ref_upd, keep)
    if log is not None:
        med_g = np.median(list(ref_grad.values()))
        med_u = np.median(list(ref_upd.values()))
        for k in ref_grad:
            log(f"leaf {k}: grad {rec.grad_m[k] / (1 - ADAM_B1):.6g} "
                f"ref {ref_grad[k]:.6g} (median {med_g:.6g}); change after "
                f"{n} steps {prog_upd[k]:.6g} ref {ref_upd[k]:.6g} (median "
                f"{med_u:.6g})")
    ctl = {}
    if control:
        del p0
        c = ref.train(run.seed, n, t["batch"], t["text_len"],
                      t["microbatches"], sz, prec="fp8")
        p0 = ref.init_weights(run.seed, sz)
        ctl["loss"] = max(abs(a - b) / abs(b)
                          for a, b in zip(c["losses"], r["losses"]))
        ctl["grad"] = _gap(_norms(c["first_grad"]), ref_grad)
        ctl["update"] = _gap(_delta_norms(stack, c["params"], p0), ref_upd,
                             keep)
        del c
        h = ref.train(run.seed, n, t["batch"], t["text_len"],
                      t["microbatches"], sz, half_batch=True)
        ctl["half_batch.loss"] = max(abs(a - b) / abs(b) for a, b in
                                     zip(h["losses"], r["losses"]))
        ctl["half_batch.grad"] = _gap(_norms(h["first_grad"]), ref_grad)
        ctl["half_batch.update"] = _gap(_delta_norms(stack, h["params"], p0),
                                        ref_upd, keep)
        del h
    return out, ctl


def sample(run: Run, k: int) -> List[dict]:
    """``k`` finished requests drawn from the seed, the longest prompt
    among them."""
    done = [r for r in run.requests
            if len(r.get("tokens", ())) == r["new_tokens"]]
    if not done:
        return []
    longest = max(done, key=lambda r: (r["prompt_len"], -r["rid"]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(run.seed)
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def serving(stack: Stack, ref, sz: dict, control: bool) -> Tuple[dict, dict]:
    run = stack.run
    k = run.mix["check"]["sample_requests"]
    reqs = sample(run, k)
    if not reqs:
        return {"served": float("inf")}, {}
    params = ref.init_weights(run.seed + 1, sz)
    gap, cgap, n_tok = 0.0, 0.0, 0
    for r in reqs:
        lg = ref.serve_logits(params, r["prompt"], r["tokens"],
                              stack.max_seq, sz)
        toks = np.asarray(r["tokens"])
        best = lg.max(axis=-1)
        gap = max(gap, float(np.max(best - lg[np.arange(len(toks)), toks])))
        n_tok += len(toks)
        if control:
            c = ref.serve_logits(params, r["prompt"], r["tokens"],
                                 stack.max_seq, sz, prec="fp8")
            pick = c.argmax(axis=-1)
            cgap = max(cgap, float(np.max(best - lg[np.arange(len(pick)),
                                                    pick])))
    run.od["checked_tokens"] = n_tok
    return {"served": gap}, ({"served": cgap} if control else {})


def judge(numbers: dict, limits: dict) -> Tuple[bool, Dict[str, list]]:
    """Every number against its limit; a number without a limit fails."""
    checks, ok = {}, True
    for name, value in numbers.items():
        lim = limits.get(name)
        checks[name] = [value, lim]
        if lim is None or not (value <= lim):
            ok = False
    return ok, checks
