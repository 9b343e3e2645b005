"""Sweeps-on-device: jitted JAX ports of the decision kernels.

The numpy kernels in :mod:`repro.core.decision` are the bit-for-bit
references; this module recasts each of them as a **fixed-shape padded**
JAX kernel (mask-padded est-end/size arrays, ``jnp.where`` sentinels
instead of ragged inputs) so they jit cleanly and `vmap` across
(mechanism x scenario x seed) sweep cells.  :func:`run_device_sweep`
replays every decision a whole `Experiment` grid captured (see
:func:`repro.core.decision.capture`) as **one device program** — a
single jitted call evaluating every captured decision of every cell —
and parity-checks the device outputs against the recorded numpy
results.  Process fan-out stays the identity baseline: the numbers the
sweep reports come from the numpy engine, the device program must
reproduce its decisions job for job.

Numerical contract (documented in docs/performance.md):

* ``dtype="float64"`` (the default, and the parity gate): inputs are
  float64/int64, traced inside the scoped :func:`jax.enable_x64`, and
  every kernel is **exactly** equal to its numpy reference — the same
  IEEE expressions over the same operands, including stable sort order.
  Not on a TPU: the chip has no native float64, its emulation rounds
  differently from IEEE float64, and requesting float64 there raises.
* ``dtype="float32"`` (the TPU contract, :func:`device_dtype`): inputs
  round to float32/int32.  Continuous
  outputs (``t_shadow``) agree within ``FLOAT32_RTOL``; discrete
  outputs (victim sets, sheds, filter masks) may legitimately differ
  where rounding crosses a comparison or reorders a sort, but the
  structural invariants still hold (sheds sum exactly to ``need`` and
  respect per-job slack; victim prefixes cover ``need``).

Padding contract: valid entries occupy a prefix of each row, the mask
marks them, and padded lanes carry identity sentinels (size 0,
est-end/overhead/need ``+inf``) that cannot alter a cumsum, win a sort
tie against a valid lane, or pass a filter.  Est-end bases and
overheads must be finite for valid lanes (the simulator's always are);
``+inf`` need_mins (on-demand jobs) are fine — they are compared, never
summed.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .decision import DecisionTrace

#: documented float32 tolerance for continuous outputs (t_shadow): the
#: selected release time is one of the float32-rounded inputs, so it can
#: differ from the float64 pick by at most ~1 ulp of the input scale —
#: unless two releases are closer than that, in which case either is a
#: correct answer and the parity suite only checks feasibility.
FLOAT32_RTOL = 1e-6


def device_dtype() -> str:
    """The replay dtype whose parity contract holds on JAX's default
    backend: exact float64, except on a TPU, where it is float32."""
    return "float32" if jax.default_backend() == "tpu" else "float64"


def _dtypes(dtype: str):
    if dtype == "float64":
        if jax.default_backend() == "tpu":
            # measured on a TPU v5e: 2,272 of the 9,904 decisions of a
            # Theta-scale grid came back with t_shadow off by a few ulps
            raise ValueError(
                "float64 decision replay is not exact on a TPU: the chip "
                "has no native float64 and its emulation rounds "
                "differently from IEEE float64; use dtype='float32', "
                "whose FLOAT32_RTOL contract holds there")
        return jnp.float64, jnp.int64
    if dtype == "float32":
        return jnp.float32, jnp.int32
    raise ValueError(f"dtype must be 'float64' or 'float32', got {dtype!r}")


# ---------------------------------------------------------------- kernels
# Fixed-shape, jit-compatible, vmappable.  Each mirrors the numpy
# reference expression-for-expression; comments call out only where the
# padding changes the derivation.

def _easy_shadow_kernel(avail, need, bases, sizes, valid, now):
    P = bases.shape[0]
    inf = jnp.asarray(jnp.inf, bases.dtype)
    ends = jnp.where(valid, jnp.maximum(bases, now), inf)
    szs = jnp.where(valid, sizes, 0)
    order = jnp.lexsort((szs, ends))
    ends_s = ends[order]
    csum = avail + jnp.cumsum(szs[order])
    i = jnp.searchsorted(csum, need)
    # padded lanes keep csum at the total supply, so a crossing (if any)
    # happens at a valid lane: i < n_valid <=> the numpy i < len(csum)
    found = i < jnp.sum(valid)
    ic = jnp.clip(i, 0, P - 1)
    covered_now = avail >= need
    t = jnp.where(covered_now, jnp.asarray(now, bases.dtype),
                  jnp.where(found, ends_s[ic], inf))
    extra = jnp.where(covered_now, avail - need,
                      jnp.where(found, csum[ic] - need, 0))
    return t, extra


def _victims_kernel(sizes, overheads, valid, need):
    P = sizes.shape[0]
    szs = jnp.where(valid, sizes, 0)
    over = jnp.where(valid, overheads, jnp.asarray(jnp.inf, overheads.dtype))
    order = jnp.argsort(over, stable=True)
    csum = jnp.cumsum(szs[order])
    supply = csum[P - 1]
    cut = jnp.searchsorted(csum, need) + 1
    ok = (need > 0) & (supply >= need)
    k = jnp.where(ok, cut, 0)
    surplus = jnp.where(ok, csum[jnp.clip(cut - 1, 0, P - 1)] - need, 0)
    return order, k, surplus


def _apportion_kernel(cur, mn, valid, need):
    P = cur.shape[0]
    slack = jnp.where(valid, jnp.maximum(cur - mn, 0), 0)
    supply = jnp.sum(slack)
    ok = (supply >= need) & (need > 0)
    supply_s = jnp.where(supply > 0, supply, 1)
    # mirror the numpy overflow guard: the exact-product expression is
    # bit-identical whenever need * max(slack) fits the int dtype; the
    # wrapped product computed on the overflow branch is discarded
    max_slack = jnp.maximum(jnp.max(slack, initial=0), 1)
    imax = jnp.iinfo(slack.dtype).max
    overflow = (jnp.max(slack, initial=0) > 0) & (need > imax // max_slack)
    quota = jnp.where(overflow, need * (slack / supply_s),
                      (need * slack) / supply_s)
    base = jnp.clip(jnp.floor(quota).astype(slack.dtype), 0, slack)
    base = jnp.where(ok, base, 0)
    short0 = jnp.where(ok, need - jnp.sum(base), 0)
    neg_inf = jnp.asarray(-jnp.inf, quota.dtype)

    # largest-remainder rounds, one node per eligible job per round —
    # the same iteration the hardened numpy reference runs
    def grow(carry):
        base, short = carry
        eligible = slack > base
        frac = jnp.where(eligible, quota - base, neg_inf)
        order = jnp.argsort(-frac, stable=True)
        take = jnp.minimum(short, jnp.sum(eligible).astype(short.dtype))
        inc = (jnp.arange(P) < take).astype(base.dtype)
        return base.at[order].add(inc), short - take

    base, _ = jax.lax.while_loop(lambda c: c[1] > 0, grow, (base, short0))

    # float32 only: rounded-up quotas can overshoot (floor lands above
    # the exact float64 floor), leaving short0 < 0; retract from the
    # most over-granted jobs so the sum is exact in every dtype
    def shrink(carry):
        base, short = carry
        granted = base > 0
        frac = jnp.where(granted, quota - base, -neg_inf)
        order = jnp.argsort(frac, stable=True)
        take = jnp.minimum(-short, jnp.sum(granted).astype(short.dtype))
        dec = (jnp.arange(P) < take).astype(base.dtype)
        return base.at[order].add(-dec), short + take

    base, _ = jax.lax.while_loop(lambda c: c[1] < 0, shrink, (base, short0))
    return ok, base


def _prefilter_kernel(needs, valid, bound):
    return valid & (needs <= bound)


def _shadow_filter_kernel(needs_c, ests_c, valid, budget, now, t_shadow):
    return valid & ((needs_c <= budget) | (now + ests_c <= t_shadow))


def _sweep_program(batches):
    """The whole grid's decisions in one jitted call.

    ``batches`` is a dict keyed by kernel name whose presence/shapes are
    static (part of the pytree structure), so one call compiles to one
    XLA program evaluating every captured decision of every cell."""
    out = {}
    if "easy_shadow" in batches:
        b = batches["easy_shadow"]
        out["easy_shadow"] = jax.vmap(_easy_shadow_kernel)(
            b["avail"], b["need"], b["bases"], b["sizes"], b["valid"],
            b["now"])
    if "select_preemption_victims" in batches:
        b = batches["select_preemption_victims"]
        out["select_preemption_victims"] = jax.vmap(_victims_kernel)(
            b["sizes"], b["overheads"], b["valid"], b["need"])
    if "apportion_shrink" in batches:
        b = batches["apportion_shrink"]
        out["apportion_shrink"] = jax.vmap(_apportion_kernel)(
            b["cur"], b["mn"], b["valid"], b["need"])
    if "backfill_prefilter" in batches:
        b = batches["backfill_prefilter"]
        out["backfill_prefilter"] = jax.vmap(_prefilter_kernel)(
            b["needs"], b["valid"], b["bound"])
    if "backfill_shadow_filter" in batches:
        b = batches["backfill_shadow_filter"]
        out["backfill_shadow_filter"] = jax.vmap(_shadow_filter_kernel)(
            b["needs"], b["ests"], b["valid"], b["budget"], b["now"],
            b["t_shadow"])
    return out


_sweep_program_jit = jax.jit(_sweep_program)

# module-level jitted single-call variants: the jit cache is keyed on the
# wrapper object, so these must be created once (a fresh jax.jit per call
# would retrace every time)
_easy_shadow_jit = jax.jit(_easy_shadow_kernel)
_victims_jit = jax.jit(_victims_kernel)
_apportion_jit = jax.jit(_apportion_kernel)
_prefilter_jit = jax.jit(_prefilter_kernel)
_shadow_filter_jit = jax.jit(_shadow_filter_kernel)


# ------------------------------------------------- single-call wrappers
# Same signatures and return conventions as the numpy kernels — these
# are what the parity suite drives directly.

def _pad(arr, P, fill, fdt):
    a = np.asarray(arr, dtype=fdt)
    out = np.full(P, fill, dtype=fdt)
    out[:a.size] = a
    return out


def easy_shadow_jax(avail: int, need: int, est_end_bases, sizes, now: float,
                    dtype: str = "float64") -> Tuple[float, int]:
    fdt, idt = _dtypes(dtype)
    n = len(est_end_bases)
    P = max(n, 1)
    with jax.enable_x64(dtype == "float64"):
        t, extra = _easy_shadow_jit(
            jnp.asarray(avail, idt), jnp.asarray(need, idt),
            jnp.asarray(_pad(est_end_bases, P, np.inf, fdt)),
            jnp.asarray(_pad(sizes, P, 0, idt)),
            jnp.arange(P) < n, jnp.asarray(now, fdt))
        return float(t), int(extra)


def select_preemption_victims_jax(sizes, overheads, need: int,
                                  dtype: str = "float64"
                                  ) -> Tuple[List[int], int]:
    fdt, idt = _dtypes(dtype)
    n = len(sizes)
    P = max(n, 1)
    with jax.enable_x64(dtype == "float64"):
        order, k, surplus = _victims_jit(
            jnp.asarray(_pad(sizes, P, 0, idt)),
            jnp.asarray(_pad(overheads, P, np.inf, fdt)),
            jnp.arange(P) < n, jnp.asarray(need, idt))
        return [int(i) for i in np.asarray(order)[:int(k)]], int(surplus)


def apportion_shrink_jax(cur_sizes, min_sizes, need: int,
                         dtype: str = "float64") -> List[int]:
    fdt, idt = _dtypes(dtype)
    n = len(cur_sizes)
    P = max(n, 1)
    if need <= 0:
        return [0] * n
    with jax.enable_x64(dtype == "float64"):
        ok, base = _apportion_jit(
            jnp.asarray(_pad(cur_sizes, P, 0, idt)),
            jnp.asarray(_pad(min_sizes, P, 0, idt)),
            jnp.arange(P) < n, jnp.asarray(need, idt))
        if not bool(ok):
            return []
        return [int(x) for x in np.asarray(base)[:n]]


def backfill_prefilter_jax(need_mins, supply_bound: float,
                           dtype: str = "float64") -> np.ndarray:
    fdt, _idt = _dtypes(dtype)
    n = len(need_mins)
    P = max(n, 1)
    with jax.enable_x64(dtype == "float64"):
        mask = _prefilter_jit(
            jnp.asarray(_pad(need_mins, P, np.inf, fdt)),
            jnp.arange(P) < n, jnp.asarray(supply_bound, fdt))
        return np.flatnonzero(np.asarray(mask)[:n])


def backfill_shadow_filter_jax(need_mins, est_remainings, candidates,
                               spare_budget: int, now: float,
                               t_shadow: float,
                               dtype: str = "float64") -> np.ndarray:
    fdt, idt = _dtypes(dtype)
    cand = np.asarray(candidates)
    needs_c = np.asarray(need_mins, dtype=np.float64)[cand]
    ests_c = np.asarray(est_remainings, dtype=np.float64)[cand]
    n = cand.size
    P = max(n, 1)
    with jax.enable_x64(dtype == "float64"):
        mask = _shadow_filter_jit(
            jnp.asarray(_pad(needs_c, P, np.inf, fdt)),
            jnp.asarray(_pad(ests_c, P, np.inf, fdt)),
            jnp.arange(P) < n, jnp.asarray(spare_budget, idt),
            jnp.asarray(now, fdt), jnp.asarray(t_shadow, fdt))
        return cand[np.asarray(mask)[:n]]


# --------------------------------------------- batched grid evaluation
@dataclass
class DeviceSweepReport:
    """What one batched device replay of a sweep grid proved."""

    n_cells: int
    n_calls: int
    calls_per_kernel: Dict[str, int]
    pad_per_kernel: Dict[str, int]
    n_dropped: int                      # calls beyond each cell's capture cap
    dtype: str
    parity_ok: bool
    #: (cell label, kernel, call index, expected, got) — first N only
    mismatches: List[tuple] = field(default_factory=list)
    n_mismatches: int = 0
    build_s: float = 0.0                # host-side padding/stacking
    compile_s: float = 0.0              # first program call (trace+compile)
    device_s: float = 0.0               # steady-state program execution
    n_programs: int = 1                 # always 1: the whole grid is one call

    @property
    def device_us_per_call(self) -> float:
        return 1e6 * self.device_s / max(self.n_calls, 1)

    def summary(self) -> dict:
        return {"n_cells": self.n_cells, "n_calls": self.n_calls,
                "calls_per_kernel": dict(self.calls_per_kernel),
                "pad_per_kernel": dict(self.pad_per_kernel),
                "n_dropped": self.n_dropped, "dtype": self.dtype,
                "parity_ok": self.parity_ok,
                "n_mismatches": self.n_mismatches,
                "n_programs": self.n_programs,
                "build_s": round(self.build_s, 4),
                "compile_s": round(self.compile_s, 4),
                "device_s": round(self.device_s, 6),
                "device_us_per_call": round(self.device_us_per_call, 3)}


def _build_batches(cells: Sequence[Tuple[object, DecisionTrace]],
                   dtype: str):
    """Stack every captured call of every cell into per-kernel padded
    batches.  Returns (numpy batches, per-kernel index lists of
    (cell_label, call_idx, inputs, expected_output))."""
    fdt_np = np.float64 if dtype == "float64" else np.float32
    idt_np = np.int64 if dtype == "float64" else np.int32
    index: Dict[str, list] = {k: [] for k in DecisionTrace.KERNELS}
    for label, trace in cells:
        for kernel, calls in trace.calls.items():
            for ci, (inputs, output) in enumerate(calls):
                index[kernel].append((label, ci, inputs, output))
    batches: Dict[str, Dict[str, np.ndarray]] = {}
    pads: Dict[str, int] = {}

    def stack(rows, P, fill, dt):
        out = np.full((len(rows), P), fill, dtype=dt)
        for i, r in enumerate(rows):
            a = np.asarray(r, dtype=dt)
            out[i, :a.size] = a
        return out

    def masks(lens, P):
        return np.arange(P)[None, :] < np.asarray(lens)[:, None]

    rows = index["easy_shadow"]
    if rows:
        P = max(max(len(inp[2]) for _, _, inp, _ in rows), 1)
        pads["easy_shadow"] = P
        batches["easy_shadow"] = {
            "avail": np.asarray([inp[0] for _, _, inp, _ in rows], idt_np),
            "need": np.asarray([inp[1] for _, _, inp, _ in rows], idt_np),
            "bases": stack([inp[2] for _, _, inp, _ in rows], P, np.inf,
                           fdt_np),
            "sizes": stack([inp[3] for _, _, inp, _ in rows], P, 0, idt_np),
            "valid": masks([len(inp[2]) for _, _, inp, _ in rows], P),
            "now": np.asarray([inp[4] for _, _, inp, _ in rows], fdt_np)}
    rows = index["select_preemption_victims"]
    if rows:
        P = max(max(len(inp[0]) for _, _, inp, _ in rows), 1)
        pads["select_preemption_victims"] = P
        batches["select_preemption_victims"] = {
            "sizes": stack([inp[0] for _, _, inp, _ in rows], P, 0, idt_np),
            "overheads": stack([inp[1] for _, _, inp, _ in rows], P, np.inf,
                               fdt_np),
            "valid": masks([len(inp[0]) for _, _, inp, _ in rows], P),
            "need": np.asarray([inp[2] for _, _, inp, _ in rows], idt_np)}
    rows = index["apportion_shrink"]
    if rows:
        P = max(max(len(inp[0]) for _, _, inp, _ in rows), 1)
        pads["apportion_shrink"] = P
        batches["apportion_shrink"] = {
            "cur": stack([inp[0] for _, _, inp, _ in rows], P, 0, idt_np),
            "mn": stack([inp[1] for _, _, inp, _ in rows], P, 0, idt_np),
            "valid": masks([len(inp[0]) for _, _, inp, _ in rows], P),
            "need": np.asarray([inp[2] for _, _, inp, _ in rows], idt_np)}
    rows = index["backfill_prefilter"]
    if rows:
        P = max(max(len(inp[0]) for _, _, inp, _ in rows), 1)
        pads["backfill_prefilter"] = P
        batches["backfill_prefilter"] = {
            "needs": stack([inp[0] for _, _, inp, _ in rows], P, np.inf,
                           fdt_np),
            "valid": masks([len(inp[0]) for _, _, inp, _ in rows], P),
            "bound": np.asarray([inp[1] for _, _, inp, _ in rows], fdt_np)}
    rows = index["backfill_shadow_filter"]
    if rows:
        P = max(max(len(inp[0]) for _, _, inp, _ in rows), 1)
        pads["backfill_shadow_filter"] = P
        batches["backfill_shadow_filter"] = {
            "needs": stack([inp[0] for _, _, inp, _ in rows], P, np.inf,
                           fdt_np),
            "ests": stack([inp[1] for _, _, inp, _ in rows], P, np.inf,
                          fdt_np),
            "valid": masks([len(inp[0]) for _, _, inp, _ in rows], P),
            "budget": np.asarray([inp[3] for _, _, inp, _ in rows], idt_np),
            "now": np.asarray([inp[4] for _, _, inp, _ in rows], fdt_np),
            "t_shadow": np.asarray([inp[5] for _, _, inp, _ in rows],
                                   fdt_np)}
    return batches, index, pads


def _check_parity(kernel: str, rows, outs, exact: bool) -> List[tuple]:
    """Compare one kernel's device outputs to the recorded numpy outputs.
    ``exact`` (float64) demands equality; float32 checks the documented
    tolerance/invariants instead."""
    bad = []
    if kernel == "easy_shadow":
        t_b, extra_b = (np.asarray(o) for o in outs)
        for i, (label, ci, inp, expected) in enumerate(rows):
            t, extra = float(t_b[i]), int(extra_b[i])
            et, eextra = expected
            if exact:
                ok = (t == et or (np.isinf(t) and np.isinf(et))) \
                    and extra == eextra
            else:
                ok = (np.isinf(t) and np.isinf(et)) or \
                    (np.isfinite(t) and np.isfinite(et)
                     and abs(t - et) <= FLOAT32_RTOL * max(abs(et), 1.0))
            if not ok:
                bad.append((label, kernel, ci, expected, (t, extra)))
    elif kernel == "select_preemption_victims":
        order_b, k_b, surplus_b = (np.asarray(o) for o in outs)
        for i, (label, ci, inp, expected) in enumerate(rows):
            victims = [int(x) for x in order_b[i, :int(k_b[i])]]
            got = (victims, int(surplus_b[i]))
            if exact:
                ok = got == expected
            else:
                sizes, _over, need = inp
                covered = sum(int(sizes[v]) for v in victims) - got[1]
                ok = (not victims and not expected[0]) or \
                    (bool(victims) and covered == need)
            if not ok:
                bad.append((label, kernel, ci, expected, got))
    elif kernel == "apportion_shrink":
        ok_b, base_b = (np.asarray(o) for o in outs)
        for i, (label, ci, inp, expected) in enumerate(rows):
            cur, mn, need = inp
            n = len(cur)
            if need <= 0:
                got: List[int] = [0] * n
            elif not bool(ok_b[i]):
                got = []
            else:
                got = [int(x) for x in base_b[i, :n]]
            if exact:
                ok = got == expected
            else:
                slack = np.maximum(np.asarray(cur) - np.asarray(mn), 0)
                ok = (got == [] and expected == []) or \
                    (sum(got) == (need if need > 0 else 0)
                     and all(0 <= g <= s for g, s in zip(got, slack)))
            if not ok:
                bad.append((label, kernel, ci, expected, got))
    elif kernel == "backfill_prefilter":
        mask_b = np.asarray(outs)
        for i, (label, ci, inp, expected) in enumerate(rows):
            n = len(inp[0])
            got = np.flatnonzero(mask_b[i, :n])
            if not np.array_equal(got, expected):
                bad.append((label, kernel, ci, expected.tolist(),
                            got.tolist()))
    elif kernel == "backfill_shadow_filter":
        mask_b = np.asarray(outs)
        for i, (label, ci, inp, expected) in enumerate(rows):
            cand = inp[2]
            got = np.asarray(cand)[mask_b[i, :len(cand)]]
            if not np.array_equal(got, expected):
                bad.append((label, kernel, ci, expected.tolist(),
                            got.tolist()))
    return bad


def run_device_sweep(cells: Sequence[Tuple[object, DecisionTrace]],
                     dtype: str = "float64",
                     max_mismatches: int = 20,
                     repeats: int = 3) -> DeviceSweepReport:
    """Replay every cell's captured decision stream as ONE device program
    and parity-check it against the recorded numpy outputs.

    ``cells`` is a sequence of (label, DecisionTrace); the float64 mode
    demands exact equality (the sweep gate), float32 checks the
    documented tolerance.  ``repeats`` re-runs the compiled program and
    keeps the fastest execution for ``device_s``.
    """
    _dtypes(dtype)  # validate early
    t0 = time.perf_counter()
    batches_np, index, pads = _build_batches(cells, dtype)
    n_calls = sum(len(v) for v in index.values())
    calls_per_kernel = {k: len(v) for k, v in index.items() if v}
    n_dropped = sum(sum(t.n_dropped.values()) for _, t in cells)
    build_s = time.perf_counter() - t0
    if not batches_np:
        return DeviceSweepReport(
            n_cells=len(cells), n_calls=0, calls_per_kernel={},
            pad_per_kernel={}, n_dropped=n_dropped, dtype=dtype,
            parity_ok=True, build_s=build_s, compile_s=0.0, device_s=0.0)
    with jax.enable_x64(dtype == "float64"):
        batches = jax.tree_util.tree_map(jnp.asarray, batches_np)
        t0 = time.perf_counter()
        outs = _sweep_program_jit(batches)
        jax.block_until_ready(outs)
        compile_s = time.perf_counter() - t0
        device_s = compile_s
        for _ in range(max(repeats - 1, 0)):
            t0 = time.perf_counter()
            outs = _sweep_program_jit(batches)
            jax.block_until_ready(outs)
            device_s = min(device_s, time.perf_counter() - t0)
        outs = jax.device_get(outs)
    mismatches: List[tuple] = []
    for kernel, rows in index.items():
        if rows:
            mismatches += _check_parity(kernel, rows, outs[kernel],
                                        exact=dtype == "float64")
    return DeviceSweepReport(
        n_cells=len(cells), n_calls=n_calls,
        calls_per_kernel=calls_per_kernel, pad_per_kernel=pads,
        n_dropped=n_dropped, dtype=dtype, parity_ok=not mismatches,
        mismatches=mismatches[:max_mismatches],
        n_mismatches=len(mismatches), build_s=build_s,
        compile_s=compile_s, device_s=device_s)
