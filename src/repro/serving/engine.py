"""Batched serving engine for on-demand jobs.

Prefill + greedy decode with a fixed-capacity KV cache: requests are
grouped into one left-padded batch (pad slots masked as keys), prefilled
once, then decoded step-by-step; finished sequences stop emitting.  This
is the execution payload of the paper's *on-demand* job class.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro import telemetry
from repro.kernels import ops as kops
from repro.models import decode_step, dist, prefill
from repro.models.config import ModelConfig


@dataclass
class Request:
    """One inference request.

    ``submitted_at`` / ``first_token_at`` / ``done_at`` are monotonic
    timestamps (``time.monotonic``): they exist to be subtracted — TTFB,
    decode time, SLO accounting — and must not jump with wall-clock
    adjustments.  ``submitted_wall`` is the one wall-clock stamp, kept
    for human-readable logs; never diff it against the monotonic fields.
    """

    rid: int
    prompt: np.ndarray            # (S,) int32
    max_new_tokens: int = 32
    submitted_at: float = field(default_factory=time.monotonic)
    submitted_wall: float = field(default_factory=time.time)
    tokens_out: List[int] = field(default_factory=list)
    first_token_at: Optional[float] = None
    done_at: Optional[float] = None


def pad_batch(prompts: Sequence[np.ndarray]) -> Tuple[np.ndarray, np.ndarray]:
    """Left-pad prompts to one length the attention kernels can tile.

    Returns (tokens (B, S) int32, kv_start (B,) int32): row i's real
    tokens fill ``tokens[i, kv_start[i]:]``, so every last token sits at
    S - 1; the model masks the pad slots before ``kv_start`` as keys.
    """
    lens = np.asarray([len(p) for p in prompts])
    S = kops.padded_len(int(lens.max()))
    toks = np.zeros((len(prompts), S), np.int32)
    for i, p in enumerate(prompts):
        toks[i, S - lens[i]:] = p
    return toks, (S - lens).astype(np.int32)


class ServeEngine:
    """Greedy batched decoding over a fixed max_seq cache.

    ``devices`` are the devices of the nodes the engine was handed: its
    params are replicated there, each batch is split over them when its
    size divides, and the cache follows the batch.  ``None`` leaves
    placement to JAX's default device.
    """

    def __init__(self, cfg: ModelConfig, params, *, max_seq: int = 512,
                 eos_id: Optional[int] = None, donate_cache: bool = True,
                 devices: Optional[Sequence] = None):
        if cfg.family not in ("dense", "moe", "vlm"):
            raise NotImplementedError(
                "ServeEngine drives attention-family LMs; recurrent archs "
                "serve via decode_step directly")
        self.cfg = cfg
        self.max_seq = kops.padded_len(max_seq)
        self.eos_id = eos_id
        self.mesh: Optional[Mesh] = None
        if devices is not None:
            devs = list(devices)
            self.mesh = Mesh(np.asarray(devs).reshape(len(devs), 1),
                             ("data", "model"))
            params = jax.device_put(params, NamedSharding(self.mesh, P()))
        self.params = params
        self._n_batches = 0
        self._prefill = jax.jit(
            lambda p, t, st: prefill(p, t, cfg, kv_start=st))
        self._decode = jax.jit(
            lambda p, c, t, pos, st: decode_step(p, c, t, pos, cfg,
                                                 kv_start=st),
            donate_argnums=(1,) if donate_cache else ())

    @contextlib.contextmanager
    def _placed(self):
        """Trace and run under this engine's mesh (the model's sharding
        constraints read it), restoring the caller's afterwards."""
        prev = (dist.get_mesh(), dist.batch_axes())
        dist.set_mesh(self.mesh, ("data",))
        try:
            yield
        finally:
            dist.set_mesh(*prev)

    def _put(self, x: np.ndarray):
        if self.mesh is None:
            return jnp.asarray(x)
        n = self.mesh.shape["data"]
        spec = P("data") if x.shape[0] % n == 0 else P()
        return jax.device_put(x, NamedSharding(self.mesh, spec))

    def _start(self, prompts):
        """Prefill a padded batch; returns (first tokens, cache, kv_start,
        prompt length, last-position logits)."""
        toks, kv_start = pad_batch(prompts)
        S = toks.shape[1]
        if S > self.max_seq:
            raise ValueError(f"padded prompt length {S} exceeds the "
                             f"{self.max_seq}-slot cache")
        kv_start = self._put(kv_start)
        logits, cache = self._prefill(self.params, self._put(toks), kv_start)
        cache = jax.tree.map(lambda c: _grow(c, self.max_seq), cache)
        next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, cache, kv_start, S, logits

    def serve_batch(self, requests: List[Request]) -> List[Request]:
        """Run a padded batch of requests to completion.  Records the
        spans ``serve.batch``, ``serve.prefill`` and one
        ``serve.decode_step`` per step, keyed by the batch's number."""
        self._n_batches += 1
        B = len(requests)
        with self._placed(), telemetry.span(
                "serve.batch", key=self._n_batches, n=B):
            n_prompt = sum(len(r.prompt) for r in requests)
            with telemetry.span("serve.prefill", n=n_prompt) as pre:
                next_tok, cache, kv_start, S, _ = self._start(
                    [r.prompt for r in requests])
                with telemetry.span("serve.token_sync", n=B):
                    toks_np = np.asarray(next_tok)  # waits for the device
            for i, r in enumerate(requests):
                r.first_token_at = pre.t1
                r.tokens_out.append(int(toks_np[i]))
            telemetry.count("serve.prompt_tokens", n_prompt)
            telemetry.count("serve.padded_tokens", B * S)
            live = np.ones((B,), bool)
            n_live, n_out, n_steps = B, B, 0
            for step in range(1, max(r.max_new_tokens for r in requests)):
                pos = S + step - 1
                if pos >= self.max_seq:
                    break
                n_steps += 1
                with telemetry.span("serve.decode_step", n=n_live):
                    with telemetry.span("serve.dispatch"):
                        logits, cache = self._decode(
                            self.params, cache, next_tok[:, None], pos,
                            kv_start)
                        next_tok = jnp.argmax(logits, axis=-1).astype(
                            jnp.int32)
                    with telemetry.span("serve.token_sync", n=B):
                        toks_np = np.asarray(next_tok)
                    for i, r in enumerate(requests):
                        if not live[i]:
                            continue
                        r.tokens_out.append(int(toks_np[i]))
                        n_out += 1
                        if len(r.tokens_out) >= r.max_new_tokens or \
                                (self.eos_id is not None
                                 and toks_np[i] == self.eos_id):
                            live[i] = False
                            n_live -= 1
                            r.done_at = time.monotonic()
                if not n_live:
                    break
            telemetry.count("serve.tokens_out", n_out)
            telemetry.count("serve.decode_slots", B * n_steps)
        now = time.monotonic()
        for r in requests:
            r.done_at = r.done_at or now
        return requests

    def step_logits(self, prompt: np.ndarray,
                    continuation: Sequence[int]) -> np.ndarray:
        """Float32 logits (1 + len(continuation), vocab) of one request:
        after its prefill, then after each given continuation token
        (teacher-forced, so two attention implementations are compared
        on the same inputs even where their greedy picks would differ)."""
        with self._placed():
            _, cache, kv_start, S, logits = self._start([prompt])
            out = [np.asarray(logits[0], np.float32)]
            for step, tok in enumerate(continuation):
                logits, cache = self._decode(
                    self.params, cache, self._put(np.full((1, 1), tok,
                                                          np.int32)),
                    S + step, kv_start)
                out.append(np.asarray(logits[0], np.float32))
        return np.stack(out)


def _grow(c, max_seq: int):
    """Pad a prefill-sized cache array out to max_seq slots.  Every cache
    the engine serves (GQA k/v and MLA latents) is (L, B, S, ...)."""
    pad = [(0, 0)] * c.ndim
    pad[2] = (0, max_seq - c.shape[2])
    return jnp.pad(c, pad)
