"""Elastic job runtime: the execution half of the paper's job classes.

An ElasticJob owns a training job's full state and implements the five
operations the scheduler issues (paper §I: "start, preemption, shrink,
expansion" + resume):

  start(devices)        jit + (init | restore) onto a mesh over `devices`
  step(batch?)          one train step (auto data pipeline)
  preempt(warning)      malleable: 2-min-warning checkpoint at the exact
                        step; rigid: fall back to the last periodic ckpt.
                        Device arrays are freed once a checkpoint exists
                        to resume from, and kept otherwise
  shrink/expand(devs)   re-shard the *live* train state onto a different
                        mesh (checkpoint-free elastic resize)
  resume(devices)       start() from the persisted checkpoint

Re-sharding uses jax.device_put with the new mesh's NamedShardings;
``resize`` waits for it and returns the time taken — the measured cost of
the paper's "negligible" malleable resize assumption.

Each operation records its spans (``repro.telemetry``), keyed by the job's
id: ``train.step`` (``train.batch``, ``train.place``, ``train.dispatch``,
``train.sync``; an MoE model's ``moe.rows`` and ``moe.rows_max`` after
it, each a counter and a row over the step),
``elastic.preempt`` (the checkpoint's ``ckpt.*`` spans,
``elastic.free``), ``elastic.resume`` (``elastic.jit``, ``ckpt.load``,
``ckpt.place``) and ``elastic.resize``.
"""
from __future__ import annotations

import os
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from repro import telemetry
from repro.models import init_params, set_mesh
from repro.models.config import ModelConfig
from repro.models.model import MOE_METRICS
from repro.sharding import batch_axes, batch_sharding, tree_shardings
from repro.training import (AdamW, checkpoint, make_train_state,
                            make_train_step, synthetic_batch)
from .straggler import StragglerMonitor


class ElasticJob:
    def __init__(self, jid: int, cfg: ModelConfig, *, kind: str = "malleable",
                 batch: int = 8, seq: int = 128, opt: Optional[AdamW] = None,
                 ckpt_dir: Optional[str] = None, ckpt_every: int = 50,
                 seed: int = 0):
        assert kind in ("rigid", "malleable")
        self.jid = jid
        self.cfg = cfg
        self.kind = kind
        self.batch = batch
        self.seq = seq
        self.opt = opt or AdamW(warmup=10, total_steps=10_000)
        self.ckpt_dir = ckpt_dir
        self.ckpt_every = ckpt_every
        self.seed = seed
        self.step_idx = 0
        self.state = None
        self.mesh: Optional[Mesh] = None
        self.devices: Sequence = ()
        self.monitor = StragglerMonitor()
        self._step_fn = None

    # ------------------------------------------------------------------ mesh
    def _build(self, devices: Sequence) -> Mesh:
        n = len(devices)
        mesh = Mesh(np.asarray(devices).reshape(n, 1), ("data", "model"))
        return mesh

    def _jit(self):
        set_mesh(self.mesh, batch_axes(self.mesh))
        step = make_train_step(self.cfg, self.opt,
                               microbatches=self.cfg.train_microbatches)
        self._step_fn = jax.jit(step, donate_argnums=(0,))

    # ----------------------------------------------------------------- start
    def _init_state(self):
        return make_train_state(
            init_params(jax.random.PRNGKey(self.seed), self.cfg), self.opt)

    def start(self, devices: Sequence) -> None:
        self.devices = list(devices)
        self.mesh = self._build(self.devices)
        self._jit()
        if self.state is None:
            # initialise straight onto the mesh: no staging copy on the
            # default device, which may belong to another job
            sh = tree_shardings(jax.eval_shape(self._init_state), self.cfg,
                                self.mesh)
            self.state = jax.jit(self._init_state, out_shardings=sh)()
        else:
            self._reshard()

    def resume(self, devices: Sequence) -> None:
        """Restore the newest checkpoint straight onto ``devices``; a job
        preempted before its first checkpoint kept its state and restarts
        from that."""
        assert self.ckpt_dir is not None
        step = checkpoint.latest_step(self.ckpt_dir)
        if step is None:
            return self.start(devices)
        with telemetry.span("elastic.resume", key=self.jid) as sp:
            self.devices = list(devices)
            self.mesh = self._build(self.devices)
            with telemetry.span("elastic.jit"):
                self._jit()
            self._free()
            template = jax.eval_shape(self._init_state)
            self.state = checkpoint.restore(
                self.ckpt_dir, template, step=step,
                shardings=tree_shardings(template, self.cfg, self.mesh))
            sp.n = os.path.getsize(checkpoint.step_file(self.ckpt_dir, step))
        self.step_idx = step

    # ------------------------------------------------------------------ step
    def next_batch(self):
        """The next step's synthetic batch, placed on the job's mesh."""
        with telemetry.span("train.batch"):
            batch = synthetic_batch(self.cfg, self.batch, self.seq,
                                    seed=self.seed, step=self.step_idx)
        with telemetry.span("train.place"):
            return jax.device_put(batch, batch_sharding(batch, self.mesh))

    def step(self) -> dict:
        """One train step, ending in the metrics' host sync.  The
        straggler monitor reads the ``train.step`` span's duration."""
        tokens = self.batch * (self.seq + (
            self.cfg.n_patches if self.cfg.family == "vlm" else 0))
        with telemetry.span("train.step", key=self.jid, n=tokens) as sp:
            batch = self.next_batch()
            # tracing happens on the first call after (re)jit: the sharding-
            # constraint mesh context must be THIS job's mesh at that moment
            set_mesh(self.mesh, batch_axes(self.mesh))
            with telemetry.span("train.dispatch"), self.mesh:
                self.state, metrics = self._step_fn(self.state, batch)
            with telemetry.span("train.sync"):
                metrics = {k: float(v) for k, v in metrics.items()}
        telemetry.count("train.tokens", tokens)
        for k in MOE_METRICS:
            if k in metrics:
                # a row over the step too, so a window's share is readable
                name = k.replace("_", ".", 1)
                telemetry.count(name, metrics[k])
                telemetry.record(name, sp.t0, sp.t1, key=self.jid,
                                 n=metrics[k])
        self.step_idx += 1
        self.monitor.observe(sp.t1 - sp.t0)
        if self.ckpt_dir and self.step_idx % self.ckpt_every == 0:
            self.checkpoint()
        return metrics

    def checkpoint(self) -> str:
        """Write the state at this step; returns the file written."""
        assert self.ckpt_dir is not None
        return checkpoint.save(self.ckpt_dir, self.step_idx, self.state)

    # -------------------------------------------------------------- preempt
    def preempt(self, warning: bool = True) -> None:
        """warning=True is the 2-minute-warning path (malleable): snapshot
        the exact current step.  Rigid jobs lose work since the last
        periodic checkpoint (paper §III-A)."""
        with telemetry.span("elastic.preempt", key=self.jid) as sp:
            if self.ckpt_dir is not None and \
                    (warning or self.kind == "malleable"):
                sp.n = os.path.getsize(self.checkpoint())
            if self.ckpt_dir is not None and \
                    checkpoint.latest_step(self.ckpt_dir) is not None:
                self._free()  # resume restores; the nodes go to the next job
        self.mesh = None
        self._step_fn = None
        self.devices = ()

    def _free(self) -> None:
        """Release the train state's device buffers now, not whenever the
        last reference dies."""
        if self.state is not None:
            with telemetry.span("elastic.free"):
                for leaf in jax.tree.leaves(self.state):
                    leaf.delete()
        self.state = None

    # -------------------------------------------------------- shrink/expand
    def resize(self, devices: Sequence) -> float:
        """Checkpoint-free elastic resize onto a new device set.  Returns
        the seconds it took, the resharded state on its devices (the
        ``elastic.resize`` span); the step recompiles on its next call."""
        with telemetry.span("elastic.resize", key=self.jid) as sp:
            self.devices = list(devices)
            self.mesh = self._build(self.devices)
            self._jit()
            self._reshard()
            jax.block_until_ready(self.state)
            sp.n = sum(x.nbytes for x in jax.tree.leaves(self.state))
        return sp.t1 - sp.t0

    def _reshard(self) -> None:
        sh = tree_shardings(self.state, self.cfg, self.mesh)
        # batch-dim arrays in the state are only params/opt (no batch): the
        # rules give everything a valid spec on the new mesh.
        self.state = jax.device_put(self.state, sh)
