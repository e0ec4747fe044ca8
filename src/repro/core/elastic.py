"""ElasticTrainer — live shrink/expand of a JAX training job (paper C1).

A job runs on a dynamic set of devices arranged as a ``(data=R, model=M)``
mesh; the elastic axis is ``data`` (R = replicas, the scheduler's slot count).
Rescaling follows the paper's four stages and reports the same breakdown as
paper Fig. 5:

    load_balance  re-split the fixed global batch / data stream over the new
                  replica set (exact for SPMD — DESIGN.md §2b)
    checkpoint    device -> host-RAM snapshot (the /dev/shm analog)
    restart       build the new mesh + re-jit (lower+compile) the train step
                  (the MPI process-group restart analog; grows with scale)
    restore       host snapshot -> device arrays under the new shardings

The beyond-paper fast path (``via_host=False``) reshards device-to-device with
a single ``jax.device_put`` and skips the host round-trip; §Perf quantifies
the difference.  Training state is ``(params, opt_state, step)``; the data
pipeline is deterministic in ``(seed, step)`` so a rescaled run reproduces the
static run's loss trajectory (pinned by tests).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint import (AsyncCheckpointer, MemoryCheckpointStore,
                              device_reshard, restore_from_host,
                              snapshot_to_host, surviving_devices,
                              unflatten_tree)
from repro.configs.base import ModelConfig
from repro.data import make_stream
from repro.models import model as M
from repro.obs import live
from repro.optim import (AdamWConfig, adamw_init, adamw_update, opt_logical_axes,
                         warmup_cosine)
from repro.sharding import AxisRules, RULE_SETS, axis_rules, make_param_shardings


@dataclass
class RescaleTimings:
    load_balance: float = 0.0
    checkpoint: float = 0.0
    restart: float = 0.0
    restore: float = 0.0
    path: str = "host"          # "p2p" (device-to-device) or "host"

    @property
    def total(self) -> float:
        return self.load_balance + self.checkpoint + self.restart + self.restore

    def as_dict(self) -> Dict[str, float]:
        # numeric-only: consumers format every value as seconds
        return {"load_balance": self.load_balance, "checkpoint": self.checkpoint,
                "restart": self.restart, "restore": self.restore,
                "total": self.total}


@dataclass
class TrainJobConfig:
    global_batch: int = 8
    seq_len: int = 32
    total_steps: int = 50
    model_axis: int = 1
    rules: str = "tp"
    peak_lr: float = 3e-3
    warmup_steps: int = 10
    seed: int = 0
    dtype: str = "float32"


class ElasticTrainer:
    def __init__(self, cfg: ModelConfig, job: TrainJobConfig,
                 devices: Sequence):
        self.cfg = cfg.with_(dtype=job.dtype)
        self.job = job
        self.step_idx = 0
        self.stream = make_stream(self.cfg, seed=job.seed,
                                  global_batch=job.global_batch,
                                  seq_len=job.seq_len)
        self.adamw = AdamWConfig()
        self.metrics_log: List[dict] = []
        self.rescale_log: List[RescaleTimings] = []
        self._lr_fn = lambda s: warmup_cosine(
            s, peak_lr=job.peak_lr, warmup_steps=job.warmup_steps,
            total_steps=job.total_steps)

        # initial "restart" (mesh + compile) and state init
        with live.span("trainer.build") as build:
            self._mesh_cache: Dict[tuple, dict] = {}
            self._async_ckpt: Optional[AsyncCheckpointer] = None
            self.validate_devices(devices)
            self._ensure_mesh(devices)
            key = jax.random.PRNGKey(job.seed)
            with live.span("trainer.init"), axis_rules(self.rules):
                self.params = jax.jit(
                    lambda k: M.init_params(self.cfg, k),
                    out_shardings=self._param_sh)(key)
                self.opt_state = jax.jit(
                    adamw_init, out_shardings=self._opt_sh)(self.params)
                jax.block_until_ready((self.params, self.opt_state))
            self._compile()
            self._mesh_cache[self._mesh_key(devices)]["compiled"] = \
                self._compiled
        self.startup_time = build.record.seconds

    # -- mesh / sharding ------------------------------------------------------
    @property
    def replicas(self) -> int:
        return self.mesh.shape["data"]

    def validate_devices(self, devices: Sequence) -> int:
        """Check a target device set BEFORE any rescale stage runs.

        An indivisible global_batch/replica combination used to surface as a
        bare AssertionError from ``_build_mesh`` — after the checkpoint stage
        had already burned a full snapshot.  Returns the replica count."""
        devices = list(devices)
        m = self.job.model_axis
        if not devices:
            raise ValueError("rescale target has no devices")
        if len(devices) % m != 0:
            raise ValueError(
                f"{len(devices)} devices not divisible by model_axis {m}")
        r = len(devices) // m
        if self.job.global_batch % r != 0:
            raise ValueError(
                f"global_batch {self.job.global_batch} not divisible by "
                f"{r} replicas")
        return r

    @staticmethod
    def _mesh_key(devices: Sequence) -> tuple:
        return tuple(d.id for d in devices)

    def _ensure_mesh(self, devices: Sequence) -> bool:
        """Build (or restore from cache) mesh/shardings for ``devices``.

        Returns True on a cache hit — a previously-visited device set skips
        the re-jit entirely, which is what makes repeated shrink⇄expand
        oscillation cheap (the 'warm restart' the fast-lane perf model
        prices)."""
        key = self._mesh_key(devices)
        cached = self._mesh_cache.get(key)
        if cached is not None and cached.get("compiled") is not None:
            for attr, v in cached.items():
                if attr != "compiled":
                    setattr(self, attr, v)
            self._compiled = cached["compiled"]
            return True
        self._build_mesh(devices)
        self._mesh_cache[key] = {
            "devices": self.devices, "mesh": self.mesh, "rules": self.rules,
            "_param_sh": self._param_sh, "_opt_sh": self._opt_sh,
            "_batch_sh": self._batch_sh, "_scalar_sh": self._scalar_sh,
            "compiled": None}
        return False

    def _build_mesh(self, devices: Sequence):
        devices = list(devices)
        m = self.job.model_axis
        assert len(devices) % m == 0, (len(devices), m)
        r = len(devices) // m
        assert self.job.global_batch % r == 0, \
            f"global_batch {self.job.global_batch} not divisible by {r} replicas"
        self.devices = devices
        self.mesh = Mesh(np.array(devices).reshape(r, m), ("data", "model"))
        self.rules = AxisRules(self.mesh, RULE_SETS[self.job.rules]())
        axes = M.logical_axes(self.cfg)
        abstract_p = M.abstract_params(self.cfg)
        from repro.optim import abstract_opt_state
        self._param_sh = make_param_shardings(self.rules, axes, abstract_p)
        self._opt_sh = make_param_shardings(self.rules, opt_logical_axes(axes),
                                            abstract_opt_state(abstract_p))
        self._batch_sh = {
            k: NamedSharding(self.mesh, P("data", *([None] * (v.ndim - 1))))
            for k, v in self._abstract_batch().items()}
        self._scalar_sh = NamedSharding(self.mesh, P())

    def _abstract_batch(self) -> dict:
        B, S = self.job.global_batch, self.job.seq_len
        d = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
             "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        if self.cfg.enc_layers:
            d["enc_embeds"] = jax.ShapeDtypeStruct(
                (B, S, self.cfg.d_model), jnp.float32)
        return d

    # -- train step -----------------------------------------------------------
    def _step_fn(self, params, opt_state, batch, step):
        def lf(p):
            return M.loss_fn(self.cfg, p, batch)
        (loss, metrics), grads = jax.value_and_grad(lf, has_aux=True)(params)
        lr = self._lr_fn(step)
        params, opt_state, om = adamw_update(self.adamw, grads, opt_state,
                                             params, lr)
        metrics = dict(metrics, **om)
        return params, opt_state, metrics

    def _compile(self):
        """The 'restart' stage: jit + AOT compile for the current mesh."""
        with live.span("trainer.compile"), axis_rules(self.rules):
            jitted = jax.jit(
                self._step_fn,
                in_shardings=(self._param_sh, self._opt_sh, self._batch_sh,
                              self._scalar_sh),
                donate_argnums=(0, 1))
            abstract_p = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
                jax.eval_shape(lambda: self.params))
            abstract_o = jax.eval_shape(lambda: self.opt_state)
            self._compiled = jitted.lower(
                abstract_p, abstract_o, self._abstract_batch(),
                jax.ShapeDtypeStruct((), jnp.int32)).compile()

    # -- public API -------------------------------------------------------------
    def step(self) -> dict:
        """One training step.  Its spans, all carrying the step index:
        ``trainer.step`` around ``trainer.batch`` (the host draw),
        ``trainer.put`` (the ``device_put``s), ``trainer.dispatch`` (the call
        into the compiled step), ``trainer.wait`` (until its outputs are
        ready) and ``trainer.readback`` (the metrics to Python floats)."""
        with live.step_span("trainer.step", self.step_idx):
            with live.span("trainer.batch"):
                batch_np = self.stream.global_batch_at(self.step_idx)
            with live.span("trainer.put"):
                batch = {k: jax.device_put(v, self._batch_sh[k])
                         for k, v in batch_np.items()}
                step_arr = jax.device_put(
                    jnp.asarray(self.step_idx, jnp.int32), self._scalar_sh)
            with live.span("trainer.dispatch"):
                self.params, self.opt_state, metrics = self._compiled(
                    self.params, self.opt_state, batch, step_arr)
            with live.span("trainer.wait"):
                jax.block_until_ready(metrics)
            with live.span("trainer.readback"):
                metrics = {k: float(v) for k, v in metrics.items()}
        self.step_idx += 1
        metrics["step"] = self.step_idx
        metrics["replicas"] = self.replicas
        self.metrics_log.append(metrics)
        return metrics

    @property
    def compiled_step(self):
        """The AOT-compiled train step of the current mesh, for
        ``memory_analysis()`` and ``as_text()``."""
        return self._compiled

    @property
    def done(self) -> bool:
        return self.step_idx >= self.job.total_steps

    def rescale(self, devices: Sequence, *, via_host: Optional[bool] = None
                ) -> RescaleTimings:
        """Shrink or expand onto ``devices`` (paper §3.1 shrink/expand).

        ``via_host=None`` (the default) picks the path automatically: when
        any source device survives into the target set, state moves
        peer-to-peer with a single ``jax.device_put`` (no host round-trip);
        when the sets are disjoint — a full migration — it falls back to the
        host-snapshot path.  Pass ``via_host=True``/``False`` to force."""
        devices = list(devices)
        self.validate_devices(devices)
        if via_host is None:
            via_host = surviving_devices(self.devices, devices) == 0
        t = RescaleTimings(path="host" if via_host else "p2p")

        with live.span("elastic.rescale", self.step_idx):
            # load balance: re-split the data stream over the new replicas
            with live.span("elastic.load_balance") as sp:
                new_r = len(devices) // self.job.model_axis
                bounds = [self.stream.shard_bounds(i, new_r)
                          for i in range(new_r)]
            t.load_balance = sp.record.seconds

            host = None
            if via_host:
                with live.span("elastic.checkpoint") as sp:
                    host = {"params": snapshot_to_host(self.params),
                            "opt": snapshot_to_host(self.opt_state)}
                t.checkpoint = sp.record.seconds

            old_params, old_opt = self.params, self.opt_state
            with live.span("elastic.restart") as sp:
                if not self._ensure_mesh(devices):
                    self._compile()
                    self._mesh_cache[self._mesh_key(devices)]["compiled"] = \
                        self._compiled
            t.restart = sp.record.seconds

            with live.span("elastic.restore") as sp:
                if via_host:
                    self.params = restore_from_host(host["params"], old_params,
                                                    self._param_sh)
                    self.opt_state = restore_from_host(host["opt"], old_opt,
                                                       self._opt_sh)
                else:
                    self.params = device_reshard(old_params, self._param_sh)
                    self.opt_state = device_reshard(old_opt, self._opt_sh)
                jax.block_until_ready((self.params, self.opt_state))
            t.restore = sp.record.seconds

        self.rescale_log.append(t)
        del bounds
        return t

    # -- fault tolerance (paper §3.2.2) ----------------------------------------
    def state_tree(self) -> dict:
        return {"params": self.params, "opt": self.opt_state,
                "step": jnp.asarray(self.step_idx, jnp.int32)}

    def save_disk(self, store, job_id: str, *, delta: bool = False,
                  fused: bool = False) -> float:
        return store.save(job_id, self.step_idx, self.state_tree(),
                          meta={"replicas": self.replicas}, delta=delta,
                          fused=fused)

    def save_disk_async(self, store, job_id: str, *, delta: bool = True,
                        fused: bool = False) -> None:
        """Snapshot now, write to disk in the background (fast lane).

        Training may continue immediately; call ``ckpt_barrier()`` before
        the job's slots are released (preempt) so ``latest_step`` is a fully
        published checkpoint."""
        if self._async_ckpt is None or self._async_ckpt.store is not store:
            if self._async_ckpt is not None:
                self._async_ckpt.close()
            self._async_ckpt = AsyncCheckpointer(store, delta=delta)
        self._async_ckpt.delta = delta
        self._async_ckpt.submit(job_id, self.step_idx, self.state_tree(),
                                meta={"replicas": self.replicas}, fused=fused)

    def ckpt_barrier(self) -> None:
        """Join all pending async checkpoint writes (preempt-time barrier)."""
        if self._async_ckpt is not None:
            self._async_ckpt.barrier()

    def close(self) -> None:
        """Publish pending async checkpoints and stop their writer thread."""
        if self._async_ckpt is not None:
            self._async_ckpt.close()
            self._async_ckpt = None

    def restore_disk(self, store, job_id: str) -> int:
        """Restart-from-checkpoint (the paper's extra restart flag)."""
        flat, manifest = store.load(job_id)
        template = jax.eval_shape(self.state_tree)
        tree = unflatten_tree(template, flat)
        self.params = jax.device_put(tree["params"], self._param_sh)
        self.opt_state = jax.device_put(tree["opt"], self._opt_sh)
        self.step_idx = int(manifest["step"])
        return self.step_idx
