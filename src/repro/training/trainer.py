"""Training substrate — microbatched train step, ZeRO-1 sharding, FT loop.

``make_train_step`` builds the jitted step:

    (params, opt_state, batch) → (params, opt_state, metrics)

* gradient accumulation over ``n_microbatches`` with ``lax.scan`` — bounds
  activation memory AND lets XLA overlap microbatch-i's reduce-scatter with
  microbatch-(i+1)'s compute (latency-hiding scheduler),
* per-unit remat inside the layer scan (models/stack.py),
* ZeRO-1: (master, m, v) sharded over the data axes via
  ``opt_sharding`` — GSPMD inserts the gather on use,
* optional int8 gradient compression w/ error feedback (shard_map DP variant).

The :class:`Trainer` adds the production loop: checkpoint/restart, straggler
deadline-skip, failure injection (for FT tests), elastic re-mesh on resume.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.models import lm
from repro.models.config import ModelConfig
from repro.optim import (AdamWConfig, adamw_init, adamw_update,
                         compress_state_init, compressed_psum, cosine_schedule)
from repro.parallel import ParallelCtx, param_sharding

P = jax.sharding.PartitionSpec


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    n_microbatches: int = 1
    remat: bool = True
    zero1: bool = True
    grad_compress: bool = False      # int8 + error feedback (shard_map DP)
    opt: AdamWConfig = AdamWConfig()
    warmup: int = 100
    total_steps: int = 1000
    step_deadline_s: float = 0.0     # >0 → straggler deadline (Trainer loop)
    checkpoint_every: int = 100
    checkpoint_dir: str = ""
    keep: int = 3


def _microbatch(batch, n: int):
    """Split leading batch dim into (n, B/n, ...)."""
    return jax.tree.map(lambda x: x.reshape(n, x.shape[0] // n, *x.shape[1:]),
                        batch)


def opt_sharding(opt_state, pshard, pctx: ParallelCtx, zero1: bool):
    """Sharding for opt state: like params, plus dp over dim0 when free (ZeRO-1)."""
    mesh = pctx.mesh
    dp_axes = pctx.data_axes
    dp_size = 1
    for a in dp_axes:
        dp_size *= mesh.shape[a]

    def per(ps, leaf):
        spec = list(ps.spec) + [None] * (leaf.ndim - len(ps.spec))
        if zero1:
            for i in range(leaf.ndim):
                if spec[i] is None and leaf.shape[i] % dp_size == 0 and leaf.shape[i] >= dp_size:
                    spec[i] = dp_axes if len(dp_axes) > 1 else dp_axes[0]
                    break
        return jax.sharding.NamedSharding(mesh, P(*spec))

    scalar = jax.sharding.NamedSharding(mesh, P())
    return {
        "step": scalar,
        "master": jax.tree.map(per, pshard, opt_state["master"]),
        "m": jax.tree.map(per, pshard, opt_state["m"]),
        "v": jax.tree.map(per, pshard, opt_state["v"]),
    }


def make_train_step(cfg: ModelConfig, tcfg: TrainConfig,
                    pctx: Optional[ParallelCtx] = None,
                    loss_fn: Optional[Callable] = None,
                    param_dtypes=None):
    """Build the train step: (opt_state, batch) → (opt_state, metrics).

    Compute params are *derived* from the f32 masters at step start (mixed
    precision without buffer aliasing — opt_state is safely donatable; with
    ZeRO-1 the cast IS the all-gather of the sharded master).
    """
    lfn = loss_fn or (lambda p, b: lm.loss_fn(cfg, p, b, pctx=pctx,
                                              remat=tcfg.remat)[0])
    nmb = tcfg.n_microbatches

    def step_fn(opt_state, batch):
        dts = param_dtypes or jax.tree.map(lambda _: jnp.bfloat16,
                                           opt_state["master"])
        params = jax.tree.map(lambda m, dt: m.astype(dt),
                              opt_state["master"], dts)
        if pctx is not None and pctx.mesh is not None:
            shard = param_sharding(params, pctx)
            params = jax.tree.map(jax.lax.with_sharding_constraint, params, shard)
        if nmb > 1:
            mbs = _microbatch(batch, nmb)

            def acc(carry, mb):
                g_acc, l_acc = carry
                l, g = jax.value_and_grad(lfn)(params, mb)
                g_acc = jax.tree.map(lambda a, b_: a + b_.astype(jnp.float32),
                                     g_acc, g)
                return (g_acc, l_acc + l), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss), _ = jax.lax.scan(acc, (g0, 0.0), mbs)
            grads = jax.tree.map(lambda g: g / nmb, grads)
            loss = loss / nmb
        else:
            loss, grads = jax.value_and_grad(lfn)(params, batch)
        lr = cosine_schedule(opt_state["step"], tcfg.warmup, tcfg.total_steps,
                             tcfg.opt.lr)
        _, opt_state, om = adamw_update(grads, opt_state, tcfg.opt,
                                        params=params, lr_t=lr)
        return opt_state, {"loss": loss, **om}

    return step_fn


def make_compressed_dp_step(cfg: ModelConfig, tcfg: TrainConfig,
                            pctx: ParallelCtx):
    """DP-only variant with int8 gradient all-reduce + error feedback.

    Built with shard_map over the data axes (model axis unused — the
    demonstration of the distributed-optimization trick at small scale; the
    big pjit step keeps gradient reduction inside GSPMD).
    """
    dp = pctx.dp
    mesh = pctx.mesh

    def local_loss(params, batch):
        return lm.loss_fn(cfg, params, batch, remat=tcfg.remat)[0]

    def step_fn(params, opt_state, err, batch):
        def shard_fn(params, opt_state, err, batch):
            loss, grads = jax.value_and_grad(local_loss)(params, batch)
            grads, err_new = compressed_psum(grads, pctx.data_axes, err)
            n = 1
            for a in pctx.data_axes:
                n *= jax.lax.axis_size(a)
            grads = jax.tree.map(lambda g: g / n, grads)
            lr = cosine_schedule(opt_state["step"], tcfg.warmup,
                                 tcfg.total_steps, tcfg.opt.lr)
            params, opt_state, om = adamw_update(grads, opt_state, tcfg.opt,
                                                 params=params, lr_t=lr)
            loss = jax.lax.pmean(loss, pctx.data_axes)
            return params, opt_state, err_new, {"loss": loss, **om}

        pspec = jax.tree.map(lambda _: P(), params)
        ospec = jax.tree.map(lambda _: P(), opt_state)
        espec = jax.tree.map(lambda _: P(), err)
        bspec = jax.tree.map(lambda _: P(dp), batch)
        return jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(pspec, ospec, espec, bspec),
            out_specs=(pspec, ospec, espec,
                       {"loss": P(), "grad_norm": P(), "lr": P()}),
            check_vma=False,
        )(params, opt_state, err, batch)

    return step_fn


class Trainer:
    """Production loop: jit, donate, checkpoint/restart, straggler deadline,
    failure injection for FT tests, elastic re-mesh on resume."""

    def __init__(self, cfg: ModelConfig, tcfg: TrainConfig, data_iter,
                 pctx: Optional[ParallelCtx] = None, key=None):
        from repro.checkpoint import CheckpointManager
        self.cfg, self.tcfg, self.pctx = cfg, tcfg, pctx
        self.data = data_iter
        key = key if key is not None else jax.random.PRNGKey(0)
        params0 = lm.init_params(cfg, key)
        self._dtypes = jax.tree.map(lambda p: p.dtype, params0)
        self.opt_state = adamw_init(params0)
        del params0
        if pctx is not None and pctx.mesh is not None:
            tmpl = self.params  # host-side template for sharding rules
            pshard = param_sharding(tmpl, pctx)
            oshard = opt_sharding(self.opt_state, pshard, pctx, tcfg.zero1)
            self.opt_state = jax.tree.map(jax.device_put, self.opt_state, oshard)
        self.step_fn = jax.jit(
            make_train_step(cfg, tcfg, pctx, param_dtypes=self._dtypes),
            donate_argnums=(0,))
        self.ckpt = (CheckpointManager(tcfg.checkpoint_dir, keep=tcfg.keep)
                     if tcfg.checkpoint_dir else None)
        self.step = 0
        self.metrics_log: list = []
        self.failure_hook: Optional[Callable[[int], None]] = None  # FT tests
        self.skipped_steps: list = []

    @property
    def params(self):
        """Compute params (bf16) derived from the f32 masters."""
        return jax.tree.map(lambda m, dt: m.astype(dt),
                            self.opt_state["master"], self._dtypes)

    def restore_if_available(self):
        if self.ckpt is None:
            return False
        latest = self.ckpt.latest_step()
        if latest is None:
            return False
        state = self.ckpt.restore(latest, {"opt": self.opt_state})
        self.opt_state = state["opt"]
        self.step = latest
        return True

    def run(self, n_steps: int):
        deadline = self.tcfg.step_deadline_s
        end = self.step + n_steps
        while self.step < end:
            batch = next(self.data)
            if self.failure_hook is not None:
                self.failure_hook(self.step)   # may raise — simulated crash
            t0 = time.monotonic()
            self.opt_state, m = self.step_fn(self.opt_state, batch)
            m = jax.tree.map(float, m)
            dt = time.monotonic() - t0
            if deadline > 0 and dt > deadline:
                # straggler: log + continue (a real fleet reissues the step on
                # a backup slice; state here is already consistent post-step)
                self.skipped_steps.append((self.step, dt))
            self.metrics_log.append({"step": self.step, "time_s": dt, **m})
            self.step += 1
            if self.ckpt and self.step % self.tcfg.checkpoint_every == 0:
                self.ckpt.save(self.step, {"opt": self.opt_state})
        return self.metrics_log
