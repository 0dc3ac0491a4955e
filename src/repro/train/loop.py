"""Generic training loop: jit'd step + checkpointing + watchdog + logging.

The loop is model-agnostic: the caller supplies ``loss_fn(params, batch)``
and the optimizer; everything else (grad clip, fault hooks, async
checkpoints, throughput accounting) is shared across the 10 archs.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Iterator, Optional

import jax
import jax.numpy as jnp

from .. import obs
from ..chaos import inject as chaos
from .optimizer import Optimizer, apply_updates, clip_by_global_norm
from .checkpoint import AsyncCheckpointer
from .fault import StepWatchdog, resume


@dataclasses.dataclass
class TrainResult:
    params: Any
    opt_state: Any
    losses: list
    steps: int
    straggler_flags: int
    wall_time: float


def make_train_step(loss_fn: Callable, opt: Optimizer,
                    clip_norm: Optional[float] = 1.0,
                    donate: bool = True):
    """Returns jit'd (params, opt_state, batch) -> (params, opt_state, loss).
    Clipping and the optimizer update trace under the ``optimizer`` name
    scope."""

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        with jax.named_scope("optimizer"):
            if clip_norm:
                grads, _ = clip_by_global_norm(grads, clip_norm)
            updates, opt_state = opt.update(grads, opt_state, params)
            return apply_updates(params, updates), opt_state, loss

    return jax.jit(step, donate_argnums=(0, 1) if donate else ())


def _batch_rows(batch) -> int:
    """Leading-dim row count of a batch (dict of arrays or one array) — the
    numerator of the rows/sec throughput gauge; 0 when undeterminable."""
    try:
        if isinstance(batch, dict):
            for v in batch.values():
                if hasattr(v, "shape") and len(v.shape) >= 1:
                    return int(v.shape[0])
        elif hasattr(batch, "shape") and len(batch.shape) >= 1:
            return int(batch.shape[0])
    except Exception:
        pass
    return 0


def fit(loss_fn: Callable, opt: Optimizer, params, batches: Iterator,
        steps: int, ckpt_dir: Optional[str] = None, ckpt_every: int = 100,
        log_every: int = 10, clip_norm: Optional[float] = 1.0,
        log: Callable = print) -> TrainResult:
    opt_state = opt.init(params)
    start = 0
    if ckpt_dir:
        params, opt_state, start = resume(ckpt_dir, params, opt_state)
    step_fn = make_train_step(loss_fn, opt, clip_norm)
    ckpt = AsyncCheckpointer(ckpt_dir) if ckpt_dir else None
    watchdog = StepWatchdog()
    losses = []
    # metric handles held outside the loop: the disabled path per step is
    # one attribute load + branch per call
    step_hist = obs.histogram("train.step_seconds")
    steps_ctr = obs.counter("train.steps")
    loss_gauge = obs.gauge("train.loss")
    rows_gauge = obs.gauge("train.rows_per_s")
    t0 = time.time()
    i = start
    for i, batch in zip(range(start, steps), batches):
        chaos.fail_point("train.step")   # crash-drill injection (no-op unarmed)
        with obs.span("train.step", cat="train", step=i) as sp:
            ts = time.time()
            params, opt_state, loss = step_fn(params, opt_state, batch)
            loss = float(loss)
            losses.append(loss)
            dt = time.time() - ts
            sp.set(loss=loss)
        step_hist.observe(dt)
        steps_ctr.inc()
        loss_gauge.set(loss)
        if obs.enabled():
            rows = _batch_rows(batch)
            if rows:
                rows_gauge.set(rows / max(dt, 1e-9))
        slow = watchdog.observe(dt)
        if slow:
            log(f"[straggler] step {i} took {dt:.3f}s (flagged)")
        if log_every and i % log_every == 0:
            log(f"step {i:6d}  loss {loss:.4f}")
        if ckpt and i and i % ckpt_every == 0:
            ckpt.save(i, params, opt_state)
    if ckpt:
        ckpt.save(i, params, opt_state)
        ckpt.close()
    return TrainResult(params=params, opt_state=opt_state, losses=losses,
                       steps=i + 1 - start, straggler_flags=watchdog.flagged,
                       wall_time=time.time() - t0)
