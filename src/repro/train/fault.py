"""Fault tolerance: supervised training loop with checkpoint/restart.

``run_supervised`` wraps a step function with the production recipe:

  * periodic checkpointing (atomic, retained),
  * failure detection (any exception from a step, incl. injected faults and
    the NaN-loss guard) triggers restart from the latest checkpoint — except
    an exception raised by the step function before it has completed once
    in this process: that is the program failing to run (a compile
    refusal, a program that does not fit the device), which a restart
    would only replay, so it is re-raised at once,
  * deterministic data (pure function of step) means restarts replay the
    exact token stream — no loader state,
  * bounded restart budget (a real cluster supervisor would also re-slice
    the job; here the pool is fixed),
  * straggler/heartbeat hook: a step exceeding ``step_timeout_s`` raises and
    restarts (timeout detection is wall-clock in-process; on a pod it is the
    coordinator heartbeat).

``FaultInjector`` deterministically raises at chosen steps — used by the
tests to prove end-to-end recovery reproduces the no-fault loss trajectory.
"""
from __future__ import annotations

import dataclasses
import logging
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import checkpoint

log = logging.getLogger("repro.fault")


class InjectedFault(RuntimeError):
    pass


@dataclasses.dataclass
class FaultInjector:
    """Raise at the given global steps (once each)."""

    fail_at: Sequence[int] = ()
    _fired: set = dataclasses.field(default_factory=set)

    def check(self, step: int) -> None:
        if step in self.fail_at and step not in self._fired:
            self._fired.add(step)
            raise InjectedFault(f"injected fault at step {step}")


@dataclasses.dataclass(frozen=True)
class SupervisorConfig:
    total_steps: int = 100
    ckpt_every: int = 20
    ckpt_dir: str = "/tmp/repro_ckpt"
    keep: int = 3
    max_restarts: int = 10
    step_timeout_s: float = 0.0      # 0 disables
    nan_guard: bool = True


def run_supervised(
    train_step: Callable,                 # (params, opt, batch, key) -> (params, opt, metrics)
    init_fn: Callable[[], Any],           # () -> (params, opt_state)
    batch_fn: Callable[[int], Dict],      # step -> host batch
    key: jax.Array,
    cfg: SupervisorConfig,
    injector: Optional[FaultInjector] = None,
    on_metrics: Optional[Callable[[int, Dict], None]] = None,
) -> Dict[str, Any]:
    """Run to total_steps surviving faults. Returns summary stats."""
    restarts = 0
    history: List[float] = []

    params, opt_state = init_fn()
    start = 0
    latest = checkpoint.latest_step(cfg.ckpt_dir)
    if latest is not None:
        params, opt_state, start = checkpoint.restore(
            cfg.ckpt_dir, params, opt_state
        )
        log.info("resumed from step %d", start)

    step = start
    stepped = False                 # has train_step completed in this process?
    while step < cfg.total_steps:
        in_step = False
        try:
            t0 = time.monotonic()
            if injector is not None:
                injector.check(step)
            batch = jax.tree.map(jnp.asarray, batch_fn(step))
            in_step = True
            params, opt_state, metrics = train_step(
                params, opt_state, batch, jax.random.fold_in(key, step)
            )
            loss = float(metrics["loss"])
            in_step = False
            stepped = True
            if cfg.nan_guard and not np.isfinite(loss):
                raise RuntimeError(f"non-finite loss {loss} at step {step}")
            if cfg.step_timeout_s and (time.monotonic() - t0) > cfg.step_timeout_s:
                raise TimeoutError(
                    f"straggler: step {step} exceeded {cfg.step_timeout_s}s"
                )
            history.append(loss)
            if on_metrics is not None:
                on_metrics(step, metrics)
            step += 1
            if step % cfg.ckpt_every == 0 or step == cfg.total_steps:
                checkpoint.save(cfg.ckpt_dir, step, params, opt_state,
                                keep=cfg.keep)
        except Exception as e:  # noqa: BLE001 — supervisor catches everything
            if in_step and not stepped:
                raise
            restarts += 1
            log.warning("step %d failed (%s); restart %d", step, e, restarts)
            if restarts > cfg.max_restarts:
                raise RuntimeError("restart budget exhausted") from e
            latest = checkpoint.latest_step(cfg.ckpt_dir)
            if latest is None:
                params, opt_state = init_fn()
                step = 0
            else:
                params, opt_state, step = checkpoint.restore(
                    cfg.ckpt_dir, params, opt_state
                )
    return {
        "final_params": params,
        "final_opt_state": opt_state,
        "losses": history,
        "restarts": restarts,
        "steps": step,
    }
