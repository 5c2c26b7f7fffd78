"""AdamW optimizer (decoupled weight decay), pure JAX, pytree-native.

Hand-written (optax is not available in this environment) with the features a
large-scale run needs: fp32 moments regardless of param dtype, global-norm
clipping, bias correction, cosine/linear/constant schedules with warmup, and
a pluggable gradient transform hook (used by the wire-format gradient
compression in ``repro.parallel.collectives``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.obs import scopes


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    peak_lr: float = 3e-4
    end_lr_frac: float = 0.1
    warmup_steps: int = 100
    total_steps: int = 10_000
    schedule: str = "cosine"          # cosine | linear | constant
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0            # 0 disables


def lr_at(cfg: OptimizerConfig, step: jax.Array) -> jax.Array:
    step = step.astype(jnp.float32)
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    t = jnp.clip(
        (step - cfg.warmup_steps)
        / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1),
        0.0,
        1.0,
    )
    if cfg.schedule == "cosine":
        decay = cfg.end_lr_frac + (1 - cfg.end_lr_frac) * 0.5 * (1 + jnp.cos(jnp.pi * t))
    elif cfg.schedule == "linear":
        decay = 1.0 - (1.0 - cfg.end_lr_frac) * t
    else:
        decay = jnp.float32(1.0)
    return cfg.peak_lr * warm * decay


def init_state(params) -> Dict[str, Any]:
    zeros32 = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {
        "step": jnp.zeros((), jnp.int32),
        "m": jax.tree.map(zeros32, params),
        "v": jax.tree.map(zeros32, params),
    }


def global_norm(tree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def clip_by_global_norm(grads, max_norm: float) -> Tuple[Any, jax.Array]:
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype),
                        grads), norm


# Parameters that should not be weight-decayed: 1-D tensors (norm gains,
# biases, per-head scalars like A_log / dt_bias / D).
def _decay_mask(params):
    return jax.tree.map(lambda p: jnp.float32(p.ndim >= 2), params)


def apply_updates(
    params,
    grads,
    state: Dict[str, Any],
    cfg: OptimizerConfig,
    grad_transform: Optional[Callable] = None,
):
    """One AdamW step. Returns (new_params, new_state, metrics)."""
    metrics: Dict[str, jax.Array] = {}
    with jax.named_scope(scopes.ADAMW_CLIP):
        if cfg.clip_norm > 0:
            grads, gnorm = clip_by_global_norm(grads, cfg.clip_norm)
            metrics["grad_norm"] = gnorm
        else:
            metrics["grad_norm"] = global_norm(grads)
    if grad_transform is not None:
        grads, state = grad_transform(grads, state)

    step = state["step"] + 1
    lr = lr_at(cfg, step)
    metrics["lr"] = lr
    b1c = 1.0 - cfg.b1 ** step.astype(jnp.float32)
    b2c = 1.0 - cfg.b2 ** step.astype(jnp.float32)
    mask = _decay_mask(params)

    def upd(p, g, m, v, wd_on):
        gf = g.astype(jnp.float32)
        m_new = cfg.b1 * m + (1 - cfg.b1) * gf
        v_new = cfg.b2 * v + (1 - cfg.b2) * gf * gf
        m_hat = m_new / b1c
        v_hat = v_new / b2c
        delta = m_hat / (jnp.sqrt(v_hat) + cfg.eps)
        delta = delta + cfg.weight_decay * wd_on * p.astype(jnp.float32)
        p_new = p.astype(jnp.float32) - lr * delta
        return p_new.astype(p.dtype), m_new, v_new

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state["m"])
    flat_v = treedef.flatten_up_to(state["v"])
    flat_mask = treedef.flatten_up_to(mask)
    with jax.named_scope(scopes.ADAMW_UPDATE):
        out = [upd(p, g, m, v, w) for p, g, m, v, w in
               zip(flat_p, flat_g, flat_m, flat_v, flat_mask)]
    new_params = treedef.unflatten([o[0] for o in out])
    new_m = treedef.unflatten([o[1] for o in out])
    new_v = treedef.unflatten([o[2] for o in out])
    new_state = dict(state, step=step, m=new_m, v=new_v)
    return new_params, new_state, metrics
