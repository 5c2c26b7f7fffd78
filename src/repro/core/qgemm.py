"""Quantized GeMM with a custom VJP — the single entry point every model
projection in this framework routes through.

``qgemm(cfg, x, w, key)`` computes x @ w under one of five recipes:

  bf16             full-precision baseline
  nvfp4            vanilla blockwise NVFP4 W4A4G4
  nvfp4_hadamard   NVFP4 + tiled 16x16 Hadamard smoothing (NVIDIA baseline)
  averis           NVFP4 + mean-residual splitting (paper Eqs. 8-10)
  averis_hadamard  Averis + Hadamard on the residual stream (paper "combined")

Every recipe is pure data: a :class:`repro.core.pipeline.GemmPlan` naming the
per-operand stage pipelines (Center -> Hadamard -> Quantize) and mean
cross-terms of the forward / input-grad / weight-grad GeMMs. One executor
(``pipeline.execute_terms``) evaluates all of them — there are no per-mode
branches in this module.

W4A4G4 scope: *both operands of every GeMM* (forward, input-grad, weight-grad)
are quantized, blocks along the contraction dim of that GeMM; stochastic
rounding is applied to the output-gradient operand of the backward GeMMs
(cfg.sr_grad), round-to-nearest everywhere else. The backward implements the
paper's quantized gradient computation directly (Eqs. 9-10 for Averis) with
straight-through semantics across quantizers — this IS the training algorithm,
not autodiff through the quantizer.

Weight operands are prepared *outside* the custom VJP (under
``lax.stop_gradient``; dW flows straight-through to the raw weight), which
makes weight QDQ hoistable: ``Model.prepare_qweights`` builds the per-step
quantized-weight cache (via :func:`prepared_weight_stack` /
:func:`prepared_weight_single`) once per optimizer step, outside ``jax.grad``
and the microbatch loop, and qgemm consumes it through ``prepared`` — each
(param, plan-operand) pair is quantized exactly once per step.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.obs import scopes

from .formats import MODES
from .pipeline import GemmPlan, PLANS, apply_stages, execute_terms, plan_for


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    """Static quantization recipe configuration (hashable; safe as nondiff arg)."""

    mode: str = "bf16"
    sr_grad: bool = True        # stochastic rounding on gradient quantization (G4)
    quantize_weights: bool = True   # W4 (False -> A4G4 with bf16 weights)
    block_size: int = 16
    # §Perf knobs (see EXPERIMENTS.md): paper-faithful defaults are float32.
    comm_dtype: str = "float32"  # dtype of GeMM partial sums -> the dtype TP
                                 # activation all-reduces travel in
    qdq_dtype: str = "float32"   # dtype of the QDQ simulation chain
    backend: str = "stages"      # "stages" (pure-XLA stage pipeline) or
                                 # "fused" (single-pass Pallas kernels with
                                 # loud fallback — see core/pipeline.py)

    def __post_init__(self):
        if self.mode not in MODES and self.mode not in PLANS:
            raise ValueError(
                f"unknown quant mode {self.mode!r}; expected one of {MODES} "
                f"or a registered plan ({sorted(PLANS)})")
        if self.backend not in ("stages", "fused"):
            raise ValueError(
                f"unknown quant backend {self.backend!r}; expected "
                f"'stages' or 'fused'")

    @property
    def is_quantized(self) -> bool:
        return self.mode != "bf16"

    @property
    def plan(self) -> GemmPlan:
        return plan_for(self.mode)


BF16 = QuantConfig(mode="bf16")
NVFP4 = QuantConfig(mode="nvfp4")
NVFP4_HADAMARD = QuantConfig(mode="nvfp4_hadamard")
AVERIS = QuantConfig(mode="averis")
AVERIS_HADAMARD = QuantConfig(mode="averis_hadamard")

_RECIPES = {c.mode: c for c in (BF16, NVFP4, NVFP4_HADAMARD, AVERIS, AVERIS_HADAMARD)}


def recipe(name: str, **overrides) -> QuantConfig:
    """Look up a recipe by name, optionally overriding fields."""
    base = _RECIPES.get(name, None)
    if base is None:
        base = QuantConfig(mode=name)   # registered custom plan
    return dataclasses.replace(base, **overrides) if overrides else base


# --------------------------------------------------------------------------
# Weight preparation: pipelined (quantized) weight operands
# --------------------------------------------------------------------------

def _prepare_weight(w: jax.Array, spec, cfg: QuantConfig) -> jax.Array:
    """One weight-operand pipeline (tests wrap this to count QDQs)."""
    return apply_stages(w, spec, cfg)


def _prepared_weights(
    plan: GemmPlan,
    gemm: str,
    w: jax.Array,
    cfg: QuantConfig,
    *,
    per_expert: bool = False,
) -> Tuple[jax.Array, ...]:
    """Inline-prepared arrays for each distinct weight spec of one GeMM —
    the fallback when no per-step cache entry was passed in (inference, or
    direct qgemm calls). ``per_expert``: ``w`` is stacked (E, m, n); the
    pipeline is vmapped over the expert axis so every expert keeps its own
    tensor-level amax.
    """
    out = []
    with jax.named_scope(scopes.QGEMM_WEIGHTS):
        for spec in plan.weight_specs(gemm):
            if per_expert:
                val = jax.vmap(
                    lambda we, _s=spec: _prepare_weight(we, _s, cfg))(w)
            else:
                val = _prepare_weight(w, spec, cfg)
            out.append(val)
    return tuple(out)


def _spec_map(plan: GemmPlan, gemm: str, prepared) -> Dict:
    return dict(zip(plan.weight_specs(gemm), prepared))


# --------------------------------------------------------------------------
# custom_vjp core (2-D operands; the public qgemm flattens leading dims)
# --------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _qgemm2d(plan: GemmPlan, cfg: QuantConfig, x, w, wq_fwd, wq_dx, key):
    y, _ = _qgemm2d_fwd(plan, cfg, x, w, wq_fwd, wq_dx, key)
    return y


def _qgemm2d_fwd(plan, cfg, x, w, wq_fwd, wq_dx, key):
    y = execute_terms(plan.fwd, "fwd", x, w, cfg,
                      out_dtype=x.dtype,
                      prepared_rhs=_spec_map(plan, "fwd", wq_fwd))
    return y, (x, w, wq_dx, key)


def _qgemm2d_bwd(plan, cfg, res, g):
    x, w, wq_dx, key = res
    g = g.astype(x.dtype)
    kdx, kdw = jax.random.split(jax.random.fold_in(key, 1))

    dx = execute_terms(plan.dx, "dx", g, w, cfg,
                       out_dtype=x.dtype, sr_key=kdx,
                       prepared_rhs=_spec_map(plan, "dx", wq_dx))
    dw = execute_terms(plan.dw, "dw", x, g, cfg,
                       out_dtype=w.dtype, sr_key=kdw)

    # Straight-through: dW targets the raw weight; the prepared (stop-grad)
    # QDQ'd copies get zeros, which die at the stop_gradient boundary.
    dkey = np.zeros(key.shape, dtype=jax.dtypes.float0)
    return (dx, dw,
            tuple(jnp.zeros_like(w) for _ in plan.weight_specs("fwd")),
            tuple(jnp.zeros_like(w) for _ in plan.weight_specs("dx")),
            dkey)


_qgemm2d.defvjp(_qgemm2d_fwd, _qgemm2d_bwd)


# --------------------------------------------------------------------------
# Public API
# --------------------------------------------------------------------------

def qgemm(x: jax.Array, w: jax.Array, cfg: QuantConfig, key: jax.Array,
          prepared=None) -> jax.Array:
    """Quantized ``x @ w`` for ``x`` of shape (..., m) and ``w`` of (m, n).

    All leading dims of ``x`` are flattened into the token axis l — the Averis
    column mean is taken over every token in the GeMM, exactly as the paper
    reshapes (b, s, m) -> (l, m). ``w`` is the raw parameter (cast to
    ``x.dtype`` here, not at call sites). ``prepared`` supplies externally
    pre-quantized weight operands ``(wq_fwd_tuple, wq_dx_tuple)`` from the
    per-step cache (see :func:`prepared_weight_stack`); without it the
    weight pipelines run inline.
    """
    m = w.shape[0]
    if x.shape[-1] != m:
        raise ValueError(f"qgemm: x[...,{x.shape[-1]}] @ w[{m},...] mismatch")
    plan = plan_for(cfg.mode)
    lead = x.shape[:-1]
    x2 = x.reshape((-1, m))
    wc = w if w.dtype == x.dtype else w.astype(x.dtype)
    if prepared is not None:
        wq_fwd, wq_dx = prepared
        assert (len(wq_fwd) == len(plan.weight_specs("fwd"))
                and len(wq_dx) == len(plan.weight_specs("dx"))), (
            "prepared weights do not match the plan (policy/site-map skew?)")
    else:
        wq_fwd = jax.lax.stop_gradient(
            _prepared_weights(plan, "fwd", wc, cfg))
        wq_dx = jax.lax.stop_gradient(
            _prepared_weights(plan, "dx", wc, cfg))
    y2 = _qgemm2d(plan, cfg, x2, wc, wq_fwd, wq_dx, key)
    return y2.reshape(lead + (w.shape[1],))


def probe_stats(x: jax.Array, cfg: QuantConfig):
    """Quant-health stats of ``x`` as the activation input of a ``cfg``
    GeMM site — the same (l, m) flattening as :func:`qgemm`, delegated to
    :func:`repro.obs.probes.gemm_site_stats`. Pure read (stop_gradient
    inside); used by ``launch/quantwatch.py`` and the in-graph probe tape.
    """
    from repro.obs.probes import gemm_site_stats

    return gemm_site_stats(x.reshape((-1, x.shape[-1])), cfg)


def qgemm_expert(
    x: jax.Array, w: jax.Array, cfg: QuantConfig, key: jax.Array,
    prepared=None,
) -> jax.Array:
    """Per-expert quantized GeMM: x (E, C, m) @ w (E, m, n) -> (E, C, n).

    Each expert's dispatched token group forms its own ``l`` axis, so the
    Averis mean is computed per expert group (DESIGN.md §5, MoE row). Expert
    weights are prepared on the stacked array (vmapped, per-expert amax)
    before the vmapped GeMM core, so the per-step cache covers experts too.
    """
    plan = plan_for(cfg.mode)
    keys = jax.random.split(key, w.shape[0])
    wc = w if w.dtype == x.dtype else w.astype(x.dtype)
    if prepared is not None:
        wq_fwd, wq_dx = prepared
        assert (len(wq_fwd) == len(plan.weight_specs("fwd"))
                and len(wq_dx) == len(plan.weight_specs("dx"))), (
            "prepared weights do not match the plan (policy/site-map skew?)")
    else:
        wq_fwd = jax.lax.stop_gradient(
            _prepared_weights(plan, "fwd", wc, cfg, per_expert=True))
        wq_dx = jax.lax.stop_gradient(
            _prepared_weights(plan, "dx", wc, cfg, per_expert=True))
    return jax.vmap(
        lambda xe, we, wqf, wqd, ke: _qgemm2d(plan, cfg, xe, we, wqf, wqd, ke)
    )(x, wc, wq_fwd, wq_dx, keys)


def prepared_weight_stack(
    stacked: jax.Array,
    seg: Tuple[int, int],
    cfg: QuantConfig,
    compute_dtype,
    *,
    per_expert: bool = False,
):
    """Pre-quantize one stacked (L, ...) weight leaf for a layer segment.

    Returns ``(wq_fwd_tuple, wq_dx_tuple)`` whose arrays carry a leading
    segment-layer axis — fed to ``lax.scan`` as xs so each iteration picks
    up its layer's prepared operands. The pipeline is vmapped over the layer
    (and expert) axes, preserving per-layer(-expert) tensor amax: slicing a
    vmapped QDQ is bitwise the QDQ of the slice. Called by
    ``Model.prepare_qweights`` once per optimizer step, *outside*
    ``jax.grad`` and the microbatch loop — inside them, weights are fresh
    per-trace tracers and nothing can be hoisted.
    """
    plan = plan_for(cfg.mode)
    s0, s1 = seg
    out = []
    with jax.named_scope(scopes.QGEMM_WEIGHTS):
        for gemm in ("fwd", "dx"):
            vals = []
            for spec in plan.weight_specs(gemm):
                wseg = stacked[s0:s1].astype(compute_dtype)
                prep = lambda we, _s=spec: _prepare_weight(we, _s, cfg)
                if per_expert:
                    prep = jax.vmap(prep)    # expert axis under layer axis
                vals.append(jax.lax.stop_gradient(jax.vmap(prep)(wseg)))
            out.append(tuple(vals))
    return tuple(out)


def prepared_weight_single(w: jax.Array, cfg: QuantConfig, compute_dtype):
    """Prepared ``(wq_fwd_tuple, wq_dx_tuple)`` for one unstacked weight
    (the lm_head path of ``Model.prepare_qweights``)."""
    plan = plan_for(cfg.mode)
    with jax.named_scope(scopes.QGEMM_WEIGHTS):
        wc = w.astype(compute_dtype)
        return tuple(
            tuple(jax.lax.stop_gradient(_prepare_weight(wc, spec, cfg))
                  for spec in plan.weight_specs(gemm))
            for gemm in ("fwd", "dx")
        )


def gemm_plan_summary(cfg: QuantConfig, x_shape, w_shape) -> Dict:
    """Static plan summary (stages + ``skipped_hadamard`` flags) for a recipe
    at concrete 2-D operand shapes; see ``pipeline.plan_summary``."""
    from .pipeline import plan_summary

    lead = int(np.prod(x_shape[:-1])) if len(x_shape) > 1 else 1
    return plan_summary(plan_for(cfg.mode), (lead, x_shape[-1]),
                        tuple(w_shape))
