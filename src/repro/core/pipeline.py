"""Composable quantization pipeline: stages, operand specs, and GeMM plans.

The paper's central architectural claim is that mean-subtraction is a
*source-level* transform — "requiring only reduction operations and standard
quantization kernels". This module makes that literal: every qgemm recipe is
**data**, not code. An operand is described by an ordered stage list

    Center(token_axis) -> Hadamard(axis) -> Quantize(axis, sr)

and a recipe is a :class:`GemmPlan` naming, for each of the three GeMMs of a
linear layer (forward, input-grad, weight-grad), the list of product
:class:`GemmTerm`\\ s to accumulate — including the rank-one mean cross-terms
of the paper's Eqs. 8-10 as explicit ``mean_row`` / ``rank1`` terms. A single
executor (:func:`execute_terms`) evaluates any plan; ``core/qgemm.py`` wires
it into a ``jax.custom_vjp``. There are no per-recipe branches anywhere.

Canonical operand orientation (2-D): ``x (l, m)``, ``w (m, n)``, output
cotangent ``g (l, n)``. Stage axes are relative to that orientation, so the
blocking axis of each Quantize is always the GeMM's contraction dimension:

    fwd:  y  = lhs(x)  @ rhs(w)        contraction m  (x axis -1, w axis 0)
    dx:   dx = lhs(g)  @ rhs(w).T      contraction n  (g axis -1, w axis 1)
    dw:   dw = lhs(x).T @ rhs(g)       contraction l  (both axis 0)

Weight operands (``weight=True``) are special: they honor
``cfg.quantize_weights``, are prepared *outside* the custom VJP (so their QDQ
can be hoisted out of gradient-accumulation loops into the per-step
quantized-weight cache — ``Model.prepare_qweights`` / qgemm.py), and never
carry a Center stage.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.obs import scopes

from .averis import split_mean
from .hadamard import hadamard_tiles
from .nvfp4 import nvfp4_qdq

_TILE = 16


# --------------------------------------------------------------------------
# Stages
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Center:
    """Split off the token mean; keep the ``take`` component.

    ``take="residual"`` yields the centered 2-D tensor; ``take="mean"`` the
    1-D mean vector (token axis reduced away). Both components of one source
    tensor share a single ``split_mean`` evaluation inside the executor.
    """

    token_axis: int = 0
    take: str = "residual"           # residual | mean

    def __post_init__(self):
        assert self.take in ("residual", "mean"), self.take


@dataclasses.dataclass(frozen=True)
class Hadamard:
    """Tiled 16x16 orthonormal Hadamard rotation along ``axis``.

    Skipped (with a once-per-length trace warning and a ``skipped_hadamard``
    flag in :func:`plan_summary`) when the axis length is not a multiple of
    16 — padding would break the paired-transform exactness, so the GeMM is
    computed unrotated: correct, just unsmoothed. Only ragged token counts
    hit this; contraction dims in the model zoo are 16-aligned.
    """

    axis: int


@dataclasses.dataclass(frozen=True)
class Quantize:
    """Blockwise NVFP4 QDQ along ``axis`` (the GeMM contraction dim).

    ``sr=True`` marks the gradient-stream operand: stochastic rounding is
    used when the recipe's ``sr_grad`` is on (G4), round-to-nearest
    otherwise. At most one SR stage may appear per GeMM (it consumes that
    GeMM's single SR key stream).
    """

    axis: int
    sr: bool = False


Stage = (Center, Hadamard, Quantize)


# --------------------------------------------------------------------------
# Operands, terms, plans
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Operand:
    """One GeMM operand: an ordered stage pipeline over a source tensor."""

    stages: Tuple = ()
    weight: bool = False             # honors cfg.quantize_weights; cacheable

    def __post_init__(self):
        if self.weight:
            assert not any(isinstance(s, Center) for s in self.stages), (
                "weight operands are token-free; Center does not apply")


@dataclasses.dataclass(frozen=True)
class GemmTerm:
    """One accumulated product term of a GeMM.

    kind:
      matmul    full 2-D product (orientation fixed by the GeMM, see module
                docstring)
      mean_row  1-D mean vector times the weight -> one output row,
                broadcast over tokens (the 1·(μ̄ W̄) terms of Eqs. 8-9)
      rank1     l · outer(μ̄_X, μ̄_D) — the exact rank-one term of Eq. 10
                (weight-grad only)
    """

    lhs: Operand
    rhs: Operand
    kind: str = "matmul"             # matmul | mean_row | rank1

    def __post_init__(self):
        assert self.kind in ("matmul", "mean_row", "rank1"), self.kind


@dataclasses.dataclass(frozen=True)
class GemmPlan:
    """A recipe as data: term lists for the forward / dx / dw GeMMs."""

    name: str
    fwd: Tuple[GemmTerm, ...]
    dx: Tuple[GemmTerm, ...]
    dw: Tuple[GemmTerm, ...]

    def __post_init__(self):
        for gemm, terms in (("fwd", self.fwd), ("dx", self.dx),
                            ("dw", self.dw)):
            n_sr = sum(
                1
                for t in terms
                for op in (t.lhs, t.rhs)
                for s in op.stages
                if isinstance(s, Quantize) and s.sr
            )
            assert n_sr <= 1, (
                f"plan {self.name!r}/{gemm}: {n_sr} SR stages; at most one "
                f"operand per GeMM may consume the SR key stream")
            if gemm == "dw":
                assert not any(t.rhs.weight or t.lhs.weight for t in terms), (
                    "dw contracts activations with gradients; no weights")

    def weight_specs(self, gemm: str) -> Tuple[Operand, ...]:
        """Distinct weight-operand specs of one GeMM, in declaration order."""
        seen = []
        for t in getattr(self, gemm):
            if t.rhs.weight and t.rhs not in seen:
                seen.append(t.rhs)
        return tuple(seen)


# --------------------------------------------------------------------------
# Recipe plans (the five MODES, now as data)
# --------------------------------------------------------------------------

def _op(*stages, weight=False):
    return Operand(tuple(stages), weight=weight)


_C_RES = Center(0, "residual")
_C_MU = Center(0, "mean")


def _build_plans() -> Dict[str, GemmPlan]:
    T = GemmTerm
    plans = {}

    plans["bf16"] = GemmPlan(
        "bf16",
        fwd=(T(_op(), _op(weight=True)),),
        dx=(T(_op(), _op(weight=True)),),
        dw=(T(_op(), _op()),),
    )

    plans["nvfp4"] = GemmPlan(
        "nvfp4",
        fwd=(T(_op(Quantize(-1)), _op(Quantize(0), weight=True)),),
        dx=(T(_op(Quantize(-1, sr=True)), _op(Quantize(1), weight=True)),),
        dw=(T(_op(Quantize(0)), _op(Quantize(0, sr=True))),),
    )

    plans["nvfp4_hadamard"] = GemmPlan(
        "nvfp4_hadamard",
        fwd=(T(_op(Hadamard(-1), Quantize(-1)),
               _op(Hadamard(0), Quantize(0), weight=True)),),
        dx=(T(_op(Hadamard(-1), Quantize(-1, sr=True)),
              _op(Hadamard(1), Quantize(1), weight=True)),),
        dw=(T(_op(Hadamard(0), Quantize(0)),
              _op(Hadamard(0), Quantize(0, sr=True))),),
    )

    # Eqs. 8-10: residual GeMM + explicit mean terms.
    plans["averis"] = GemmPlan(
        "averis",
        fwd=(
            T(_op(_C_RES, Quantize(-1)), _op(Quantize(0), weight=True)),
            T(_op(_C_MU, Quantize(-1)), _op(Quantize(0), weight=True),
              kind="mean_row"),
        ),
        dx=(
            T(_op(_C_RES, Quantize(-1, sr=True)),
              _op(Quantize(1), weight=True)),
            T(_op(_C_MU, Quantize(-1)), _op(Quantize(1), weight=True),
              kind="mean_row"),
        ),
        dw=(
            T(_op(_C_RES, Quantize(0)), _op(_C_RES, Quantize(0, sr=True))),
            T(_op(_C_MU, Quantize(-1)), _op(_C_MU, Quantize(-1)),
              kind="rank1"),
        ),
    )

    # Averis + Hadamard on the residual stream only: the mean path pairs
    # with the *unrotated* quantized weight (paper "combined" recipe).
    plans["averis_hadamard"] = GemmPlan(
        "averis_hadamard",
        fwd=(
            T(_op(_C_RES, Hadamard(-1), Quantize(-1)),
              _op(Hadamard(0), Quantize(0), weight=True)),
            T(_op(_C_MU, Quantize(-1)), _op(Quantize(0), weight=True),
              kind="mean_row"),
        ),
        dx=(
            T(_op(_C_RES, Hadamard(-1), Quantize(-1, sr=True)),
              _op(Hadamard(1), Quantize(1), weight=True)),
            T(_op(_C_MU, Quantize(-1)), _op(Quantize(1), weight=True),
              kind="mean_row"),
        ),
        dw=(
            T(_op(_C_RES, Hadamard(0), Quantize(0)),
              _op(_C_RES, Hadamard(0), Quantize(0, sr=True))),
            T(_op(_C_MU, Quantize(-1)), _op(_C_MU, Quantize(-1)),
              kind="rank1"),
        ),
    )
    return plans


PLANS: Dict[str, GemmPlan] = _build_plans()


def plan_for(mode: str) -> GemmPlan:
    """The GemmPlan of a recipe name. Custom plans register via PLANS."""
    try:
        return PLANS[mode]
    except KeyError:
        raise ValueError(f"no GemmPlan registered for mode {mode!r}; "
                         f"known: {sorted(PLANS)}") from None


def register_plan(plan: GemmPlan) -> None:
    """Register a custom recipe plan (new scenarios without touching the
    executor — the point of the pipeline refactor)."""
    PLANS[plan.name] = plan


# --------------------------------------------------------------------------
# Hadamard skip surfacing
# --------------------------------------------------------------------------

def reset_hadamard_skip_warnings() -> None:
    """Clear the once-per-length warning dedup on the process hub (tests)."""
    from repro.obs.telemetry import global_hub
    global_hub().reset_warnings("hadamard_skip")


def _hadamard_or_skip(t: jax.Array, axis: int) -> jax.Array:
    n = t.shape[axis]
    if n % _TILE != 0:
        # Silent-recipe-downgrade counter: surfaces in quantwatch and
        # ServeMetrics.summary(), not just the once-per-length warning.
        # Lazy import keeps repro.core free of an obs dependency at import
        # time (obs.telemetry is stdlib-only, so this costs nothing). The
        # count lands process-wide AND on the scoped hub when an engine is
        # stepping (obs.telemetry.use_hub); warn-once dedup is per hub.
        from repro.obs.telemetry import report_downgrade
        report_downgrade(
            "quant/skipped_hadamard", "hadamard_skip", str(n),
            f"Hadamard stage skipped: axis length {n} is not a multiple "
            f"of {_TILE}; the GeMM runs unrotated (correct, unsmoothed). "
            f"See plan_summary()['skipped_hadamard'].",
            stacklevel=2)
        return t
    return hadamard_tiles(t, axis)


# --------------------------------------------------------------------------
# Fused backend (Pallas kernels; repro.kernels.fused)
# --------------------------------------------------------------------------

def reset_fused_fallback_warnings() -> None:
    """Clear the once-per-reason warning dedup on the process hub (tests)."""
    from repro.obs.telemetry import global_hub
    global_hub().reset_warnings("fused_fallback")


def _fused_fallback(reason: str) -> None:
    """Loud fallback: a pipeline the fused backend was asked to run went to
    the stage path instead. Counted per occurrence (mirrors
    ``quant/skipped_hadamard``) and warned once per (hub, reason)."""
    from repro.obs.telemetry import report_downgrade
    report_downgrade(
        "quant/fused_fallback", "fused_fallback", reason,
        f"fused quant backend fell back to the stage path: {reason}. "
        f"Counted in telemetry as quant/fused_fallback.", stacklevel=3)


def _fused_interpret() -> bool:
    """Pallas execution mode: compiled Mosaic on TPU, interpret elsewhere."""
    return jax.default_backend() != "tpu"


def _classify_fused(operand: Operand, cfg, t: jax.Array):
    """Classify one operand pipeline for the fused backend.

    Returns ``("fuse", (center, rotate, transposed, use_sr))`` when the
    pipeline is a fused-kernel target at this shape, ``("side", reason)``
    for pipelines the fused backend leaves on the stage path *by design*
    (mean-vector side channels, unquantized weights — not fallbacks), or
    ``("fallback", reason)`` when a quantization pipeline the kernels should
    own cannot run fused here (counted into telemetry by the caller).
    """
    stages = list(operand.stages)
    if operand.weight and not cfg.quantize_weights:
        stages = [s for s in stages if not isinstance(s, Quantize)]
    if not any(isinstance(s, Quantize) for s in stages):
        return ("side", "no quantize stage")
    if any(isinstance(s, Center) and s.take == "mean" for s in stages):
        return ("side", "mean-vector side channel")

    center = rotate = None
    i = 0
    if i < len(stages) and isinstance(stages[i], Center):
        center = stages[i]
        i += 1
    if i < len(stages) and isinstance(stages[i], Hadamard):
        rotate = stages[i]
        i += 1
    if i != len(stages) - 1 or not isinstance(stages[i], Quantize):
        return ("fallback", f"unrecognized stage pipeline {stages!r}")
    quant = stages[i]

    if t.ndim != 2:
        return ("fallback", f"operand rank {t.ndim} != 2")
    if cfg.block_size != _TILE:
        return ("fallback", f"block_size {cfg.block_size} != {_TILE}")
    if jnp.dtype(cfg.qdq_dtype) != jnp.float32:
        return ("fallback", f"qdq_dtype {cfg.qdq_dtype} != float32 "
                            f"(kernels compute in fp32)")
    q_axis = quant.axis % 2
    transposed = q_axis == 0
    if center is not None and center.token_axis != 0:
        return ("fallback", f"token_axis {center.token_axis} != 0")
    if rotate is not None:
        if rotate.axis % 2 != q_axis:
            return ("fallback", "Hadamard axis != Quantize axis")
        if t.shape[q_axis] % _TILE != 0:
            # the stage path will skip the rotation (its own counter);
            # route there rather than silently dropping the rotation here
            return ("fallback",
                    f"ragged Hadamard axis {t.shape[q_axis]}")
    use_sr = quant.sr and cfg.sr_grad
    return ("fuse", (center is not None, rotate is not None, transposed,
                     use_sr))


def _apply_fused(
    t: jax.Array,
    how,
    *,
    sr_key: Optional[jax.Array],
    splits: Optional[dict],
) -> jax.Array:
    """Run one fused-target pipeline through the Pallas kernels.

    The token mean is computed once by ``column_mean_2d`` and memoized into
    ``splits`` so the plan's mean-row/rank1 terms consume the *same* mean
    the kernel centered against (one reduction per source, exactly like the
    stage path's shared ``split_mean``).
    """
    from repro.kernels.fused import center_hadamard_qdq_2d
    from repro.kernels.mean_split import column_mean_2d

    center, rotate, transposed, use_sr = how
    interpret = _fused_interpret()
    mu2 = None
    if center:
        if splits is not None:
            if 0 not in splits:
                mu_vec = column_mean_2d(t, interpret=interpret)
                # same (mu, res) protocol as the stage path's split_mean
                # memo; the residual is lazy (None) — it is only ever
                # materialized if a stage-path operand asks for it
                splits[0] = (mu_vec.reshape(-1).astype(t.dtype), None)
            mu2 = splits[0][0].astype(jnp.float32).reshape(1, -1)
        else:
            mu2 = column_mean_2d(t, interpret=interpret)     # (1, m) fp32
    bits = None
    if use_sr:
        bits = jax.random.bits(sr_key, t.shape, jnp.uint32)
    # Pallas has no JVP rule and quantization is non-differentiable anyway:
    # every gradient that matters is defined by the qgemm custom_vjp (and
    # prepared-weight cotangents are straight-through zeros), so cut the
    # tangent path at the kernel boundary.
    t_in = jax.lax.stop_gradient(t)
    mu_in = None if mu2 is None else jax.lax.stop_gradient(mu2)
    # transposed operands (quantize axis == token axis, the dw orientation)
    # run natively with sublane blocks — no transpose copies
    return center_hadamard_qdq_2d(t_in, mu_in, None, bits, rotate=rotate,
                                  interpret=interpret,
                                  block_axis=0 if transposed else -1)


# --------------------------------------------------------------------------
# Executor
# --------------------------------------------------------------------------

def apply_stages(
    t: jax.Array,
    operand: Operand,
    cfg,                              # QuantConfig (duck-typed; no cycle)
    *,
    sr_key: Optional[jax.Array] = None,
    splits: Optional[dict] = None,
) -> jax.Array:
    """Run one operand pipeline. ``splits`` memoizes Center per token axis so
    the mean and residual components of one source share one reduction.

    With ``cfg.backend == "fused"`` the recognized Center→Hadamard→Quantize
    pipelines run as single Pallas kernels (``repro.kernels.fused``) instead
    of separate XLA stages; unsupported shapes fall back loudly
    (``quant/fused_fallback`` telemetry + once-per-reason warning). Mean
    side channels and unquantized weights stay on the stage path by design.
    """
    if getattr(cfg, "backend", "stages") == "fused":
        kind, how = _classify_fused(operand, cfg, t)
        if kind == "fuse":
            with jax.named_scope(scopes.FUSED):
                return _apply_fused(t, how, sr_key=sr_key, splits=splits)
        if kind == "fallback":
            _fused_fallback(how)
    v = t
    for st in operand.stages:
        with jax.named_scope(scopes.STAGE[type(st).__name__]):
            if isinstance(st, Center):
                # Memoize only source-level splits (Center as first
                # stage): the mean/residual pair of one tensor is
                # computed once per GeMM.
                memoizable = splits is not None and v is t
                if memoizable and st.token_axis in splits:
                    mu, res = splits[st.token_axis]
                    if res is None and st.take == "residual":
                        # memo written by the fused backend (which never
                        # materializes the residual): rebuild it from the
                        # shared mean so both backends center identically
                        res = (v.astype(jnp.float32) - jnp.expand_dims(
                            mu.astype(jnp.float32), st.token_axis)
                               ).astype(v.dtype)
                        splits[st.token_axis] = (mu, res)
                else:
                    mu, res = split_mean(v, token_axis=st.token_axis)
                    if memoizable:
                        splits[st.token_axis] = (mu, res)
                v = res if st.take == "residual" else mu
            elif isinstance(st, Hadamard):
                v = _hadamard_or_skip(v, st.axis)
            elif isinstance(st, Quantize):
                if operand.weight and not cfg.quantize_weights:
                    continue             # bf16 weights (A4G4 without W4)
                use_sr = st.sr and cfg.sr_grad
                v = nvfp4_qdq(v, st.axis, sr=use_sr,
                              key=sr_key if use_sr else None,
                              block_size=cfg.block_size,
                              compute_dtype=jnp.dtype(cfg.qdq_dtype))
            else:                        # pragma: no cover
                raise TypeError(f"unknown stage {st!r}")
    return v


def execute_terms(
    terms: Tuple[GemmTerm, ...],
    gemm: str,                        # fwd | dx | dw
    lhs: jax.Array,
    rhs: jax.Array,
    cfg,
    *,
    out_dtype,
    sr_key: Optional[jax.Array] = None,
    prepared_rhs: Optional[Dict[Operand, jax.Array]] = None,
) -> jax.Array:
    """Evaluate one GeMM's term list and accumulate in ``cfg.comm_dtype``.

    ``prepared_rhs`` maps weight-operand specs to their already-pipelined
    arrays (quantized outside the custom VJP — see qgemm.py); non-weight
    operands are pipelined here. Terms are accumulated in declaration order.
    """
    acc = jnp.dtype(cfg.comm_dtype)
    memo: Dict[Tuple[str, Operand], jax.Array] = {}
    splits = {"lhs": {}, "rhs": {}}

    def value(op: Operand, t: jax.Array, side: str) -> jax.Array:
        if op.weight:
            return prepared_rhs[op]
        mk = (side, op)
        if mk not in memo:
            memo[mk] = apply_stages(t, op, cfg, sr_key=sr_key,
                                    splits=splits[side])
        return memo[mk]

    total = None
    with jax.named_scope(scopes.QGEMM[gemm]):
        for term in terms:
            a = value(term.lhs, lhs, "lhs")
            b = value(term.rhs, rhs, "rhs")
            with jax.named_scope(scopes.TERM[term.kind]):
                if term.kind == "matmul":
                    if gemm == "fwd":
                        v = jnp.dot(a, b, preferred_element_type=acc)
                    elif gemm == "dx":
                        v = jnp.dot(a, b.T, preferred_element_type=acc)
                    else:                    # dw
                        v = jnp.dot(a.T, b, preferred_element_type=acc)
                elif term.kind == "mean_row":
                    bt = b if gemm == "fwd" else b.T
                    v = jnp.dot(a, bt, preferred_element_type=acc)[None, :]
                else:            # rank1 (dw): l · outer(μ̄_X, μ̄_D)
                    assert gemm == "dw", "rank1 terms are weight-grad only"
                    v = lhs.shape[0] * jnp.outer(
                        a.astype(jnp.float32), b.astype(jnp.float32)
                    ).astype(acc)
                total = v if total is None else total + v
        return total.astype(out_dtype)


# --------------------------------------------------------------------------
# Static plan summary (shapes only; no tracing)
# --------------------------------------------------------------------------

def _stage_shapes(shape: Tuple[int, ...], operand: Operand):
    """Walk one pipeline symbolically; yield (stage, axis_len, skipped)."""
    shape = list(shape)
    out = []
    for st in operand.stages:
        if isinstance(st, Center):
            if st.take == "mean":
                del shape[st.token_axis]
            out.append((st, None, False))
        elif isinstance(st, Hadamard):
            n = shape[st.axis]
            out.append((st, n, n % _TILE != 0))
        else:
            out.append((st, shape[st.axis], False))
    return out, tuple(shape)


def plan_summary(plan: GemmPlan, x_shape: Tuple[int, int],
                 w_shape: Tuple[int, int]) -> Dict:
    """Static description of what a plan does at given operand shapes.

    Returns per-GeMM term/stage listings plus ``skipped_hadamard`` flags —
    the surfaced form of the silent ragged-axis Hadamard skip: a stage is
    flagged when its axis length is not 16-aligned at these shapes.
    """
    l, m = x_shape
    n = w_shape[1]
    shapes = {
        "fwd": ((l, m), (m, n)),
        "dx": ((l, n), (m, n)),
        "dw": ((l, m), (l, n)),
    }
    summary: Dict = {"plan": plan.name, "skipped_hadamard": False, "gemms": {}}
    for gemm in ("fwd", "dx", "dw"):
        lhs_shape, rhs_shape = shapes[gemm]
        terms = []
        g_skip = False
        for t in getattr(plan, gemm):
            entry = {"kind": t.kind, "operands": []}
            for side, op, shape in (("lhs", t.lhs, lhs_shape),
                                    ("rhs", t.rhs, rhs_shape)):
                stages, _ = _stage_shapes(shape, op)
                skips = [
                    {"stage": type(st).__name__, "axis_len": n_ax,
                     "skipped": skip}
                    for st, n_ax, skip in stages
                ]
                op_skip = any(s["skipped"] for s in skips)
                g_skip = g_skip or op_skip
                entry["operands"].append(
                    {"side": side, "weight": op.weight, "stages": skips,
                     "skipped_hadamard": op_skip})
            terms.append(entry)
        summary["gemms"][gemm] = {"terms": terms, "skipped_hadamard": g_skip}
        summary["skipped_hadamard"] = summary["skipped_hadamard"] or g_skip
    return summary
