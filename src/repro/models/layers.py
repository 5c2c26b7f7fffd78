"""Shared model building blocks: parameter defs, norms, RoPE, FFNs.

Parameters are declared as ``Param`` specs (shape + logical axes + init), so
the same declaration drives real initialization, ``jax.eval_shape`` dry-run
trees, and sharding-spec extraction — no framework magic.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.policy import PrecisionPolicy
from repro.core.qgemm import QuantConfig, qgemm
from repro.parallel.sharding import constrain


# --------------------------------------------------------------------------
# Param declaration system
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Param:
    shape: Tuple[int, ...]
    logical: Tuple[Optional[str], ...]
    init: str = "normal"       # normal | zeros | ones | custom:<name>
    scale: float = 0.02

    def __post_init__(self):
        assert len(self.shape) == len(self.logical), (self.shape, self.logical)


def _init_leaf(p: Param, key, dtype):
    if p.init == "normal":
        return (jax.random.normal(key, p.shape, jnp.float32) * p.scale).astype(dtype)
    if p.init == "zeros":
        return jnp.zeros(p.shape, dtype)
    if p.init == "ones":
        return jnp.ones(p.shape, dtype)
    if p.init == "mamba_A_log":
        # A in [1, 16] -> A_log = log(A); standard Mamba2 init.
        u = jax.random.uniform(key, p.shape, jnp.float32, 1.0, 16.0)
        return jnp.log(u).astype(dtype)
    if p.init == "mamba_dt_bias":
        # softplus(dt_bias) uniform-ish in [1e-3, 1e-1].
        u = jax.random.uniform(key, p.shape, jnp.float32, math.log(1e-3), math.log(1e-1))
        dt = jnp.exp(u)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)  # inv softplus
    raise ValueError(p.init)


def is_param(x) -> bool:
    return isinstance(x, Param)


def init_tree(defs: Dict[str, Any], key: jax.Array, dtype=jnp.float32):
    """Materialize a (nested) dict of Param defs into arrays."""
    leaves, treedef = jax.tree.flatten(defs, is_leaf=is_param)
    keys = jax.random.split(key, len(leaves))
    vals = [_init_leaf(p, k, dtype) for p, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def logical_tree(defs: Dict[str, Any], prepend: Tuple[Optional[str], ...] = ()):
    """Extract the logical-axes tree (optionally prepending stacked dims)."""
    return jax.tree.map(lambda p: prepend + p.logical, defs, is_leaf=is_param)


def shape_tree(defs: Dict[str, Any], prepend: Tuple[int, ...] = ()):
    return jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(prepend + p.shape, jnp.float32),
        defs,
        is_leaf=is_param,
    )


# --------------------------------------------------------------------------
# Quantization context: routes every weight GeMM through repro.core.qgemm
# --------------------------------------------------------------------------

@dataclasses.dataclass
class QuantCtx:
    """Routes every weight GeMM through the per-site precision policy.

    ``policy`` maps (role, layer) -> QuantConfig (a bare QuantConfig is
    wrapped as a uniform policy for back-compat); ``key`` seeds the SR
    streams with ``site`` disambiguating GeMMs inside one block; ``layer``
    is the static layer index of the current scan segment (None outside the
    stack).

    ``path`` is the static tag chain accumulated through :meth:`child` —
    together with ``site`` it addresses one GeMM call site
    (``transformer.gemm_weight_sites``). ``prepared`` maps those addresses
    to this layer's pre-quantized weight operands, and ``qweights`` is the
    whole per-step quantized-weight cache (``Model.prepare_qweights``
    output: built once per optimizer step, outside ``jax.grad`` and the
    microbatch loop, because weight tracers inside those are per-trace and
    nothing computed there can be hoisted).
    """

    policy: PrecisionPolicy
    key: jax.Array
    layer: Optional[int] = None
    path: Tuple[int, ...] = ()
    prepared: Optional[Dict] = None
    qweights: Optional[Dict] = None
    probes: Optional[Dict] = None    # quant-health tape: {"role/path.site":
                                     # stats dict} appended by gemm() when
                                     # installed; None = probes statically
                                     # off (the traced graph is then
                                     # byte-identical to a probe-free build)

    def __post_init__(self):
        if isinstance(self.policy, QuantConfig):
            self.policy = PrecisionPolicy.uniform(self.policy)

    @property
    def cfg(self) -> QuantConfig:
        """The policy's default recipe (site-independent back-compat view)."""
        return self.policy.default

    def resolve(self, role: Optional[str]) -> QuantConfig:
        return self.policy.resolve(role, self.layer)

    def _prep(self, site: int):
        if self.prepared is None:
            return None
        return self.prepared.get(self.path + (site,))

    def _probe(self, x: jax.Array, site: int, role: Optional[str],
               cfg: QuantConfig) -> None:
        # Probing happens HERE, on the forward activation before it enters
        # the qgemm custom_vjp (whose fwd runs under tracing machinery that
        # must not leak side-channel tracers). stop_gradient inside
        # gemm_site_stats keeps the probe a pure read.
        from repro.obs.probes import gemm_site_stats

        x2 = x.reshape(-1, x.shape[-1])
        key = f"{role or 'default'}/{'.'.join(map(str, self.path + (site,)))}"
        self.probes[key] = gemm_site_stats(x2, cfg)

    def gemm(self, x: jax.Array, w: jax.Array, site: int,
             role: Optional[str] = None, prepared=None) -> jax.Array:
        cfg = self.resolve(role)
        if self.probes is not None:
            self._probe(x, site, role, cfg)
        with jax.named_scope(role or "default"):
            return qgemm(x, w, cfg,
                         jax.random.fold_in(self.key, site),
                         prepared=prepared if prepared is not None
                         else self._prep(site))

    def gemm_expert(self, x: jax.Array, w: jax.Array, site: int,
                    role: Optional[str] = None) -> jax.Array:
        from repro.core.qgemm import qgemm_expert

        cfg = self.resolve(role)
        if self.probes is not None:
            # Expert GeMMs share one site address: probe the token stream
            # flattened across experts (the quantizer sees per-expert
            # blocks, but the site-level health signal is the pooled one).
            self._probe(x.reshape(-1, x.shape[-1]), site, role, cfg)
        with jax.named_scope(role or "default"):
            return qgemm_expert(x, w, cfg,
                                jax.random.fold_in(self.key, site),
                                prepared=self._prep(site))

    def child(self, tag: int) -> "QuantCtx":
        return QuantCtx(self.policy, jax.random.fold_in(self.key, tag),
                        layer=self.layer, path=self.path + (tag,),
                        prepared=self.prepared, qweights=self.qweights,
                        probes=self.probes)


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------

def rms_norm(x: jax.Array, gain: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * gain.astype(jnp.float32)).astype(x.dtype)


def gated_rms_norm(y: jax.Array, z: jax.Array, gain: jax.Array) -> jax.Array:
    """Mamba2 output norm: RMSNorm(y * silu(z))."""
    return rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype), gain)


# --------------------------------------------------------------------------
# RoPE (standard + M-RoPE)
# --------------------------------------------------------------------------

def rope_angles(
    positions: jax.Array,  # (b, s) int or (b, 3, s) for mrope
    head_dim: int,
    theta: float,
    mrope_sections: Tuple[int, ...] = (),
) -> Tuple[jax.Array, jax.Array]:
    """Return cos/sin of shape (b, s, head_dim//2), fp32."""
    half = head_dim // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    if positions.ndim == 2:  # standard
        ang = positions[..., None].astype(jnp.float32) * inv_freq  # (b,s,half)
    else:  # M-RoPE: (b, 3, s); frequency slots assigned to t/h/w sections
        assert sum(mrope_sections) == half, (mrope_sections, half)
        sect_id = jnp.repeat(
            jnp.arange(len(mrope_sections)),
            jnp.asarray(mrope_sections),
            total_repeat_length=half,
        )  # (half,) in {0,1,2}
        ang_all = positions[..., None].astype(jnp.float32) * inv_freq  # (b,3,s,half)
        onehot = jax.nn.one_hot(sect_id, len(mrope_sections), dtype=jnp.float32)
        ang = jnp.einsum("bksh,hk->bsh", ang_all, onehot)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (b, s, n_heads, head_dim); split-half rotation."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[:, :, None, :].astype(jnp.float32)
    s = sin[:, :, None, :].astype(jnp.float32)
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [x1f * c - x2f * s, x2f * c + x1f * s], axis=-1
    ).astype(x.dtype)


# --------------------------------------------------------------------------
# FFN (dense SwiGLU / GELU)
# --------------------------------------------------------------------------

def ffn_defs(d_model: int, d_ff: int, ffn_type: str) -> Dict[str, Param]:
    if ffn_type == "swiglu":
        return {
            "w_gate": Param((d_model, d_ff), ("embed", "mlp")),
            "w_up": Param((d_model, d_ff), ("embed", "mlp")),
            "w_down": Param((d_ff, d_model), ("mlp", "embed")),
        }
    return {
        "w_up": Param((d_model, d_ff), ("embed", "mlp")),
        "w_down": Param((d_ff, d_model), ("mlp", "embed")),
    }


def ffn_apply(p, x: jax.Array, ctx: QuantCtx, ffn_type: str) -> jax.Array:
    if ffn_type == "swiglu":
        g = ctx.gemm(x, p["w_gate"], site=20, role="mlp_up")
        u = ctx.gemm(x, p["w_up"], site=21, role="mlp_up")
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
        h = constrain(h, ("batch", "seq", "mlp"))
        return ctx.gemm(h, p["w_down"], site=22, role="mlp_down")
    u = ctx.gemm(x, p["w_up"], site=21, role="mlp_up")
    h = jax.nn.gelu(u.astype(jnp.float32)).astype(x.dtype)
    h = constrain(h, ("batch", "seq", "mlp"))
    return ctx.gemm(h, p["w_down"], site=22, role="mlp_down")
