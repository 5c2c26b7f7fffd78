"""Training step construction: loss, grads, optimizer, grad accumulation,
and the mesh-aware sharded step with wire-format gradient collectives.

``make_train_step`` builds the jit-able pure function
    (params, opt_state, batch, step_key) -> (params, opt_state, metrics)
with the FP4 recipe — or a full per-site :class:`PrecisionPolicy`
(``quant_policy`` spec strings like ``"averis;lm_head=bf16"``) — baked in.
Given a mesh (or ``dp_shards > 1``) it returns the sharded step instead.

Gradient accumulation is a ``lax.scan`` over microbatches (the standard
large-batch idiom: per-step HBM footprint is one microbatch's activations).
Weight QDQ is hoisted out of it: ``model.prepare_qweights`` runs once per
optimizer step, *before* ``jax.grad`` and the scan, so every (param,
plan-operand) pair is quantized exactly once per step and enters the scan as
a loop-invariant. SR gradient streams stay keyed per-microbatch.

Sharded step (``make_sharded_train_step``) — the W4A4**G4** system story:

* params and optimizer moments are stored sharded per
  :class:`repro.parallel.sharding.ShardingRules` (FSDP over the data axis);
  inside the ``shard_map`` body they are all-gathered for compute and the
  updated values sliced back to local shards (storage sharded, update
  replicated — the simulation-faithful layout for wire accounting).
* the batch is split into ``dp_shards`` **virtual DP shards** (default: the
  mesh's data-parallel device count). Each shard's gradients are encoded
  per-bucket with the comm recipes of ``repro.parallel.collectives``
  (``comm=nvfp4_centered`` = exact fp32 bucket mean + blockwise NVFP4 QDQ
  of the centered residual, error feedback in optimizer state), gathered,
  and folded in global shard order.
* because encoding happens **per shard** (not per device) and the fold
  order is the shard order, the step is *bitwise identical* for any device
  count dividing ``dp_shards``: 8 shards on 8 devices == 8 shards on 1
  device. That is how the single-device identity path certifies the
  8-device subprocess test, and vice versa.
* with one shard there is no wire (``dp_shards == 1`` -> identity codec),
  matching the plain single-device step bitwise.

``TrainConfig.grad_compression`` (the optimizer-hook path) now also routes
through the collectives registry: any comm recipe name is accepted, and the
former ``optim/compress.py`` int8 error-feedback transform is the registered
``int8_ef`` recipe (numerics preserved; legacy alias ``ef_int8`` accepted).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.policy import PrecisionPolicy
from repro.models.layers import QuantCtx
from repro.models.model import Model
from repro.optim import adamw
from repro.parallel import collectives as coll
from repro.parallel.sharding import ShardingRules


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    quant_mode: str = "bf16"
    quant_policy: str = ""           # PrecisionPolicy spec; when set it
                                     # overrides quant_mode (which remains the
                                     # single-recipe shorthand)
    microbatches: int = 1            # gradient-accumulation factor
    optimizer: adamw.OptimizerConfig = adamw.OptimizerConfig()
    grad_compression: str = "none"   # comm recipe applied as an optimizer
                                     # grad transform every step (none |
                                     # int8_ef | bf16 | nvfp4 |
                                     # nvfp4_centered | ...); legacy alias
                                     # ef_int8 accepted
    comm_recipe: str = ""            # DP gradient-wire recipe for the
                                     # sharded step; "" defers to the
                                     # policy's comm= clause, then
                                     # grad_compression, then fp32
    comm_bucket_mb: float = 4.0      # flat-buffer bucket size (MiB)
    wire_format: str = "packed"      # nvfp4 DP-wire transport: "packed"
                                     # ships WirePacket bytes and decodes
                                     # inside the fold (~0.56S bytes/elem
                                     # read); "decoded" is the legacy QDQ
                                     # simulation (4S bytes/elem). EF
                                     # numerics are identical either way.
    quant_probes: bool = False       # in-graph quant-health probes
                                     # (repro.obs.probes): per-site stats
                                     # land in the step metrics under
                                     # "quant_probes" (+ "comm_probes" on
                                     # the sharded path). Off by default —
                                     # the gate is STATIC: off traces the
                                     # exact pre-probe graph.


def resolve_policy(tcfg: TrainConfig, model: Optional[Model] = None
                   ) -> PrecisionPolicy:
    """TrainConfig (+ optional per-arch ModelConfig default) -> policy.

    Precedence: tcfg.quant_policy > model.cfg.quant_policy > tcfg.quant_mode.
    """
    spec = tcfg.quant_policy
    if not spec and model is not None:
        spec = getattr(model.cfg, "quant_policy", "") or ""
    return PrecisionPolicy.parse(spec or tcfg.quant_mode)


def resolve_comm_recipe(tcfg: TrainConfig, policy: PrecisionPolicy) -> str:
    """The sharded step's default wire recipe (canonical registry name).

    Precedence: ``tcfg.comm_recipe`` (the explicit flag) > the policy's
    ``comm=`` clause > ``tcfg.grad_compression`` > lossless fp32. Per-tensor
    ``comm.<pattern>=`` clauses always apply on top.
    """
    name = tcfg.comm_recipe or policy.comm_default
    if not name and tcfg.grad_compression not in ("", "none"):
        name = tcfg.grad_compression
    return coll.get_comm_recipe(name or "fp32").name


def make_loss_fn(model: Model, qcfg, probe: bool = False):
    """``qcfg``: QuantConfig or PrecisionPolicy (both accepted by QuantCtx).

    ``qweights`` (optional) is the per-step quantized-weight cache from
    ``model.prepare_qweights`` — its arrays are constants w.r.t. the grad
    trace (straight-through dW targets the raw params, so gradients are
    unchanged by the hoist).

    ``probe=True`` installs a quant-health tape on the ``QuantCtx``; the
    per-GeMM-site stats (``repro.obs.probes``) come back under
    ``metrics["quant_probes"]``. The gate is static: ``probe=False`` builds
    the exact pre-probe graph (probes live under ``stop_gradient``, so even
    on, the loss and gradients are untouched — only extra outputs appear).
    """

    def loss_fn(params, batch, key, qweights=None):
        tape: Dict[str, Any] = {}
        ctx = QuantCtx(qcfg, key, qweights=qweights,
                       probes=tape if probe else None)
        loss, metrics = model.loss(params, batch, ctx)
        if probe:
            metrics = dict(metrics, quant_probes=tape)
        return loss, metrics

    return loss_fn


def _make_shard_grads(model: Model, tcfg: TrainConfig, grad_fn):
    """(params, batch_shard, key, qweights) -> (loss, metrics, grads) with
    the microbatch accumulation scan applied inside the shard."""

    def shard_grads(params, batch, key, qweights):
        if tcfg.microbatches > 1:
            n = tcfg.microbatches
            micro = jax.tree.map(
                lambda a: a.reshape((n, a.shape[0] // n) + a.shape[1:]), batch
            )
            keys = jax.random.split(key, n)

            def body(carry, xs):
                g_acc, l_acc = carry
                mb, k = xs
                (loss, mets), grads = grad_fn(params, mb, k, qweights)
                g_acc = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32) / n, g_acc, grads
                )
                # Probe tape as scan ys ({} when probes are off — zero
                # leaves, so the probe-free jaxpr is unchanged).
                return ((g_acc, l_acc + loss / n),
                        mets.get("quant_probes", {}))

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            (grads, loss), tapes = jax.lax.scan(body, (g0, 0.0), (micro, keys))
            metrics = {}
            if jax.tree_util.tree_leaves(tapes):
                metrics["quant_probes"] = jax.tree.map(
                    lambda a: jnp.mean(a, axis=0), tapes)
            return loss, metrics, grads
        (loss, metrics), grads = grad_fn(params, batch, key, qweights)
        return loss, metrics, grads

    return shard_grads


def make_train_step(
    model: Model, tcfg: TrainConfig, *,
    mesh: Optional[Mesh] = None,
    rules: Optional[ShardingRules] = None,
    dp_shards: Optional[int] = None,
) -> Callable[..., Tuple[Any, Any, Dict[str, jax.Array]]]:
    """Single-device step, or the sharded step when a mesh (or a virtual
    shard count > 1) is given."""
    if mesh is not None or (dp_shards or 1) > 1:
        return make_sharded_train_step(model, tcfg, mesh, rules=rules,
                                       dp_shards=dp_shards)
    if tcfg.comm_recipe:
        raise ValueError(
            f"TrainConfig.comm_recipe={tcfg.comm_recipe!r} selects the DP "
            f"gradient wire, which only exists on the sharded path — pass "
            f"mesh=/dp_shards>1 (or use grad_compression for the "
            f"optimizer-hook codec); refusing to drop it silently")
    policy = resolve_policy(tcfg, model)
    grad_fn = jax.value_and_grad(
        make_loss_fn(model, policy, probe=tcfg.quant_probes), has_aux=True)
    shard_grads = _make_shard_grads(model, tcfg, grad_fn)
    transform = None
    if tcfg.grad_compression not in ("", "none"):
        transform = coll.make_comm_transform(
            recipe=tcfg.grad_compression, policy=policy,
            bucket_mb=tcfg.comm_bucket_mb)

    def train_step(params, opt_state, batch, step_key):
        # Per-step quantized-weight cache: built once here, OUTSIDE grad and
        # the microbatch scan, so the QDQ of every weight is loop-invariant
        # (params only change at apply_updates below). Inside the scan the
        # cache arrays are closure constants — hoisted, not recomputed.
        qweights = model.prepare_qweights(params, policy)
        loss, metrics, grads = shard_grads(params, batch, step_key, qweights)
        params, opt_state, opt_metrics = adamw.apply_updates(
            params, grads, opt_state, tcfg.optimizer, grad_transform=transform
        )
        out = {"loss": loss, **metrics, **opt_metrics}
        return params, opt_state, out

    return train_step


def make_traced_train_step(model: Model, tcfg: TrainConfig, tracer):
    """Single-device step split into separately-jitted, span-wrapped phases.

    The four phases of ``make_train_step``'s fused body — prepare_qweights,
    microbatch scan, encode/reduce/fold (clip + the grad-compression
    codec), optimizer — each run under a ``repro.obs.trace.ChromeTracer``
    span bracketed by ``jax.block_until_ready``, so the trace shows real
    phase durations instead of async dispatch time.

    Numerically identical to the fused step: phase 3 replicates
    ``adamw.apply_updates``' clip -> grad_transform ordering exactly, and
    phase 4 re-runs ``apply_updates`` with clipping disabled and no
    transform (its stale ``grad_norm`` is overwritten with phase 3's).
    The split costs one extra device round-trip per phase — a tracing
    mode, not the production step.
    """
    if tcfg.comm_recipe:
        raise ValueError("the traced step is single-device; comm_recipe "
                         "selects the sharded DP wire")
    policy = resolve_policy(tcfg, model)
    grad_fn = jax.value_and_grad(
        make_loss_fn(model, policy, probe=tcfg.quant_probes), has_aux=True)
    shard_grads = jax.jit(_make_shard_grads(model, tcfg, grad_fn))
    prepare = jax.jit(lambda p: model.prepare_qweights(p, policy))
    transform = None
    if tcfg.grad_compression not in ("", "none"):
        transform = coll.make_comm_transform(
            recipe=tcfg.grad_compression, policy=policy,
            bucket_mb=tcfg.comm_bucket_mb)

    def _encode_reduce_fold(grads, opt_state):
        metrics: Dict[str, jax.Array] = {}
        if tcfg.optimizer.clip_norm > 0:
            grads, gnorm = adamw.clip_by_global_norm(
                grads, tcfg.optimizer.clip_norm)
            metrics["grad_norm"] = gnorm
        else:
            metrics["grad_norm"] = adamw.global_norm(grads)
        if transform is not None:
            grads, opt_state = transform(grads, opt_state)
        return grads, opt_state, metrics

    encode = jax.jit(_encode_reduce_fold)
    nocip = dataclasses.replace(tcfg.optimizer, clip_norm=0.0)
    apply_fn = jax.jit(
        lambda p, g, s: adamw.apply_updates(p, g, s, nocip))

    def train_step(params, opt_state, batch, step_key):
        with tracer.span("train.prepare_qweights", cat="train"):
            qweights = prepare(params)
            jax.block_until_ready(qweights)
        with tracer.span("train.microbatch_scan", cat="train"):
            loss, metrics, grads = shard_grads(params, batch, step_key,
                                               qweights)
            jax.block_until_ready((loss, grads))
        with tracer.span("train.encode_reduce_fold", cat="train"):
            grads, opt_state, gmetrics = encode(grads, opt_state)
            jax.block_until_ready(grads)
        with tracer.span("train.optimizer", cat="train"):
            params, opt_state, opt_metrics = apply_fn(params, grads,
                                                      opt_state)
            jax.block_until_ready(params)
        out = {"loss": loss, **metrics, **opt_metrics, **gmetrics}
        return params, opt_state, out

    return train_step


# --------------------------------------------------------------------------
# Sharded step: gather/slice by PartitionSpec + wire-format DP reduction
# --------------------------------------------------------------------------

def _spec_entries(spec) -> Tuple:
    return tuple(spec) if spec is not None else ()


def _gather_by_spec(x: jax.Array, spec) -> jax.Array:
    """Local shard -> full array inside shard_map (inverse of the storage
    sharding). Tuple entries gather innermost (fastest-varying) axis first
    so block order matches the pod-major device layout."""
    for d, entry in enumerate(_spec_entries(spec)):
        if entry is None:
            continue
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        for a in reversed(axes):
            x = jax.lax.all_gather(x, a, axis=d, tiled=True)
    return x


def _slice_by_spec(x: jax.Array, spec, mesh: Mesh) -> jax.Array:
    """Full array -> this device's shard (the storage layout for outputs)."""
    for d, entry in enumerate(_spec_entries(spec)):
        if entry is None:
            continue
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        size = 1
        idx = 0
        for a in axes:
            size *= mesh.shape[a]
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        n = x.shape[d] // size
        x = jax.lax.dynamic_slice_in_dim(x, idx * n, n, axis=d)
    return x


def _is_logical_leaf(t) -> bool:
    return isinstance(t, tuple) and all(
        isinstance(e, (str, type(None))) for e in t)


def _grad_shapes(params, tcfg: TrainConfig):
    """The gradient tree's shapes/dtypes for this config: the microbatch
    scan accumulates in fp32, so under accumulation the wire (bucket keys,
    EF dtypes, decoded-gradient dtype) must be fp32 even when params are
    not — keying it to param dtypes would silently downcast the reduced
    gradients and orphan EF buffers."""
    if tcfg.microbatches == 1:
        return params
    return jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), params)


def make_sharded_train_step(
    model: Model, tcfg: TrainConfig,
    mesh: Optional[Mesh] = None, *,
    rules: Optional[ShardingRules] = None,
    dp_shards: Optional[int] = None,
):
    """Mesh-aware train step with the DP reduce on the simulated wire.

    See the module docstring for the layout. Do not wrap calls in
    ``sharding.use_rules`` — the body runs under manual (shard_map) axes
    where ``with_sharding_constraint`` does not apply.
    """
    policy = resolve_policy(tcfg, model)
    grad_fn = jax.value_and_grad(
        make_loss_fn(model, policy, probe=tcfg.quant_probes), has_aux=True)
    shard_grads = _make_shard_grads(model, tcfg, grad_fn)

    if mesh is None:
        mesh = jax.make_mesh((1,), ("data",))
    rules = rules or ShardingRules(mesh)
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    if not dp_axes:
        raise ValueError("sharded train step needs a 'data' and/or 'pod' "
                         f"mesh axis; got {mesh.axis_names}")
    s_dev = 1
    for a in dp_axes:
        s_dev *= mesh.shape[a]
    S = dp_shards if dp_shards is not None else s_dev
    if S % s_dev != 0:
        raise ValueError(f"dp_shards={S} must be a multiple of the mesh's "
                         f"DP device count {s_dev}")
    n_local = S // s_dev
    dp_entry = dp_axes[0] if len(dp_axes) == 1 else dp_axes
    codec_on = S > 1                    # identity wire on a single shard

    if tcfg.wire_format not in ("packed", "decoded"):
        raise ValueError(f"TrainConfig.wire_format={tcfg.wire_format!r}; "
                         f"expected 'packed' or 'decoded'")
    packed_wire = codec_on and tcfg.wire_format == "packed"

    wire = resolve_comm_recipe(tcfg, policy)
    aparams = model.abstract_params()
    pspecs = jax.tree.map(
        lambda log, a: rules.spec(log, a.shape),
        model.param_logical(), aparams, is_leaf=_is_logical_leaf)
    agrads = _grad_shapes(aparams, tcfg)
    layout = coll.build_layout(agrads, default_recipe=wire, policy=policy,
                               bucket_mb=tcfg.comm_bucket_mb)
    ef_names = frozenset(layout.ef_dtypes()) if codec_on else frozenset()

    opt_specs: Dict[str, Any] = {"step": P(), "m": pspecs, "v": pspecs}
    if ef_names:
        opt_specs["comm"] = {"ef": {n: P(dp_entry) for n in ef_names}}

    def body(params_l, opt_l, batch_l, key):
        params_f = jax.tree.map(_gather_by_spec, params_l, pspecs)
        m_f = jax.tree.map(_gather_by_spec, opt_l["m"], pspecs)
        v_f = jax.tree.map(_gather_by_spec, opt_l["v"], pspecs)
        qweights = model.prepare_qweights(params_f, policy)

        dev = 0
        for a in dp_axes:
            dev = dev * mesh.shape[a] + jax.lax.axis_index(a)
        base = dev * n_local

        shards = jax.tree.map(
            lambda a: a.reshape((n_local, a.shape[0] // n_local)
                                + a.shape[1:]), batch_l)

        wires: Dict[str, list] = {b.name: [] for b in layout.buckets}
        new_ef: Dict[str, list] = {n: [] for n in ef_names}
        losses = []
        probe_tapes, comm_tapes = [], []
        # Python-unrolled over this device's local shards: n_local is 1 in
        # real multi-device runs; only the laptop simulation of a large
        # mesh (dp_shards >> devices) pays the n_local-x trace cost.
        for j in range(n_local):
            sb = jax.tree.map(lambda a: a[j], shards)
            # Keys are folded by *global shard index* so SR streams are
            # topology-invariant; with a single shard the raw step key
            # passes through, matching the plain single-device step bitwise.
            k_s = (key if S == 1
                   else jax.random.fold_in(key, base + j))
            loss_s, mets_s, grads_s = shard_grads(params_f, sb, k_s, qweights)
            flats = coll.bucketize(layout, grads_s)
            ef_rows = ({n: opt_l["comm"]["ef"][n][j] for n in ef_names}
                       if ef_names else None)
            w_j, ef_j = coll.encode_shard_buckets(layout, flats, ef_rows,
                                                  codec_on=codec_on,
                                                  packed=packed_wire)
            if tcfg.quant_probes:
                probe_tapes.append(mets_s.get("quant_probes", {}))
                # probes consume the production wires (packets decoded
                # under stop_gradient) instead of re-encoding each bucket
                comm_tapes.append(coll.bucket_probe_stats(
                    layout, flats, ef_rows, codec_on=codec_on,
                    wires=w_j if codec_on else None))
            for b in layout.buckets:
                wires[b.name].append(w_j[b.name])
            for n in ef_names:
                new_ef[n].append(ef_j[n])
            losses.append(loss_s.astype(jnp.float32))

        def gather_stacked(stack):
            # (n_local, ...) per device -> (S, ...) in global shard order
            for a in reversed(dp_axes):
                stack = jax.lax.all_gather(stack, a, axis=0, tiled=True)
            return stack

        # Fold in shard order (collectives.fold_shards / fold_packet_shards)
        # — the same sequence of fp32 adds on every device count dividing S,
        # which is what makes 1-device and 8-device runs bitwise-identical.
        # Packed buckets stack/gather leaf-wise (WirePacket is a pytree:
        # u8 codes, u8 scale bytes, fp32 amax/mean scalars) and the fold
        # decodes the packed bytes in-register.
        acc_flats = {}
        for b in layout.buckets:
            if isinstance(wires[b.name][0], coll.WirePacket):
                pk = jax.tree.map(
                    lambda *xs: gather_stacked(jnp.stack(xs)),
                    *wires[b.name])
                acc_flats[b.name] = coll.fold_packet_shards(
                    coll.get_comm_recipe(b.recipe), pk, S, n=b.size)
            else:
                acc_flats[b.name] = coll.fold_shards(
                    gather_stacked(jnp.stack(wires[b.name])), S)
        # decode onto the *gradient* tree (fp32 under microbatch
        # accumulation — the plain step feeds apply_updates exactly this)
        grads_hat = coll.debucketize(layout, acc_flats, agrads)
        loss = coll.fold_shards(gather_stacked(jnp.stack(losses)), S)

        state_f = {"step": opt_l["step"], "m": m_f, "v": v_f}
        params_new, state_new, opt_metrics = adamw.apply_updates(
            params_f, grads_hat, state_f, tcfg.optimizer)

        slice_tree = lambda t: jax.tree.map(
            lambda x, sp: _slice_by_spec(x, sp, mesh), t, pspecs)
        opt_out: Dict[str, Any] = {
            "step": state_new["step"],
            "m": slice_tree(state_new["m"]),
            "v": slice_tree(state_new["v"]),
        }
        if ef_names:
            opt_out["comm"] = {"ef": {n: jnp.stack(new_ef[n])
                                      for n in ef_names}}
        metrics = {"loss": loss, **opt_metrics}
        if tcfg.quant_probes:
            # Same stack -> gather -> fixed-order fold as the wire itself,
            # so probe values are bitwise shard-count-invariant too.
            fold_tapes = lambda tapes: jax.tree.map(
                lambda *xs: coll.fold_shards(
                    gather_stacked(jnp.stack(xs)), S), *tapes)
            metrics["quant_probes"] = fold_tapes(probe_tapes)
            metrics["comm_probes"] = fold_tapes(comm_tapes)
        return slice_tree(params_new), opt_out, metrics

    def train_step(params, opt_state, batch, step_key):
        b = jax.tree.leaves(batch)[0].shape[0]
        if b % S != 0:
            raise ValueError(f"batch size {b} not divisible by "
                             f"dp_shards={S}")
        batch_specs = jax.tree.map(lambda _: P(dp_entry), batch)
        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(pspecs, opt_specs, batch_specs, P()),
                           out_specs=(pspecs, opt_specs, P()),
                           check_vma=False)
        return fn(params, opt_state, batch, step_key)

    # Shardings for jax.jit: inputs land where shard_map's out_specs put
    # the outputs, so donated params/optimizer buffers alias (host or
    # single-device inputs, e.g. a restored checkpoint, are placed first)
    named = lambda t: jax.tree.map(lambda sp: NamedSharding(mesh, sp), t,
                                   is_leaf=lambda x: isinstance(x, P))
    state_shardings = (named(pspecs), named(opt_specs))
    train_step.in_shardings = (*state_shardings,
                               NamedSharding(mesh, P(dp_entry)),
                               NamedSharding(mesh, P()))
    train_step.out_shardings = (*state_shardings, NamedSharding(mesh, P()))
    train_step.mesh = mesh
    train_step.dp_shards = S
    train_step.comm_layout = layout
    train_step.comm_recipe = wire
    train_step.wire_format = "packed" if packed_wire else "decoded"
    return train_step


def init_train_state(model: Model, tcfg: TrainConfig, key: jax.Array, *,
                     dp_shards: Optional[int] = None):
    """Params + optimizer state; ``dp_shards`` must match the sharded step's
    virtual shard count so error-feedback buffers get one row per wire
    participant (omit it for the single-device / grad-transform path)."""
    params = model.init(key)
    opt_state = adamw.init_state(params)
    policy = resolve_policy(tcfg, model)
    # EF buffers must key to the same (recipe, dtype) buckets the wire
    # builds from the *gradient* tree — see _grad_shapes.
    if dp_shards is not None:
        if dp_shards > 1:
            opt_state.update(coll.init_comm_state(
                _grad_shapes(params, tcfg),
                default_recipe=resolve_comm_recipe(tcfg, policy),
                policy=policy, bucket_mb=tcfg.comm_bucket_mb,
                dp_shards=dp_shards))
    elif tcfg.grad_compression not in ("", "none"):
        opt_state.update(coll.init_comm_state(
            _grad_shapes(params, tcfg),
            default_recipe=tcfg.grad_compression, policy=policy,
            bucket_mb=tcfg.comm_bucket_mb))
    return params, opt_state


def make_eval_step(model: Model, quant_mode: str):
    """Forward-only eval under a given recipe or policy spec (the paper's
    'NVFP4 forward evaluation' protocol for downstream numbers)."""
    policy = PrecisionPolicy.parse(quant_mode)

    def eval_step(params, batch, key):
        ctx = QuantCtx(policy, key)
        loss, metrics = model.loss(params, batch, ctx)
        return {"loss": loss, **metrics}

    return eval_step
