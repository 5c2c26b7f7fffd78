"""Gradient-communication subsystem: bucketed, policy-routed wire codecs.

The paper's recipe is W4A4**G4** and its central claim — the rank-one mean
component drives FP4 dynamic-range inflation, so split it off at the source
and quantize the residual — applies to the data-parallel gradient all-reduce
exactly as it does to the GeMMs. This module makes the gradient wire a
first-class quantization site built from the *same* stage primitives as the
GeMM core (``repro.core.pipeline``):

    nvfp4_centered bucket codec
        mean   : Operand(Center(0, "mean"))        all-reduced exactly in fp32
        payload: Operand(Center(0, "residual"), Quantize(-1))   NVFP4 QDQ

so ``Center``/``Quantize`` are the single source of quant truth for GeMMs,
KV pages, and collectives alike.

Gradients are flattened into **buckets** (flat fp32 buffers of up to
``bucket_mb`` MiB, the classic DDP fusion-buffer idiom) and each bucket is
encoded with a registered :class:`CommRecipe`:

    fp32            lossless wire (identity; the exact baseline)
    bf16            cast round-trip (2 bytes/elem)
    int8_ef         per-tensor symmetric int8 + error feedback — the former
                    ``optim/compress.py`` transform, numerics preserved
    nvfp4           blockwise NVFP4 QDQ of the raw bucket + error feedback
    nvfp4_centered  exact fp32 bucket mean + NVFP4 QDQ of the centered
                    residual + error feedback (the paper's G4-on-the-wire)

Per-tensor routing comes from the ``comm=``/``comm.<pattern>=`` clauses of a
:class:`repro.core.policy.PrecisionPolicy` spec (e.g.
``averis;comm=nvfp4_centered;comm.embed=bf16;comm.*norm*=fp32``); tensors
sharing a (recipe, dtype) pair are packed together, ``per_tensor`` recipes
(int8_ef) get one bucket per tensor so their per-tensor scales are preserved.

Error feedback (1-bit-Adam / EF-SGD lineage) is carried in the optimizer
state under ``state["comm"]["ef"]`` and stored in the **gradient dtype** —
not a second full fp32 copy of the params.

Two wire formats share one codec:

* **decoded** (the QDQ simulation): every shard dequantizes its bucket back
  to fp32 before the fold — numerically faithful, but the reduce reads
  ``4 x S`` bytes/elem regardless of the wire format.
* **packed** (the default): nvfp4 buckets travel as :class:`WirePacket`
  bytes — packed E2M1 nibbles + raw E4M3 block-scale bytes + fp32
  amax/mean scalars — and ``kernels/wire_fold.py`` decodes them *inside*
  the fold, reading ~0.5625 bytes/elem/shard with the centered mean folded
  analytically as S fp32 scalars. Error feedback is computed from the
  packet's decoded value, so EF numerics are identical across formats;
  the fold itself is pinned bitwise to the decode-then-``lax.scan`` left
  fold in global shard order, preserving device-count invariance.

:func:`bucket_wire_bytes` accounts the bytes that travel (payload + scales
+ the fp32 mean side-channel) — for packed nvfp4 they are now the bytes
the fold actually reads.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from fnmatch import fnmatch
from typing import Any, Dict, List, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.formats import BLOCK_SIZE
from repro.core.nvfp4 import (encode_e2m1_codes, pack_nibbles,
                              quantize_block_scales)
from repro.core.pipeline import (Center, Operand, Quantize, apply_stages,
                                 _fused_fallback, _fused_interpret)
from repro.core.qgemm import QuantConfig
from repro.kernels import wire_fold

# QuantConfig consumed by apply_stages for wire payloads: blockwise NVFP4,
# RN elements (error feedback de-biases; the wire carries no SR stream).
_WIRE_QCFG = QuantConfig(mode="nvfp4", sr_grad=False)

# Wire hot path: encode nvfp4 buckets through the fused Pallas kernel
# (one pass: subtract-mean → amax → blockwise QDQ instead of materialized
# stage intermediates) and fold shards in a sequential-grid kernel. Both
# fall back to the stage/scan paths on unsupported shapes (counted as
# quant/fused_fallback). Tests flip this to compare the two paths.
WIRE_FUSED = True

# The stage pipelines of the centered wire — shared-split Center exactly as
# in the GeMM executor (one mean reduction per bucket).
MEAN_OP = Operand((Center(0, "mean"),))
RESIDUAL_NVFP4_OP = Operand((Center(0, "residual"), Quantize(-1)))
RAW_NVFP4_OP = Operand((Quantize(-1),))


# --------------------------------------------------------------------------
# Recipes
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CommRecipe:
    """One gradient-wire format.

    ``payload`` names the element encoding of the (possibly centered)
    bucket; ``center`` adds the exact-fp32-mean side channel; ``per_tensor``
    forces one bucket per tensor (per-tensor scales, int8_ef compat);
    ``ef_dtype`` overrides the error-feedback storage dtype (default: the
    gradient dtype of the bucket).
    """

    name: str
    payload: str = "fp32"            # fp32 | bf16 | int8 | nvfp4
    center: bool = False
    error_feedback: bool = False
    per_tensor: bool = False
    ef_dtype: Optional[str] = None

    def __post_init__(self):
        assert self.payload in ("fp32", "bf16", "int8", "nvfp4"), self.payload

    @property
    def is_identity(self) -> bool:
        return self.payload == "fp32" and not self.center


COMM_RECIPES: Dict[str, CommRecipe] = {}


def register_comm_recipe(r: CommRecipe) -> None:
    COMM_RECIPES[r.name] = r


for _r in (
    CommRecipe("fp32"),
    CommRecipe("none"),                  # alias of fp32
    CommRecipe("bf16", payload="bf16"),
    CommRecipe("int8_ef", payload="int8", error_feedback=True,
               per_tensor=True),
    CommRecipe("nvfp4", payload="nvfp4", error_feedback=True),
    CommRecipe("nvfp4_centered", payload="nvfp4", center=True,
               error_feedback=True),
):
    register_comm_recipe(_r)

LEGACY_ALIASES = {"ef_int8": "int8_ef"}  # old TrainConfig.grad_compression


def get_comm_recipe(name: str) -> CommRecipe:
    name = LEGACY_ALIASES.get(name, name)
    try:
        return COMM_RECIPES[name]
    except KeyError:
        raise ValueError(
            f"unknown comm recipe {name!r}; known: {sorted(COMM_RECIPES)}"
        ) from None


# --------------------------------------------------------------------------
# Bucket layout
# --------------------------------------------------------------------------

def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:                            # pragma: no cover
            parts.append(str(p))
    return "/".join(parts)


@dataclasses.dataclass(frozen=True)
class BucketSlot:
    """One tensor's slice inside a bucket's flat buffer."""

    path: str
    leaf_index: int                      # position in the flattened grads tree
    offset: int
    size: int
    shape: Tuple[int, ...]


@dataclasses.dataclass(frozen=True)
class Bucket:
    name: str
    recipe: str
    dtype: str                           # gradient dtype of the member tensors
    slots: Tuple[BucketSlot, ...]

    @property
    def size(self) -> int:
        return sum(s.size for s in self.slots)


@dataclasses.dataclass(frozen=True)
class CommLayout:
    """Static bucket assignment for one gradient tree structure."""

    buckets: Tuple[Bucket, ...]
    num_leaves: int

    @property
    def has_error_feedback(self) -> bool:
        return any(get_comm_recipe(b.recipe).error_feedback
                   for b in self.buckets)

    def ef_dtypes(self) -> Dict[str, Any]:
        """{bucket name: EF storage dtype} for EF-carrying buckets."""
        out = {}
        for b in self.buckets:
            r = get_comm_recipe(b.recipe)
            if r.error_feedback:
                out[b.name] = jnp.dtype(r.ef_dtype or b.dtype)
        return out

    def wire_summary(self) -> Dict[str, Any]:
        """Simulated wire bytes per step per participating shard.

        ``bf16_baseline_bytes`` is what a plain bf16 all-reduce of the same
        gradients would send; ``ratio_vs_bf16`` is the headline number the
        bench reports (fp4 buckets land at ~0.28x).
        """
        per_recipe: Dict[str, Dict[str, float]] = {}
        total = 0.0
        elems = 0
        for b in self.buckets:
            r = get_comm_recipe(b.recipe)
            nbytes = bucket_wire_bytes(r, b.size)
            d = per_recipe.setdefault(
                r.name, {"buckets": 0, "elems": 0, "bytes": 0.0})
            d["buckets"] += 1
            d["elems"] += b.size
            d["bytes"] += nbytes
            total += nbytes
            elems += b.size
        baseline = 2.0 * elems
        return {
            "per_recipe": per_recipe,
            "total_bytes_per_step": total,
            "total_elems": elems,
            "bf16_baseline_bytes": baseline,
            "ratio_vs_bf16": total / baseline if elems else 0.0,
            "num_buckets": len(self.buckets),
        }


def bucket_wire_bytes(recipe: CommRecipe, n: int) -> float:
    """Bytes one bucket of ``n`` gradient elements puts on the wire.

    nvfp4 counts 4-bit codes + one E4M3 scale per 16-block + the fp32
    per-bucket tensor scale; ``center`` adds the fp32 exact-mean side
    channel (4 bytes — the 'cheap' part of the paper's split).
    """
    payload = {
        "fp32": 4.0 * n,
        "bf16": 2.0 * n,
        "int8": 1.0 * n + 4.0,
        "nvfp4": 0.5 * n + math.ceil(n / 16) + 4.0,
    }[recipe.payload]
    return payload + (4.0 if recipe.center else 0.0)


def build_layout(grads_tree, *, default_recipe: str = "fp32",
                 policy=None, bucket_mb: float = 4.0) -> CommLayout:
    """Assign every gradient leaf to a bucket.

    ``policy``: optional :class:`repro.core.policy.PrecisionPolicy` whose
    ``comm.<pattern>=`` clauses route individual tensors away from
    ``default_recipe``. ``default_recipe`` must already be the *resolved*
    default (explicit flag > the policy's ``comm=`` clause > legacy
    fallbacks — ``trainer.resolve_comm_recipe``); the policy's
    ``comm_default`` is NOT re-applied here, so an explicit flag override
    keeps its precedence. Tensors are packed in tree order
    into buckets of at most ``bucket_mb`` MiB of gradient-dtype elements;
    a tensor larger than the cap gets its own bucket (tensors never split
    across buckets). ``per_tensor`` recipes always bucket singly.
    """
    flat, _ = jax.tree_util.tree_flatten_with_path(grads_tree)
    get_comm_recipe(default_recipe)      # validate early
    groups: Dict[Tuple[str, str], List[Tuple[str, int, Tuple[int, ...]]]] = {}
    for i, (path, leaf) in enumerate(flat):
        p = _path_str(path)
        name = default_recipe
        if policy is not None:
            name = policy.comm_override(p) or default_recipe
        name = LEGACY_ALIASES.get(name, name)
        get_comm_recipe(name)
        dt = str(jnp.dtype(leaf.dtype))
        groups.setdefault((name, dt), []).append((p, i, tuple(leaf.shape)))

    buckets: List[Bucket] = []
    for (name, dt), members in sorted(groups.items()):
        recipe = get_comm_recipe(name)
        cap = max(int(bucket_mb * 2**20 / jnp.dtype(dt).itemsize), 1)
        cur: List[BucketSlot] = []
        cur_size = 0

        def flush():
            nonlocal cur, cur_size
            if cur:
                buckets.append(Bucket(
                    name=f"{name}.{dt}.{len(buckets):03d}",
                    recipe=name, dtype=dt, slots=tuple(cur)))
                cur, cur_size = [], 0

        for p, i, shape in members:
            size = int(math.prod(shape)) if shape else 1
            if recipe.per_tensor:
                flush()
                cur = [BucketSlot(p, i, 0, size, shape)]
                cur_size = size
                flush()
                continue
            if cur and cur_size + size > cap:
                flush()
            cur.append(BucketSlot(p, i, cur_size, size, shape))
            cur_size += size
        flush()
    return CommLayout(buckets=tuple(buckets), num_leaves=len(flat))


def bucketize(layout: CommLayout, grads_tree) -> Dict[str, jax.Array]:
    """Gradient tree -> {bucket name: flat fp32 buffer} (tree-order concat)."""
    leaves = jax.tree.leaves(grads_tree)
    assert len(leaves) == layout.num_leaves, (len(leaves), layout.num_leaves)
    out = {}
    for b in layout.buckets:
        parts = [leaves[s.leaf_index].reshape(-1).astype(jnp.float32)
                 for s in b.slots]
        out[b.name] = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    return out


def debucketize(layout: CommLayout, flats: Dict[str, jax.Array], grads_tree):
    """Inverse of :func:`bucketize`; leaves come back in their own dtype."""
    leaves = list(jax.tree.leaves(grads_tree))
    treedef = jax.tree.structure(grads_tree)
    for b in layout.buckets:
        flat = flats[b.name]
        for s in b.slots:
            piece = jax.lax.dynamic_slice_in_dim(flat, s.offset, s.size, 0)
            leaves[s.leaf_index] = piece.reshape(s.shape).astype(
                leaves[s.leaf_index].dtype)
    return jax.tree.unflatten(treedef, leaves)


# --------------------------------------------------------------------------
# Bucket codec
# --------------------------------------------------------------------------

def _q_int8(x: jax.Array) -> jax.Array:
    """Symmetric per-bucket int8 QDQ in fp32 (the former compress.py wire).

    Bit-for-bit the old ``optim/compress.py`` formula: max/round/clip are
    permutation-invariant, so operating on the raveled tensor reproduces the
    per-tensor transform exactly (int8_ef buckets are per-tensor).
    """
    scale = jnp.maximum(jnp.max(jnp.abs(x)) / 127.0, 1e-30)
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    return q * scale


_WIRE_TILE_COLS = (512, 256, 128, 64, 32, 16)
# row tiles (of 128 lanes) of the decoded-shard Pallas fold: f32 sublane
# multiples, at most 256 KiB per block
_FOLD_TILE_ROWS = (512, 256, 128, 64, 32, 16, 8)


def _wire_cols(n: int) -> Optional[int]:
    """Widest block-aligned column count that tiles a flat bucket exactly."""
    for m in _WIRE_TILE_COLS:
        if n % m == 0:
            return m
    return None


def _pad_tail(flat: jax.Array, pad: int,
              mu: Optional[jax.Array]) -> jax.Array:
    """Extend a flat bucket by ``pad`` elements WITHOUT corrupting the
    shared tail 16-block scale: centered buckets are padded with the bucket
    mean itself (PR 7's mu-padding trick — the padded entries center to
    exact zeros), uncentered with zeros. Either way the padding contributes
    0 to every amax, so the quantization of the REAL entries is bitwise the
    unpadded stage path's (``nvfp4_qdq`` zero-pads the residual the same
    way internally)."""
    if pad == 0:
        return flat
    if mu is None:
        fill = jnp.zeros((pad,), flat.dtype)
    else:
        fill = jnp.broadcast_to(mu.astype(flat.dtype), (pad,))
    return jnp.concatenate([flat, fill])


def _fused_bucket_qdq(corrected: jax.Array,
                      *, center: bool) -> Optional[jax.Array]:
    """One-pass Pallas encode of an nvfp4 wire bucket; None -> stage path.

    The flat bucket is viewed as (rows, m) with m a multiple of the quant
    block, which preserves the 1-D block boundaries exactly; the scalar
    bucket mean broadcasts to a lane vector for the kernel's Center. The
    decoded wire is bitwise the stage path's (same mean, same blocks, same
    per-tensor amax — max is order-invariant) within one jit regime.
    Ragged buckets (size not a multiple of the quant block) are mu-padded
    to the next block boundary (:func:`_pad_tail`) instead of falling back
    to the stage path, and the padding is sliced off the decoded wire.
    """
    if corrected.ndim != 1:
        _fused_fallback(
            f"wire bucket shape {corrected.shape} is not flat")
        return None
    n = corrected.shape[-1]
    mu_s = jnp.mean(corrected.astype(jnp.float32)) if center else None
    pad = (-n) % BLOCK_SIZE
    padded = _pad_tail(corrected, pad, mu_s)
    m = _wire_cols(n + pad)
    from repro.kernels.fused import center_hadamard_qdq_2d
    interpret = _fused_interpret()
    x2 = padded.reshape(-1, m)
    mu_row = None
    if center:
        mu_row = jnp.broadcast_to(mu_s.reshape(1, 1), (1, m))
    res_q = center_hadamard_qdq_2d(x2, mu_row, None, None, rotate=False,
                                   interpret=interpret).reshape(-1)[:n]
    return res_q + mu_s if center else res_q


# --------------------------------------------------------------------------
# Packed wire: real bytes end-to-end (decode happens inside the fold)
# --------------------------------------------------------------------------

class WirePacket(NamedTuple):
    """One nvfp4 bucket's actual wire bytes (what a real collective ships).

    The payload is padded to whole nibble-pair blocks
    (:func:`packet_wire_elems` elements) with the mu-padding trick, so a
    bucket of ``n`` gradients travels as ``~0.5625*n`` bytes + 8 scalar
    bytes instead of ``4*n``:

      codes    (padded_n/2,)  uint8  packed E2M1 nibble pairs, low first
      scales   (padded_n/16,) uint8  raw E4M3 per-16-block scale bytes
      amax     ()             fp32   per-bucket amax of the quantized
                                     operand (s_t is re-derived at decode)
      mean     ()             fp32   exact bucket mean (0.0 uncentered)

    A NamedTuple, hence a jax pytree: packets stack/all-gather leaf-wise
    through ``shard_map`` exactly like the decoded fp32 wires they replace.
    ``kernels/wire_fold.py`` folds S stacked packets without ever
    materializing the decoded (S, B) fp32 stack.
    """

    codes: jax.Array
    scales: jax.Array
    amax: jax.Array
    mean: jax.Array


#: Decoded wire buffers are plain arrays; packed wires are WirePackets.
WireValue = Union[jax.Array, WirePacket]


def packet_wire_elems(n: int) -> int:
    """Padded payload element count of an ``n``-element bucket's packet
    (whole 2*BLOCK_SIZE groups, so codes pack to whole bytes per block)."""
    return n + (-n) % (2 * BLOCK_SIZE)


def _packed_cols(n_padded: int) -> int:
    """Widest nibble-pair-aligned column count tiling a padded payload
    (always succeeds: the payload is a multiple of 2*BLOCK_SIZE)."""
    for m in _WIRE_TILE_COLS:
        if m % (2 * BLOCK_SIZE) == 0 and n_padded % m == 0:
            return m
    raise AssertionError(f"padded payload {n_padded} not 32-aligned")


def _encode_bucket_packet(corrected: jax.Array, *,
                          center: bool) -> WirePacket:
    """Encode one flat fp32 bucket into its :class:`WirePacket`.

    The fused path reuses PR 7's pack kernel (`center_hadamard_pack_2d`)
    on the (rows, m) view; the stage twin is the ``core/nvfp4`` codec
    chain. Both produce identical bytes, and decoding them
    (:func:`decode_packet`) is bitwise the decoded wire of
    :func:`_fused_bucket_qdq` / the stage QDQ — same q, same scales, same
    per-tensor amax — so error feedback is unchanged by the wire format.
    """
    n = corrected.shape[-1]
    xf = corrected.astype(jnp.float32)
    mu_s = jnp.mean(xf) if center else None
    pad = packet_wire_elems(n) - n
    padded = _pad_tail(xf, pad, mu_s)
    if WIRE_FUSED:
        from repro.kernels.fused import center_hadamard_pack_2d, fused_amax_2d
        interpret = _fused_interpret()
        m = _packed_cols(padded.shape[-1])
        x2 = padded.reshape(-1, m)
        mu_row = None
        if center:
            mu_row = jnp.broadcast_to(mu_s.reshape(1, 1), (1, m))
        amax2 = fused_amax_2d(x2, mu_row, rotate=False, interpret=interpret)
        codes2, scales2, _ = center_hadamard_pack_2d(
            x2, mu_row, amax2, None, rotate=False, interpret=interpret)
        codes = codes2.reshape(-1)
        scales = jax.lax.bitcast_convert_type(scales2, jnp.uint8).reshape(-1)
        amax = amax2.reshape(())
    else:
        res = padded - mu_s if center else padded
        rb = res.reshape(-1, BLOCK_SIZE)
        absr = jnp.abs(rb)
        amax = jnp.max(absr)
        s_t = wire_fold.shard_tensor_scales(amax)
        s_b = quantize_block_scales(jnp.max(absr, axis=-1), s_t)
        codes4 = encode_e2m1_codes(rb, s_b.astype(jnp.float32) * s_t)
        codes = pack_nibbles(codes4.reshape(-1))
        scales = jax.lax.bitcast_convert_type(s_b, jnp.uint8)
    mean = mu_s if center else jnp.float32(0.0)
    return WirePacket(codes=codes, scales=scales, amax=amax, mean=mean)


def decode_packet(recipe: CommRecipe, packet: WirePacket,
                  n: int) -> jax.Array:
    """Packet -> the (n,) fp32 value the receiving side decodes.

    Bitwise the decoded-wire (QDQ simulation) value of the same bucket:
    residual = codes x E4M3 scales x re-derived s_t, padding sliced off,
    plus the exact mean for centered recipes.
    """
    v = wire_fold.decode_wire_values(
        packet.codes, packet.scales,
        wire_fold.shard_tensor_scales(packet.amax))[:n]
    return v + packet.mean if recipe.center else v


def fold_packet_shards(recipe: CommRecipe, stacked: WirePacket,
                       num_shards: int, *, n: int,
                       backend: str = "auto") -> jax.Array:
    """Fold an (S,)-stacked :class:`WirePacket` into the (n,) reduced bucket.

    The packed twin of :func:`fold_shards`: ``kernels/wire_fold.py``
    decodes each shard's bytes inside the same fixed-order left fold
    (bitwise ``fold_packets_reference``, i.e. decode-then-scan), with the
    centered mean folded analytically as S fp32 scalars. Device-count
    invariance is inherited: the fold is a deterministic function of the
    globally-ordered packet stack.
    """
    mean = stacked.mean if recipe.center else None
    acc = wire_fold.fold_packets(stacked.codes, stacked.scales,
                                 stacked.amax, mean, num_shards,
                                 backend=backend)
    return acc[:n]


def _fold_kernel(x_ref, o_ref, *, num_shards: int):
    """Grid (row tiles, S), shards innermost: the output block is the fold
    accumulator, o = Σ_s x[s]/S in shard order (init at s == 0)."""
    s = pl.program_id(1)
    part = x_ref[0].astype(jnp.float32) / num_shards

    @pl.when(s == 0)
    def _init():
        o_ref[...] = part

    @pl.when(s != 0)
    def _acc():
        o_ref[...] = o_ref[...] + part


def _fold_shards_pallas(stacked: jax.Array, num_shards: int, *,
                        interpret: Optional[bool] = None
                        ) -> Optional[jax.Array]:
    """Pallas left fold of (S, B) decoded shards; None -> lax.scan path.

    Each shard is viewed as rows of 128 lanes (one row of B lanes when B
    is not a 128-multiple) and a block holds one shard's row tile, so
    Mosaic's rule for the last two block dims holds and VMEM does not
    grow with S."""
    if stacked.ndim != 2:
        return None
    s_dim, b = stacked.shape
    lanes = 128 if b % 128 == 0 else b
    rows = b // lanes
    tile_r = next((t for t in _FOLD_TILE_ROWS if rows % t == 0), rows)
    if tile_r * lanes > _FOLD_TILE_ROWS[0] * 128:
        return None
    if interpret is None:
        interpret = _fused_interpret()
    return pl.pallas_call(
        functools.partial(_fold_kernel, num_shards=num_shards),
        out_shape=jax.ShapeDtypeStruct((rows, lanes), jnp.float32),
        grid=(rows // tile_r, s_dim),
        in_specs=[pl.BlockSpec((1, tile_r, lanes),
                               lambda r, s: (s, r, 0))],
        out_specs=pl.BlockSpec((tile_r, lanes), lambda r, s: (r, 0)),
        interpret=interpret,
    )(stacked.reshape(s_dim, rows, lanes)).reshape(b)


def encode_bucket(
    recipe: CommRecipe,
    flat: jax.Array,
    ef: Optional[jax.Array] = None,
    *,
    packed: bool = False,
) -> Tuple[WireValue, Optional[jax.Array]]:
    """Encode one flat fp32 bucket for the wire.

    Returns ``(wire, new_ef)``. With ``packed=False`` (the QDQ simulation)
    ``wire`` is the decoded fp32 value the receiving side would see; with
    ``packed=True`` the nvfp4 payloads emit a :class:`WirePacket` — the
    actual wire bytes — and the receiving side decodes inside the fold
    (:func:`fold_packet_shards`). ``new_ef`` is the updated error-feedback
    residual in the EF storage dtype (None when the recipe carries no EF);
    it is always computed from the packet's *decoded* value, so EF numerics
    are identical across wire formats.

    The nvfp4 payloads run through the shared pipeline stages
    (:data:`MEAN_OP` / :data:`RESIDUAL_NVFP4_OP` / :data:`RAW_NVFP4_OP`) or
    their fused/packed twins, so the wire's centering + quantization is
    literally the GeMM core's.
    """
    corrected = flat
    if ef is not None:
        corrected = flat + ef.astype(jnp.float32)

    wire: WireValue
    if recipe.is_identity:
        wire = corrected
    elif recipe.payload == "bf16" and not recipe.center:
        wire = corrected.astype(jnp.bfloat16).astype(jnp.float32)
    elif recipe.payload == "int8" and not recipe.center:
        wire = _q_int8(corrected)
    elif recipe.payload == "nvfp4" and packed:
        wire = _encode_bucket_packet(corrected, center=recipe.center)
    elif recipe.payload == "nvfp4":
        wire = (_fused_bucket_qdq(corrected, center=recipe.center)
                if WIRE_FUSED else None)
        if wire is None and recipe.center:
            splits: Dict = {}
            mu = apply_stages(corrected, MEAN_OP, _WIRE_QCFG, splits=splits)
            res_q = apply_stages(corrected, RESIDUAL_NVFP4_OP, _WIRE_QCFG,
                                 splits=splits)
            wire = res_q + mu            # scalar mean broadcast, exact fp32
        elif wire is None:
            wire = apply_stages(corrected, RAW_NVFP4_OP, _WIRE_QCFG)
    else:                                # pragma: no cover
        raise NotImplementedError(f"comm recipe {recipe}")

    new_ef = None
    if recipe.error_feedback:
        ef_dt = ef.dtype if ef is not None else jnp.float32
        decoded = (decode_packet(recipe, wire, corrected.shape[-1])
                   if isinstance(wire, WirePacket) else wire)
        new_ef = (corrected - decoded).astype(ef_dt)
    return wire, new_ef


def encode_shard_buckets(
    layout: CommLayout,
    flats: Dict[str, jax.Array],
    ef_rows: Optional[Dict[str, jax.Array]] = None,
    *,
    codec_on: bool = True,
    packed: bool = False,
) -> Tuple[Dict[str, WireValue], Dict[str, jax.Array]]:
    """Encode one wire participant's buckets.

    ``flats``: {bucket name: flat fp32 buffer} from :func:`bucketize`;
    ``ef_rows``: this participant's EF buffers for EF-carrying buckets.
    ``packed=True`` makes nvfp4 buckets emit :class:`WirePacket` bytes
    (fold with :func:`fold_packet_shards`); other payloads always stay
    decoded buffers. Returns ``(wires, new_ef_rows)``; with
    ``codec_on=False`` (a single participant — no wire exists) buffers pass
    through and EF is untouched. The single implementation behind both the
    sharded train step and the mesh-free benchmark reduce, so their
    semantics cannot drift.
    """
    wires: Dict[str, WireValue] = {}
    new_ef: Dict[str, jax.Array] = {}
    for b in layout.buckets:
        if codec_on:
            row = (ef_rows or {}).get(b.name)
            w, ef2 = encode_bucket(get_comm_recipe(b.recipe), flats[b.name],
                                   row, packed=packed)
        else:
            w, ef2 = flats[b.name], None
        wires[b.name] = w
        if ef2 is not None:
            new_ef[b.name] = ef2
    return wires, new_ef


def bucket_probe_stats(
    layout: CommLayout,
    flats: Dict[str, jax.Array],
    ef_rows: Optional[Dict[str, jax.Array]] = None,
    *,
    codec_on: bool = True,
    wires: Optional[Dict[str, WireValue]] = None,
) -> Dict[str, Dict[str, jax.Array]]:
    """Quant-health probe of every bucket's wire encoding.

    When the caller passes the production ``wires`` (the
    :func:`encode_shard_buckets` output — decoded buffers or
    :class:`WirePacket`\\ s), the probe consumes them under
    ``stop_gradient`` instead of re-encoding, halving the probe-on encode
    cost; packets are decoded to the value the receiving side sees. With
    ``wires=None`` it remains a stop-gradient *duplicate* of the encode
    (each probed bucket encoded twice). Either way the production path is
    untouched — probes cannot perturb the wire, and probes-off graphs stay
    bitwise identical. Returns
    ``{bucket name: repro.obs.probes.comm_bucket_stats(...)}`` — R,
    clip/underflow rate, bin occupancy, and the EF-residual norm per bucket.
    """
    from repro.obs.probes import comm_bucket_stats

    out: Dict[str, Dict[str, jax.Array]] = {}
    for b in layout.buckets:
        r = get_comm_recipe(b.recipe)
        flat = jax.lax.stop_gradient(flats[b.name]).astype(jnp.float32)
        row = (ef_rows or {}).get(b.name)
        if row is not None:
            row = jax.lax.stop_gradient(row)
        corrected = (flat if row is None
                     else flat + row.astype(jnp.float32))
        if wires is not None and b.name in wires:
            w = wires[b.name]
            if isinstance(w, WirePacket):
                w = decode_packet(r, w, flat.shape[-1])
            wire = jax.lax.stop_gradient(w).astype(jnp.float32)
        elif codec_on:
            wire = encode_bucket(r, flat, row)[0]
        else:
            wire = corrected
        out[b.name] = comm_bucket_stats(r, corrected, wire)
    return out


def fold_shards(stacked: jax.Array, num_shards: int) -> jax.Array:
    """``Σ_s stacked[s] / S`` as a fixed-order sequence of fp32 adds.

    THE reduction of the wire: because every participant folds the same
    decoded shards in the same global order, the result is bitwise
    independent of how shards are distributed over devices. A ``lax.scan``
    (not a tree/pairwise reduce, which would reassociate the fp32 adds, and
    not a Python unroll, whose graph grows with the shard count) performs
    exactly that left fold at O(1) trace size. With :data:`WIRE_FUSED` the
    same fold runs as a sequential-grid Pallas kernel (identical shard
    order, hence bitwise-identical) when the payload tiles evenly.
    """
    if WIRE_FUSED:
        folded = _fold_shards_pallas(stacked, num_shards)
        if folded is not None:
            return folded
    acc0 = jnp.zeros(stacked.shape[1:], jnp.float32)
    acc, _ = jax.lax.scan(
        lambda c, x: (c + x.astype(jnp.float32) / num_shards, None),
        acc0, stacked)
    return acc


# --------------------------------------------------------------------------
# State + transform (the optimizer-hook path; 1-participant wire)
# --------------------------------------------------------------------------

def init_comm_state(params_or_grads, *, default_recipe: str = "fp32",
                    policy=None, bucket_mb: float = 4.0,
                    dp_shards: Optional[int] = None) -> Dict[str, Any]:
    """Zero EF buffers for a gradient tree; ``{}`` when no bucket carries EF.

    ``dp_shards``: when set, EF buffers gain a leading shard axis (one EF
    stream per wire participant — the sharded train step's layout); when
    None the buffers are flat (the optimizer-transform path).
    """
    layout = build_layout(params_or_grads, default_recipe=default_recipe,
                          policy=policy, bucket_mb=bucket_mb)
    ef_dtypes = layout.ef_dtypes()
    if not ef_dtypes:
        return {}
    ef = {}
    for b in layout.buckets:
        if b.name not in ef_dtypes:
            continue
        shape = (b.size,) if dp_shards is None else (dp_shards, b.size)
        ef[b.name] = jnp.zeros(shape, ef_dtypes[b.name])
    return {"comm": {"ef": ef}}


def apply_comm(layout: CommLayout, grads_tree, ef_state: Dict[str, jax.Array]
               ) -> Tuple[Any, Dict[str, jax.Array]]:
    """Run every bucket of a gradient tree through its wire codec once.

    ``ef_state``: {bucket name: flat EF buffer} (no shard axis). Returns the
    decoded gradient tree and the updated EF buffers.
    """
    flats = bucketize(layout, grads_tree)
    new_ef = dict(ef_state)
    out = {}
    for b in layout.buckets:
        recipe = get_comm_recipe(b.recipe)
        ef = ef_state.get(b.name)
        if recipe.error_feedback and ef is None and ef_state:
            # A present-but-mismatched EF dict means the state was built
            # from a different tree (e.g. param dtypes instead of gradient
            # dtypes) — dropping EF silently would violate the documented
            # error-feedback guarantee, so fail loudly.
            raise ValueError(
                f"comm EF state has no buffer for bucket {b.name!r} "
                f"(found {sorted(ef_state)}); init_comm_state must be "
                f"built from the gradient tree, dtypes included")
        wire, ef2 = encode_bucket(recipe, flats[b.name], ef)
        out[b.name] = wire
        if ef2 is not None:
            new_ef[b.name] = ef2
    return debucketize(layout, out, grads_tree), new_ef


def make_comm_transform(*, recipe: str, policy=None, bucket_mb: float = 4.0):
    """A ``grad_transform`` hook for ``optim.adamw.apply_updates``.

    Simulates every step's gradients traveling the wire (the replacement of
    the old ``optim/compress.py`` int8-EF hook — pass ``recipe="int8_ef"``
    for its exact numerics). EF lives in ``state["comm"]["ef"]``.
    """
    get_comm_recipe(recipe)

    def transform(grads, state):
        layout = build_layout(grads, default_recipe=recipe, policy=policy,
                              bucket_mb=bucket_mb)
        ef = state.get("comm", {}).get("ef", {})
        new_grads, new_ef = apply_comm(layout, grads, ef)
        if not new_ef:
            return new_grads, state
        return new_grads, dict(state, comm={"ef": new_ef})

    return transform
