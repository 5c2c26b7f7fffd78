"""Plain float32 reference of a dense decoder LM's first training steps.

It imports nothing of the program under test. From a configuration file's
numbers and a seed it makes the weights, and then trains as the job file
states: embedding, pre-norm blocks (RMSNorm, GQA attention with split-half
RoPE, optional QKV bias and per-head q/k RMSNorm, SwiGLU MLP), a tied head,
mean token cross entropy, global-norm clipping and AdamW. Every matmul is
float32 at ``Precision.HIGHEST``; parameters, Adam's moments, norms,
softmax and the loss are float32. The values that the configuration keeps
in its compute dtype (``run_dtypes.compute_dtype``: activations, weights as
a GeMM reads them, quantized operands, GeMM outputs and their gradients)
are rounded to it, so that the reference computes at the precision the
configuration states: with FP4 operands a rounding difference flips a code,
and a reference in float32 throughout would differ from any bfloat16
program by more than one a precision step lower would add.

Recipes (``job["recipe"]``):

* ``bf16``: plain matmuls.
* ``averis``: every linear layer's three GeMMs on NVFP4 operands with the
  token mean split off (the paper's Eqs. 8-10). NVFP4 here: an fp32 tensor
  scale amax / (6 * 448), one E4M3 scale per 16 elements along the GeMM's
  contraction axis, elements rounded to E2M1 {0, .5, 1, 1.5, 2, 3, 4, 6}
  to nearest with ties to the even grid index, or stochastically on the
  output-gradient operand of the two backward GeMMs.

The stochastic bits follow the key schedule that the configuration's
training step states (``SR_KEYS``), written out here: step ``k`` of a run
trains with ``fold_in(seed key, k)``; layer ``i`` folds in ``i``, then its
attention (1) or MLP (2) block, then the GeMM's site; the head folds in 99
and then 0. A GeMM's backward splits ``fold_in(its key, 1)`` into the keys
of its input-gradient and weight-gradient operands, and each operand draws
one float32 uniform per element in its blocked (..., n / 16, 16) layout.
With the same bits, the two sides round the same values the same way.

``lowp`` rounds those values to float8_e4m3fn instead in the forward pass
(gradients stay at the compute dtype): the same model one precision step
down. ``split_sums`` sums every GeMM's contraction in two halves: the same
arithmetic in another order, a witness of how far rounding alone moves a
reading.

The weights are the benchmark's (:func:`make_weights`), laid out as
``{"embed", "final_norm", "layers": {...}}`` with the layer axis first; the
train mode hands the same arrays to the program.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
E2M1 = (0.0, 0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 6.0)
E4M3_MAX = 448.0
BLOCK = 16


# ---------------------------------------------------------------- weights

def seed_key(seed: int) -> jax.Array:
    """A key from any whole number (``--seed`` may exceed 32 bits)."""
    if seed < 0:
        raise ValueError(f"seed {seed} is negative")
    key = jax.random.key(seed & 0x7FFFFFFF)
    rest = seed >> 31
    while rest:
        key = jax.random.fold_in(key, rest & 0x7FFFFFFF)
        rest >>= 31
    return key


def dims(model: Dict) -> Dict[str, int]:
    """The sizes the reference needs, from a Hugging Face-style config."""
    nh = model["num_attention_heads"]
    return dict(
        L=model["num_hidden_layers"], d=model["hidden_size"],
        f=model["intermediate_size"], V=model["vocab_size"], nh=nh,
        nkv=model.get("num_key_value_heads", nh),
        hd=model.get("head_dim") or model["hidden_size"] // nh)


def weight_specs(model: Dict) -> List[Tuple[Tuple[str, ...], Tuple[int, ...],
                                            str]]:
    """(path, shape, init) of every weight, in a fixed order."""
    n = dims(model)
    L, d, f, V = n["L"], n["d"], n["f"], n["V"]
    q, kv, hd = n["nh"] * n["hd"], n["nkv"] * n["hd"], n["hd"]
    arch = model["architecture"]
    specs = [
        (("embed",), (V, d), "normal"),
        (("final_norm",), (d,), "ones"),
        (("layers", "ln1"), (L, d), "ones"),
        (("layers", "ln2"), (L, d), "ones"),
        (("layers", "attn", "wq"), (L, d, q), "normal"),
        (("layers", "attn", "wk"), (L, d, kv), "normal"),
        (("layers", "attn", "wv"), (L, d, kv), "normal"),
        (("layers", "attn", "wo"), (L, q, d), "normal"),
        (("layers", "ffn", "w_gate"), (L, d, f), "normal"),
        (("layers", "ffn", "w_up"), (L, d, f), "normal"),
        (("layers", "ffn", "w_down"), (L, f, d), "normal"),
    ]
    if arch["qkv_bias"]:
        specs += [(("layers", "attn", "bq"), (L, q), "zeros"),
                  (("layers", "attn", "bk"), (L, kv), "zeros"),
                  (("layers", "attn", "bv"), (L, kv), "zeros")]
    if arch["qk_norm"]:
        specs += [(("layers", "attn", "q_norm"), (L, hd), "ones"),
                  (("layers", "attn", "k_norm"), (L, hd), "ones")]
    if not model.get("tie_word_embeddings", True):
        raise NotImplementedError("untied heads are not modelled")
    return specs


def _put(tree: Dict, path, value) -> None:
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


def make_weights(model: Dict, key: jax.Array) -> Dict:
    """All weights in float32 from ``key`` (trace under one ``jax.jit``)."""
    std = model.get("initializer_range", 0.02)
    out: Dict = {}
    for i, (path, shape, init) in enumerate(weight_specs(model)):
        if init == "normal":
            v = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * std
        elif init == "ones":
            v = jnp.ones(shape, jnp.float32)
        else:
            v = jnp.zeros(shape, jnp.float32)
        _put(out, path, v)
    return out


def leaf_norms(tree: Dict, scale=1.0) -> Dict[str, jax.Array]:
    """Norm of every leaf, per layer for the stacked ones ("path" -> (L,)
    or scalar). Trace under ``jax.jit``."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        x = leaf.astype(jnp.float32) * scale
        axes = tuple(range(1, x.ndim)) if name.startswith("layers/") else None
        out[name] = jnp.sqrt(jnp.sum(x * x, axis=axes))
    return out


def change_norms(params: Dict, model: Dict, key: jax.Array) -> Dict:
    """Per-leaf norm of ``params`` minus the seed's initial weights, the
    initial weights made anew from ``key`` (trace under ``jax.jit``)."""
    p0 = make_weights(model, key)
    return leaf_norms(jax.tree.map(lambda a, b: a.astype(jnp.float32) - b,
                                   params, p0))


# ---------------------------------------------------------------- NVFP4

def _round_e2m1(a: jax.Array, u=None) -> jax.Array:
    """|values| in block-scale units -> E2M1 grid (nearest, ties to the
    even index; or stochastic with uniforms ``u``)."""
    grid = jnp.asarray(E2M1, jnp.float32)
    a = jnp.minimum(a, 6.0)
    lo_i = jnp.clip(jnp.searchsorted(grid, a, side="right") - 1, 0, 7)
    hi_i = jnp.minimum(lo_i + 1, 7)
    lo, hi = grid[lo_i], grid[hi_i]
    if u is None:
        up = (a - lo > hi - a) | ((a - lo == hi - a) & (lo_i % 2 == 1))
    else:
        # up with probability equal to the position inside the interval
        up = u < jnp.where(hi > lo, (a - lo) / jnp.where(hi > lo, hi - lo, 1.0),
                           0.0)
    return jnp.where(up, hi, lo)


# The key schedule: block tag and GeMM site of every linear layer.
SR_KEYS = {"attn": 1, "mlp": 2, "head": 99,
           "wq": 1, "wk": 2, "wv": 3, "wo": 4,
           "w_gate": 20, "w_up": 21, "w_down": 22, "lm_head": 0}


def gemm_key(key: jax.Array, *path) -> jax.Array:
    """``key`` with the schedule's numbers for ``path`` folded in turn
    (layer indices as they are, names through ``SR_KEYS``)."""
    for p in path:
        key = jax.random.fold_in(key, SR_KEYS[p] if isinstance(p, str) else p)
    return key


def qdq(x: jax.Array, axis: int, key=None) -> jax.Array:
    """NVFP4 quantize-dequantize of ``x`` with 16-blocks along ``axis``."""
    x = jnp.moveaxis(x.astype(jnp.float32), axis, -1)
    shape = x.shape
    xb = x.reshape(shape[:-1] + (shape[-1] // BLOCK, BLOCK))
    ax = jnp.abs(xb)
    s_t = jnp.maximum(jnp.max(ax) / (6.0 * E4M3_MAX), 1e-30)
    s_b = jnp.clip(jnp.max(ax, axis=-1, keepdims=True) / (6.0 * s_t),
                   0.0, E4M3_MAX).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    scale = s_b * s_t
    a = jnp.where(scale > 0, ax / jnp.where(scale > 0, scale, 1.0), 0.0)
    u = None if key is None else jax.random.uniform(key, a.shape)
    q = jnp.sign(xb) * _round_e2m1(a, u) * scale
    return jnp.moveaxis(q.reshape(shape), -1, axis)


def _dot(a, b):
    return jnp.dot(a, b, precision=HIGHEST, preferred_element_type=jnp.float32)


def _dot_split(a, b):
    """``a @ b`` with the contraction summed in two halves."""
    k = a.shape[-1] // 2
    return _dot(a[..., :k], b[:k]) + _dot(a[..., k:], b[k:])


def _rt(x: jax.Array, dtype: str) -> jax.Array:
    """Round ``x`` to ``dtype`` and back to float32 (its gradient is
    rounded the same way)."""
    return x.astype(dtype).astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _linear(recipe: str, fdt: str, bdt: str, split: bool, x, w, key):
    return _linear_fwd(recipe, fdt, bdt, split, x, w, key)[0]


def _linear_fwd(recipe, fdt, bdt, split, x, w, key):
    dot = _dot_split if split else _dot
    r = lambda t: _rt(t, fdt)
    wc = r(w)
    if recipe == "bf16":
        y = dot(x, wc)
    elif recipe == "averis":
        # Eq. 8: the token mean and the centered residual, each quantized
        mu = jnp.mean(x, axis=0)
        wq = r(qdq(wc, 0))
        y = (dot(r(qdq(r(x - mu), -1)), wq)
             + dot(r(qdq(r(mu), -1)), wq)[None, :])
    else:
        raise ValueError(f"the reference has no recipe {recipe!r}")
    return r(y), (x, w, key)


def _linear_bwd(recipe, fdt, bdt, split, res, g):
    dot = _dot_split if split else _dot
    x, w, key = res
    r = lambda t: _rt(t, bdt)
    g, wc = r(g), r(w)
    if recipe == "bf16":
        return r(dot(g, wc.T)), r(dot(x.T, g)), None
    # Eqs. 9-10: stochastic rounding on the output gradient's residual
    k_dx, k_dw = jax.random.split(jax.random.fold_in(key, 1))
    mu_g = jnp.mean(g, axis=0)
    g_r, mu_g = r(g - mu_g), r(mu_g)
    w_dx = r(qdq(wc, 1))
    dx = (dot(r(qdq(g_r, -1, k_dx)), w_dx.T)
          + dot(r(qdq(mu_g, -1)), w_dx.T)[None, :])
    mu_x = jnp.mean(x, axis=0)
    x_r, mu_x = r(x - mu_x), r(mu_x)
    dw = (dot(r(qdq(x_r, 0)).T, r(qdq(g_r, 0, k_dw)))
          + x.shape[0] * jnp.outer(r(qdq(mu_x, -1)), r(qdq(mu_g, -1))))
    return r(dx), r(dw), None


_linear.defvjp(_linear_fwd, _linear_bwd)


def linear(recipe: str, x: jax.Array, w: jax.Array, key, fdt: str,
           bdt: str, split: bool = False) -> jax.Array:
    """``x @ w`` under ``recipe``; values rounded to ``fdt`` in the forward
    pass and to ``bdt`` in the backward pass where the program keeps them
    in its compute dtype."""
    lead = x.shape[:-1]
    y = _linear(recipe, fdt, bdt, split, x.reshape(-1, x.shape[-1]), w, key)
    return y.reshape(lead + (w.shape[1],))


# ---------------------------------------------------------------- model

def _rounder(fdt: str, bdt: str):
    """Forward rounding to ``fdt``, the gradient rounded to ``bdt``."""
    if fdt == bdt:
        return lambda x: _rt(x, fdt)

    def r(x):
        b = _rt(x, bdt)
        return b + jax.lax.stop_gradient(_rt(x, fdt) - b)
    return r


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """Split-half rotary embedding over positions 0..s-1; x (b, s, h, hd)."""
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    c, sn = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], axis=-1)


def loss_fn(params: Dict, tokens: jax.Array, key: jax.Array, model: Dict,
            recipe: str, lowp: bool = False,
            split_sums: bool = False) -> jax.Array:
    """Mean next-token cross entropy of ``tokens`` (b, s)."""
    n = dims(model)
    arch = model["architecture"]
    eps = model.get("rms_norm_eps", 1e-6)
    theta = float(model["rope_theta"])
    bdt = model["run_dtypes"]["compute_dtype"]
    fdt = "float8_e4m3fn" if lowp else bdt
    r = _rounder(fdt, bdt)
    b, s = tokens.shape
    nh, nkv, hd = n["nh"], n["nkv"], n["hd"]

    def lin(t, w, k):
        return linear(recipe, t, w, k, fdt, bdt, split_sums)

    def block(x, xs):
        p, i = xs
        k = jax.random.fold_in(key, i)
        a = p["attn"]
        h = r(_rms(x, p["ln1"], eps))
        q, kk, v = (lin(h, a[w], gemm_key(k, "attn", w))
                    for w in ("wq", "wk", "wv"))
        if arch["qkv_bias"]:
            q, kk, v = r(q + r(a["bq"])), r(kk + r(a["bk"])), r(v + r(a["bv"]))
        q = q.reshape(b, s, nh, hd)
        kk = kk.reshape(b, s, nkv, hd)
        v = v.reshape(b, s, nkv, hd)
        if arch["qk_norm"]:
            q, kk = r(_rms(q, a["q_norm"], eps)), r(_rms(kk, a["k_norm"], eps))
        q, kk = r(_rope(q, theta)), r(_rope(kk, theta))
        kk = jnp.repeat(kk, nh // nkv, axis=2)
        v = jnp.repeat(v, nh // nkv, axis=2)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, kk, precision=HIGHEST) / np.sqrt(hd)
        causal = jnp.tril(jnp.ones((s, s), bool))
        w = jax.nn.softmax(jnp.where(causal, sc, -1e30), axis=-1)
        o = r(jnp.einsum("bhqk,bkhd->bqhd", r(w), v, precision=HIGHEST))
        x = r(x + lin(o.reshape(b, s, nh * hd), a["wo"],
                      gemm_key(k, "attn", "wo")))
        h = r(_rms(x, p["ln2"], eps))
        m = p["ffn"]
        g = lin(h, m["w_gate"], gemm_key(k, "mlp", "w_gate"))
        u = lin(h, m["w_up"], gemm_key(k, "mlp", "w_up"))
        f = lin(r(r(jax.nn.silu(g)) * u), m["w_down"],
                gemm_key(k, "mlp", "w_down"))
        return r(x + f), None

    x = r(params["embed"][tokens])
    x, _ = jax.lax.scan(jax.checkpoint(block), x,
                        (params["layers"], jnp.arange(n["L"])))
    h = r(_rms(x, params["final_norm"], eps))
    logits = lin(h, params["embed"].T, gemm_key(key, "head", "lm_head"))
    logits = logits[:, :-1]
    gold = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


# ---------------------------------------------------------------- AdamW

def lr_at(opt: Dict, step: int) -> float:
    """The job's learning rate at optimizer step ``step`` (1-based)."""
    warm = min(step / max(opt["warmup_steps"], 1), 1.0)
    t = min(max((step - opt["warmup_steps"])
                / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0), 1.0)
    end = opt["end_lr_frac"]
    decay = {"cosine": end + (1 - end) * 0.5 * (1 + np.cos(np.pi * t)),
             "linear": 1.0 - (1.0 - end) * t,
             "constant": 1.0}[opt["schedule"]]
    return opt["peak_lr"] * warm * decay


def _decayed(name: str, leaf) -> bool:
    """The job's weight-decay rule: every array of rank >= 2 as stored
    (layer axis included)."""
    return leaf.ndim >= 2


@functools.partial(jax.jit, static_argnames=("opt_items",),
                   donate_argnums=(0, 2, 3))
def _adamw(params, grads, m, v, lr, step, scale, opt_items):
    """One AdamW step on gradients ``grads * scale`` (the clip factor)."""
    opt = dict(opt_items)
    b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
    b1c = 1.0 - b1 ** step
    b2c = 1.0 - b2 ** step

    def upd(path, p, g, mm, vv):
        g = g * scale
        mm = b1 * mm + (1 - b1) * g
        vv = b2 * vv + (1 - b2) * g * g
        delta = (mm / b1c) / (jnp.sqrt(vv / b2c) + eps)
        if _decayed(path, p):
            delta = delta + wd * p
        return p - lr * delta, mm, vv

    flat, tdef = jax.tree_util.tree_flatten_with_path(params)
    gs, ms, vs = (tdef.flatten_up_to(t) for t in (grads, m, v))
    out = [upd(path, p, g, a, c) for (path, p), g, a, c in zip(flat, gs, ms, vs)]
    return tuple(tdef.unflatten([o[j] for o in out]) for j in range(3))


def _opt_items(opt: Dict):
    keys = ("b1", "b2", "eps", "weight_decay")
    return tuple((k, float(opt[k])) for k in keys)


def train_record(model: Dict, job: Dict, seed: int, batches, *,
                 lowp: bool = False, half_batch: bool = False,
                 split_sums: bool = False) -> Dict:
    """Run the reference through ``len(batches)`` steps from the seed's
    weights; return the numbers the comparison reads: each step's loss and
    global gradient norm before clipping, the first step's clipped
    gradient per leaf, and each leaf's change
    after the last step. Adam's moments wait on the host while gradients
    are computed, so that a full-width model fits one chip."""
    recipe, opt = job["recipe"], job["optimizer"]
    key = seed_key(seed)
    hmodel = _Hashable(model)
    with jax.default_matmul_precision("highest"):
        params = WEIGHTS(hmodel, key)
        zeros = jax.tree.map(lambda p: np.zeros(p.shape, np.float32), params)
        m_host, v_host = zeros, jax.tree.map(np.copy, zeros)
        losses, norms, g1 = [], [], None
        for i, batch in enumerate(batches):
            tokens = jnp.asarray(batch[:1] if half_batch else batch)
            step_key = jax.random.fold_in(key, i)
            loss, g = GRAD(params, tokens, step_key, model=hmodel,
                           recipe=recipe, lowp=lowp, split_sums=split_sums)
            losses.append(float(loss))
            gn = float(jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g))))
            norms.append(gn)
            clip = opt["clip_norm"]
            scale = min(1.0, clip / max(gn, 1e-9)) if clip > 0 else 1.0
            if i == 0:
                g1 = jax.device_get(NORMS(g, scale))
            m, v = jax.device_put(m_host), jax.device_put(v_host)
            params, m, v = _adamw(params, g, m, v, lr_at(opt, i + 1), i + 1,
                                  scale, opt_items=_opt_items(opt))
            del g
            m_host, v_host = jax.device_get(m), jax.device_get(v)
            del m, v
        change = jax.device_get(CHANGES(params, hmodel, key))
    del params
    return {"losses": losses, "grad_norms": norms, "grad1": g1,
            "change": change}


class _Hashable(dict):
    """A config dict usable as a static jit argument."""

    def __hash__(self):
        return hash(repr(sorted(self.items(), key=lambda kv: kv[0])))

    def __eq__(self, other):
        return repr(sorted(self.items())) == repr(sorted(other.items()))


# Jitted once per process, so that every seed and every caller (the train
# mode, the calibration) shares one compiled program per shape.
WEIGHTS = jax.jit(make_weights, static_argnums=0)
NORMS = jax.jit(leaf_norms)
CHANGES = jax.jit(change_norms, static_argnums=1)
GRAD = jax.jit(jax.value_and_grad(loss_fn),
               static_argnames=("model", "recipe", "lowp", "split_sums"))
LOSS = jax.jit(loss_fn, static_argnames=("model", "recipe", "lowp",
                                         "split_sums"))
