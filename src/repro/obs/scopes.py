"""The train step's ``jax.named_scope`` vocabulary, in one place.

A named scope adds only HLO ``op_name`` metadata (``jit(train_step)/.../
mlp_up/qgemm.fwd/quantize/...``): the compiled program and its numerics
are unchanged, so the scopes are always on. A profiler trace names device
operations by HLO instruction; the compiled step's ``as_text()`` maps each
instruction to its ``op_name``, and so each traced operation to the
innermost scope below that it ran under.

* ``QGEMM[gemm]`` (``core/pipeline.py:execute_terms``): everything one of a
  linear layer's three GeMMs evaluates.
* ``QGEMM_WEIGHTS`` (``core/qgemm.py``): the weight-operand pipelines that
  ``Model.prepare_qweights`` runs once per step, outside ``jax.grad``.
* ``STAGE[...]`` and ``FUSED`` (``core/pipeline.py:apply_stages``): the
  recipe's center, Hadamard and quantize passes, or one fused Pallas pass.
* ``TERM[kind]`` (``execute_terms``): the GeMM product terms themselves.
* the GeMM site's role (``models/layers.py:QuantCtx.gemm``): ``attn_qkv``,
  ``attn_o``, ``mlp_up``, ``mlp_down``, ``lm_head``, ...
* ``ATTN_CORE`` (``models/attention.py:attention_core``): scores, softmax
  and values.
* ``LOSS`` (``Model.loss``): the cross-entropy's logsumexp and gather.
* ``ADAMW_CLIP``, ``ADAMW_UPDATE`` (``optim/adamw.py:apply_updates``).
"""
from __future__ import annotations

QGEMM = {g: f"qgemm.{g}" for g in ("fwd", "dx", "dw")}
QGEMM_WEIGHTS = "qgemm.weights"
STAGE = {"Center": "center", "Hadamard": "hadamard", "Quantize": "quantize"}
FUSED = "fused"
TERM = {k: k for k in ("matmul", "mean_row", "rank1")}
ATTN_CORE = "attn.core"
LOSS = "loss"
ADAMW_CLIP = "adamw.clip"
ADAMW_UPDATE = "adamw.update"
