"""Domino: tensor-parallel compute/communication overlap.

Parity: reference ``runtime/domino/transformer.py`` (``DominoTransformer``
:411, ``ShardedAttention`` :108): row/column-split TP layers whose batch is
split into two half-chunks so each chunk's TP allreduce runs asynchronously
under the other chunk's compute (hand-managed CUDA streams + async allreduce
handles; motivation: TP comm up to 43% of iteration time,
``blogs/deepspeed-domino/README.md:36``).

TPU translation — two mechanisms, both expressed here:

1. **XLA latency hiding (free Domino).** Under SPMD the TP collectives
   (psum after row-parallel matmuls) are emitted by the partitioner, and
   XLA's latency-hiding scheduler already overlaps them with independent
   compute, which is the bulk of what Domino hand-builds. The knobs live in
   :data:`XLA_OVERLAP_FLAGS` — enabled by default on recent libtpu; exposed
   so deployments can assert/force them.

2. **Explicit chunk interleaving.** :func:`domino_lm_loss` recreates
   Domino's batch-split: the microbatch is split into ``n_chunks`` along
   batch, each chunk's layer stack is traced independently, and the chunks'
   programs interleave in the scheduler's window. Losses combine exactly
   (equal chunks ⇒ identical numerics to the unsplit loss).

MEASURED (round 2, TP=2 on the 8-device CPU mesh — the only multi-device
venue available): chunked = 0.99× of unsplit, i.e. NO win — XLA's scheduler
already overlaps whatever it can and the chunk split only shrinks per-matmul
surfaces. The chunk path is therefore an OPT-IN mechanism (``domino_spec``)
kept for parity and for future multi-chip ICI profiling, not an asserted
speedup; mechanism 1 (the default compiler behavior + flags above) is the
production answer to Domino on TPU. See ``tests/unit/test_domino_zenflow.py``
for the parity + measurement harness.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import transformer as T

PyTree = Any

# XLA flags that control collective/compute overlap on TPU (documented for
# deployment parity with Domino's async-allreduce machinery; current libtpu
# enables the scheduler by default). Apply through
# :func:`apply_overlap_flags` — NEVER by blindly appending to XLA_FLAGS:
# the set spans jaxlib generations and an unknown ``--xla_*`` flag
# hard-aborts backend creation (``F parse_flags_from_env``). The probe
# (``utils/xla_compat.probe_xla_flags``, same machinery as
# tests/conftest.py's collective-timeout flags) vets each flag in a
# throwaway subprocess and the unsupported ones are logged and skipped.
XLA_OVERLAP_FLAGS = (
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
    "--xla_tpu_overlap_compute_collective_tc=true",
    "--xla_enable_async_all_gather=true",
    "--xla_enable_async_collective_permute=true",
)


def supported_overlap_flags() -> tuple:
    """The subset of :data:`XLA_OVERLAP_FLAGS` this jaxlib accepts
    (probed once per jaxlib version, cached; see
    ``utils/xla_compat.probe_xla_flags``)."""
    from deepspeed_tpu.utils.xla_compat import probe_xla_flags

    return probe_xla_flags(XLA_OVERLAP_FLAGS)


def apply_overlap_flags() -> str:
    """Append the PROBED overlap flags to ``XLA_FLAGS`` (idempotent).

    Returns the flags actually APPENDED by this call, as one string —
    empty when nothing changed: no flag supported, or every flag name
    already present in ``XLA_FLAGS`` (a user's explicit ``=false`` is
    respected, not overridden, and not reported as armed). Every
    skipped flag is logged, not raised: an older jaxlib must degrade to
    its default scheduler, not crash. Call BEFORE the first jax backend
    use — once a backend exists the env change only reaches
    subprocesses (bench entries, launcher workers)."""
    import os

    from deepspeed_tpu.utils.logging import logger

    supported = supported_overlap_flags()
    skipped = [f for f in XLA_OVERLAP_FLAGS if f not in supported]
    if skipped:
        logger.info(
            f"domino overlap flags not supported by this jaxlib — "
            f"skipped: {' '.join(skipped)}")
    if not supported:
        return ""
    current = os.environ.get("XLA_FLAGS", "")
    # compare flag NAMES, not full tokens: a user who explicitly set
    # --xla_...=false must not have it silently overridden by appending
    # our =true after it (XLA takes the last occurrence)
    present = {tok.split("=", 1)[0] for tok in current.split()}
    missing = [f for f in supported
               if f.split("=", 1)[0] not in present]
    if missing:
        os.environ["XLA_FLAGS"] = (current + " " + " ".join(missing)).strip()
    return " ".join(missing)


def domino_lm_loss(params: PyTree, tokens: jax.Array, cfg: T.TransformerConfig,
                   n_chunks: int = 2,
                   attention_fn: Optional[Callable] = None,
                   activation_constraint: Optional[Callable] = None,
                   loss_mask: Optional[jax.Array] = None) -> jax.Array:
    """Causal-LM loss with the batch split into ``n_chunks`` interleaved
    chunks (the Domino batch-split; reference ``DominoTransformer`` forward).

    Each chunk runs the full layer stack as an independent program slice, so
    the TP allreduce of one chunk overlaps the compute of the next. With
    equal chunk sizes the result is numerically identical to the unsplit
    loss (mean of per-chunk means over equal token counts).
    """
    B = tokens.shape[0]
    if B % n_chunks:
        raise ValueError(f"batch {B} not divisible by n_chunks={n_chunks}")
    step = B // n_chunks
    losses = []
    for c in range(n_chunks):
        tk = jax.lax.slice_in_dim(tokens, c * step, (c + 1) * step, axis=0)
        hidden, head, aux = T.forward_hidden(
            params, tk, cfg, attention_fn=attention_fn,
            activation_constraint=activation_constraint)
        logits = T.lm_logits(hidden, head, cfg)
        mk = None
        if loss_mask is not None:
            mk = jax.lax.slice_in_dim(loss_mask, c * step, (c + 1) * step, 0)
        loss = T.causal_lm_loss(logits, tk, mk)
        if cfg.n_experts > 0:
            loss = loss + cfg.moe_aux_coef * aux
        losses.append(loss)
    return jnp.mean(jnp.stack(losses))


def domino_spec(cfg, n_chunks: int = 2, attention: Optional[str] = None,
                **overrides):
    """ModelSpec whose loss uses Domino chunk interleaving — drop-in for
    ``causal_lm_spec`` when TP comm dominates (``deepspeed_tpu.initialize``
    consumes it unchanged)."""
    import dataclasses as _dc

    from deepspeed_tpu.models.api import causal_lm_spec, resolve_attention

    base = causal_lm_spec(cfg, attention=attention, **overrides)
    attention_fn = resolve_attention(attention)
    model_cfg = base.config

    def loss_fn(params, batch):
        tokens = batch["tokens"] if isinstance(batch, dict) else batch
        mask = batch.get("loss_mask") if isinstance(batch, dict) else None
        return domino_lm_loss(params, tokens, model_cfg, n_chunks=n_chunks,
                              attention_fn=attention_fn, loss_mask=mask)

    return _dc.replace(base, loss_fn=loss_fn, name=base.name + f"+domino{n_chunks}")
