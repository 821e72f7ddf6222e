"""ModelSpec — the contract between user models and the engine.

The reference wraps ``nn.Module`` objects (``runtime/engine.py:235``); this
framework is functional, so a model is a triple of pure functions plus sharding
metadata. Adapters exist for the built-in transformer zoo (here) and flax modules
(``models/flax_adapter.py``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Union

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import transformer as T

PyTree = Any
Batch = Union[jax.Array, Dict[str, jax.Array]]


@dataclasses.dataclass
class ModelSpec:
    init_fn: Callable[[jax.Array], PyTree]            # rng → fp32 params
    loss_fn: Callable[[PyTree, Batch], jax.Array]     # (compute params, batch) → scalar
    axes_fn: Callable[[], PyTree]                     # → logical-axes tree
    apply_fn: Optional[Callable[[PyTree, Batch], Any]] = None  # → model outputs
    name: str = "model"
    num_params: Optional[int] = None
    seq_len: Optional[int] = None  # nominal sequence length (profiling etc.)
    config: Any = None             # underlying model config (zoo: TransformerConfig)
    trainable_fn: Optional[Callable[[], PyTree]] = None  # bool tree; None = all trainable
    # optional explicit (loss, grads) path — used by schedules whose backward
    # cannot be derived by autodiff over the loss (1F1B pipeline). Called as
    # fn(compute_params, batch, loss_scale); returning None falls back to
    # value_and_grad over loss_fn. The decision must be trace-static.
    loss_and_grads_fn: Optional[Callable] = None
    # optional self-rebuild factory: fn(attention=None, loss_tiles=0) →
    # an equivalent ModelSpec with those knobs changed, preserving every
    # customization (LoRA adapters, imported weights, trainable masks...).
    # AutoSP uses this to swap the attention mechanism; specs without a
    # builder are left untouched (plan disabled).
    builder: Optional[Callable[..., "ModelSpec"]] = None


def _tokens_of(batch: Batch) -> jax.Array:
    if isinstance(batch, dict):
        return batch["tokens"]
    return batch


def _mask_of(batch: Batch):
    if isinstance(batch, dict):
        return batch.get("loss_mask")
    return None


def resolve_attention(attention: Optional[str]):
    """Named attention impls:

    * 'xla' (default) — XLA-fused reference attention
    * 'flash' — Pallas kernel (ops/pallas/flash_attention.py)
    * 'ulysses' / 'ulysses_flash' — all-to-all SP around xla/flash inner attention
    * 'ring' — KV-ring context parallelism over the 'seq' axis
    * 'chunked' — FPDT-style query-chunked attention (memory-capped)
    """
    if attention in (None, "xla", "default"):
        return None
    if attention == "flash":
        from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

        return flash_attention
    if attention == "ulysses":
        from deepspeed_tpu.sequence import ulysses_attention

        return ulysses_attention()
    if attention == "ulysses_flash":
        from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
        from deepspeed_tpu.sequence import ulysses_attention

        return ulysses_attention(inner=flash_attention)
    if attention == "ring":
        from deepspeed_tpu.sequence import ring_attention

        return ring_attention()
    if attention == "chunked":
        from deepspeed_tpu.sequence import chunked_attention

        return chunked_attention
    if attention == "fpdt":
        from deepspeed_tpu.sequence.tiled import fpdt_attention

        return fpdt_attention
    if attention.startswith("sparse"):
        # 'sparse' | 'sparse:fixed' | 'sparse:bigbird' | 'sparse:bslongformer'
        # (reference ops/sparse_attention SparseSelfAttention patterns)
        from deepspeed_tpu.ops.pallas import block_sparse as bs

        kind = attention.split(":", 1)[1] if ":" in attention else "fixed"
        builders = {"fixed": bs.fixed_layout, "bigbird": bs.bigbird_layout,
                    "bslongformer": bs.bslongformer_layout}
        if kind not in builders:
            raise ValueError(f"unknown sparse pattern {kind!r}; "
                             f"supported: {sorted(builders)}")

        def sparse_attn(q, k, v, causal=True, block_size=64):
            # model layout is [B, S, N, D]; kernel wants [B, N, S, D]
            if k.shape[2] != q.shape[2]:  # GQA: repeat kv heads
                rep = q.shape[2] // k.shape[2]
                k = jnp.repeat(k, rep, axis=2)
                v = jnp.repeat(v, rep, axis=2)
            lay = builders[kind](q.shape[1] // block_size)
            if causal:
                lay = bs.causal_layout(lay)
            out = bs.block_sparse_attention(
                q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3), lay, block_size, causal=causal)
            return out.transpose(0, 2, 1, 3)

        return sparse_attn
    raise ValueError(f"unknown attention impl {attention!r}")


def causal_lm_spec(cfg: Union[str, T.TransformerConfig],
                   attention_fn=None, activation_constraint=None,
                   attention: Optional[str] = None,
                   loss_tiles: int = 0,
                   loss_impl: str = "fused",
                   pipeline_schedule: str = "1f1b",
                   pipeline_micro_batches: Optional[int] = None,
                   param_sync_fn=None,
                   **overrides) -> ModelSpec:
    """Build a ModelSpec for a causal-LM transformer preset or config.

    ``loss_tiles > 1`` computes the LM loss over sequence tiles without
    materializing full logits (ALST TiledFusedLogitsLoss analog,
    reference ``runtime/sequence_parallel/ulysses_sp.py:1065``).
    PRECEDENCE: tiling takes priority over ``loss_impl`` — a tiled loss
    uses exact fp32 tile numerics, NOT the fused bf16-logit path
    (``loss_impl`` only selects between fused/exact when untiled; the two
    knobs answer different questions: memory class vs numerics class).
    ``pipeline_schedule``: '1f1b' (explicit backward, O(stages) activation
    memory — reference ``runtime/pipe/schedule.py:189``) or 'gpipe'
    (autodiff-reversed wavefront, O(microbatches)); only used when the mesh
    has a 'pipe' axis > 1. ``pipeline_micro_batches`` sets the schedule's
    microbatch count M (reference ``pipeline.micro_batches``): the fill/
    drain bubble is (P-1)/(M+P-1), so M ≫ P amortizes it; default M = P.
    ``param_sync_fn`` (engine-injected; ``parallel/overlap.make_grad_sync``)
    wraps each layer-scan chunk's params so gradient sync is emitted
    mid-backward — pair with the ``scan_chunks`` config override."""
    if attention_fn is not None and attention is not None:
        raise ValueError("pass either attention_fn or attention=, not both")
    if loss_impl not in ("fused", "exact"):
        raise ValueError(f"unknown loss_impl {loss_impl!r}; one of "
                         "fused|exact (a typo must not silently change the "
                         "loss numerics/perf class)")
    if attention_fn is None:
        attention_fn = resolve_attention(attention)
    if isinstance(cfg, str):
        name = cfg
        cfg = T.get_model_config(cfg, **overrides)
    else:
        name = "transformer"
        if overrides:
            cfg = dataclasses.replace(cfg, **overrides)

    def _pipe_stages() -> int:
        from deepspeed_tpu.comm.mesh import PIPE_AXIS, maybe_mesh

        mesh = maybe_mesh()
        return mesh.shape.get(PIPE_AXIS, 1) if mesh is not None else 1

    def loss_fn(params, batch):
        tokens = _tokens_of(batch)
        if _pipe_stages() > 1:
            loss, aux = T.pipelined_lm_loss(
                params, tokens, cfg, attention_fn=attention_fn,
                activation_constraint=activation_constraint,
                loss_mask=_mask_of(batch),
                n_micro=pipeline_micro_batches)
            if cfg.n_experts > 0:
                loss = loss + cfg.moe_aux_coef * aux
            return loss
        # engine-injected data-efficiency controls (PLD mask, random-LTD
        # kept-token indices) ride the batch dict under underscore keys
        pld_keep = batch.get("_pld_keep") if isinstance(batch, dict) else None
        ltd_idx = batch.get("_random_ltd_idx") if isinstance(batch, dict) \
            else None
        hidden, head, aux = T.forward_hidden(
            params, tokens, cfg, attention_fn=attention_fn,
            activation_constraint=activation_constraint,
            pld_keep=pld_keep, random_ltd_idx=ltd_idx,
            param_sync=param_sync_fn)
        with jax.named_scope("lm_head_loss"):
            if loss_tiles > 1:
                from deepspeed_tpu.sequence.tiled import tiled_lm_loss

                loss = tiled_lm_loss(hidden, head, tokens, _mask_of(batch),
                                     num_tiles=loss_tiles,
                                     logits_divisor=cfg.logits_divisor)
            elif loss_impl == "fused":
                # default training loss: bf16 logits + fp32 softmax stats
                # with a bandwidth-tuned custom VJP (torch-autocast CE
                # semantics — the exact-fp32-logits path stays under
                # loss_impl="exact"; inference/apply_fn logits are always
                # exact fp32)
                loss = T.fused_lm_loss(hidden, head, tokens,
                                       _mask_of(batch), cfg.logits_divisor)
            else:
                logits = T.lm_logits(hidden, head, cfg)
                loss = T.causal_lm_loss(logits, tokens, _mask_of(batch))
        if cfg.n_experts > 0:
            loss = loss + cfg.moe_aux_coef * aux
        return loss

    def apply_fn(params, batch):
        return T.forward(params, _tokens_of(batch), cfg,
                         attention_fn=attention_fn,
                         activation_constraint=activation_constraint)

    def loss_and_grads_fn(params, batch, loss_scale=None):
        if pipeline_schedule != "1f1b" or _pipe_stages() <= 1:
            return None   # engine falls back to value_and_grad(loss_fn)
        return T.pipelined_lm_loss_and_grads(
            params, _tokens_of(batch), cfg, attention_fn=attention_fn,
            activation_constraint=activation_constraint,
            loss_mask=_mask_of(batch), loss_scale=loss_scale,
            n_micro=pipeline_micro_batches)

    user_attention_fn = attention_fn is not None and attention is None
    orig_loss_tiles = loss_tiles
    orig_attention = attention
    orig_param_sync = param_sync_fn

    def _rebuild(attention: Optional[str] = None,
                 loss_tiles: int = 0,
                 remat: Optional[str] = None,
                 act_quant_bits: Optional[int] = None,
                 scan_chunks: Optional[int] = None,
                 param_sync_fn=None) -> "ModelSpec":
        # keep the stronger loss tiling of (original, requested) — AutoSP
        # must not untile a loss the user tiled to avoid full logits; an
        # unspecified attention keeps the original named mechanism.
        # act_quant_bits threads QAT activation quantization into the block
        # forward (compression/compress.py init_compression).
        # scan_chunks/param_sync_fn: the engine's overlap-scheduler rebuild
        # (chunked layer scan + mid-backward grad sync); None keeps the
        # original spec's values.
        cfg_over = {}
        if remat:
            cfg_over["remat"] = remat
        if act_quant_bits is not None:
            cfg_over["act_quant_bits"] = act_quant_bits
        if scan_chunks is not None:
            cfg_over["scan_chunks"] = int(scan_chunks)
        cfg2 = dataclasses.replace(cfg, **cfg_over) if cfg_over else cfg
        return causal_lm_spec(cfg2,
                              attention=attention or orig_attention,
                              loss_tiles=max(loss_tiles, orig_loss_tiles),
                              loss_impl=loss_impl,
                              activation_constraint=activation_constraint,
                              pipeline_schedule=pipeline_schedule,
                              param_sync_fn=param_sync_fn or orig_param_sync)

    return ModelSpec(
        init_fn=lambda rng: T.init_params(cfg, rng),
        loss_fn=loss_fn,
        apply_fn=apply_fn,
        axes_fn=lambda: T.param_logical_axes(cfg),
        name=name,
        num_params=cfg.num_params(),
        seq_len=cfg.max_seq_len,
        config=cfg,
        loss_and_grads_fn=loss_and_grads_fn,
        # a hand-written attention_fn has semantics a rewrite can't preserve
        # (sliding window, custom bias...) — no builder, so AutoSP declines
        builder=None if user_attention_fn else _rebuild,
    )


def spec_from_hf(model, arch: Optional[str] = None, attention: Optional[str] = None,
                 loss_tiles: int = 0, **overrides) -> ModelSpec:
    """Build a trainable ModelSpec from a HuggingFace model (or
    ``(state_dict, config)`` pair): weights are imported once
    (``models/hf_import.py``) and become the spec's initial parameters.

    The reference's equivalent is passing an HF model straight to
    ``deepspeed.initialize`` — here interop happens at the weight level."""
    import dataclasses as _dc

    import jax.numpy as _jnp

    from deepspeed_tpu.models.hf_import import import_hf_model

    cfg, params = import_hf_model(model, arch=arch)
    if overrides:
        cfg = _dc.replace(cfg, **overrides)
    base = causal_lm_spec(cfg, attention=attention, loss_tiles=loss_tiles)
    init_params = jax.tree.map(lambda x: _jnp.asarray(x, _jnp.float32), params)
    name = getattr(getattr(model, "config", None), "model_type", None) \
        or (arch or "hf_model")

    def _rebuild(attention: Optional[str] = None,
                 loss_tiles: int = 0,
                 remat: Optional[str] = None, **kwargs) -> ModelSpec:
        # **kwargs: scan_chunks / param_sync_fn etc. — forwarded so the
        # engine's overlap rebuild works on imported-weight specs too
        nb = base.builder(attention=attention, loss_tiles=loss_tiles,
                          remat=remat, **kwargs)
        return _dc.replace(nb, init_fn=lambda rng: init_params,
                           name=str(name))

    return _dc.replace(base, init_fn=lambda rng: init_params, name=str(name),
                       builder=_rebuild)
