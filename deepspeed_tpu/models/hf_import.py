"""Import HuggingFace transformer weights into the model zoo.

Role: the reference consumes HF models directly (AutoTP
``module_inject/auto_tp.py``, checkpoint loading ``inference/engine.py:303``,
FastGen's per-arch implementations ``inference/v2/model_implementations``).
This framework is torch-free at runtime, so interop happens at the weight
level: convert an HF state dict (torch CPU tensors) into the zoo's
layer-stacked param pytree once, then everything — ZeRO, TP, inference —
works on it.

Supported architectures: gpt2, llama (mistral shares the schema), mixtral
(MoE). Conventions verified by logit-matching tests against ``transformers``:
* HF ``nn.Linear`` weights are [out, in] → transposed; GPT-2's ``Conv1D`` is
  already [in, out] → copied as-is.
* Llama RoPE uses the rotate-half (non-interleaved) convention — identical to
  ``transformer.apply_rope``.
"""
from __future__ import annotations

import copy
import dataclasses
from typing import Any, Dict, Optional, Tuple

import numpy as np

from deepspeed_tpu.models.transformer import TransformerConfig

PyTree = Any


def _np(t) -> np.ndarray:
    if hasattr(t, "detach"):
        t = t.detach().cpu().float().numpy()
    return np.asarray(t, np.float32)


def _layers(L) -> range:
    """``L``: a depth (layers 0 .. L-1) or the range of layer indices."""
    return range(L) if isinstance(L, int) else L


def _stack(sd: Dict[str, Any], fmt: str, L, transpose: bool = False
           ) -> np.ndarray:
    mats = [_np(sd[fmt.format(i)]) for i in _layers(L)]
    if transpose:
        mats = [m.T for m in mats]
    return np.stack(mats)


def _canon_rope_scaling(hf_config) -> Optional[tuple]:
    """HF rope_scaling dict → canonical hashable tuple for the frozen zoo
    config; validates the type is one the zoo implements
    (``transformer._scaled_inv_freq``: default/linear/llama3/yarn) by raising
    the zoo's NotImplementedError for anything else — silently ignoring
    scaling would mean wrong logits on every real Llama-3/DeepSeek
    checkpoint."""
    rs = getattr(hf_config, "rope_scaling", None)
    if not rs:
        return None
    sc = {k: v for k, v in dict(rs).items() if v is not None}
    # yarn falls back to the model's max positions when 'original_...' absent
    sc.setdefault("max_position_embeddings",
                  getattr(hf_config, "max_position_embeddings", 2048))
    from deepspeed_tpu.models.transformer import _scaled_inv_freq

    _scaled_inv_freq(64, 10000.0, sc)   # type/keys validation
    return tuple(sorted(sc.items()))


# --------------------------------------------------------------------------- #
# GPT-2
# --------------------------------------------------------------------------- #

def config_from_gpt2(hf_config) -> TransformerConfig:
    return TransformerConfig(
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.n_embd,
        num_layers=hf_config.n_layer,
        num_heads=hf_config.n_head,
        max_seq_len=hf_config.n_positions,
        pos_emb="learned", norm="layernorm", activation="gelu",
        use_bias=True, tie_embeddings=True,
        norm_eps=hf_config.layer_norm_epsilon, dtype="float32")


def params_from_gpt2(sd: Dict[str, Any], cfg: TransformerConfig) -> PyTree:
    L, H = cfg.num_layers, cfg.hidden_size
    pre = "transformer." if any(k.startswith("transformer.") for k in sd) else ""

    # Conv1D c_attn: [H, 3H] (in, out) — split into q/k/v without transposing
    c_attn = _stack(sd, pre + "h.{}.attn.c_attn.weight", L)       # [L, H, 3H]
    b_attn = _stack(sd, pre + "h.{}.attn.c_attn.bias", L)         # [L, 3H]
    blocks = {
        "ln1": {"scale": _stack(sd, pre + "h.{}.ln_1.weight", L),
                "bias": _stack(sd, pre + "h.{}.ln_1.bias", L)},
        "ln2": {"scale": _stack(sd, pre + "h.{}.ln_2.weight", L),
                "bias": _stack(sd, pre + "h.{}.ln_2.bias", L)},
        "wq": c_attn[:, :, :H], "wk": c_attn[:, :, H:2 * H],
        "wv": c_attn[:, :, 2 * H:],
        "bq": b_attn[:, :H], "bk": b_attn[:, H:2 * H], "bv": b_attn[:, 2 * H:],
        "wo": _stack(sd, pre + "h.{}.attn.c_proj.weight", L),
        "bo": _stack(sd, pre + "h.{}.attn.c_proj.bias", L),
        "w_up": _stack(sd, pre + "h.{}.mlp.c_fc.weight", L),
        "b_up": _stack(sd, pre + "h.{}.mlp.c_fc.bias", L),
        "w_down": _stack(sd, pre + "h.{}.mlp.c_proj.weight", L),
        "b_down": _stack(sd, pre + "h.{}.mlp.c_proj.bias", L),
    }
    return {
        "tok_emb": _np(sd[pre + "wte.weight"]),
        "pos_emb": _np(sd[pre + "wpe.weight"]),
        "blocks": blocks,
        "final_norm": {"scale": _np(sd[pre + "ln_f.weight"]),
                       "bias": _np(sd[pre + "ln_f.bias"])},
    }


# --------------------------------------------------------------------------- #
# Llama / Mistral
# --------------------------------------------------------------------------- #

def config_from_llama(hf_config) -> TransformerConfig:
    return TransformerConfig(
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.hidden_size,
        num_layers=hf_config.num_hidden_layers,
        num_heads=hf_config.num_attention_heads,
        num_kv_heads=getattr(hf_config, "num_key_value_heads", None),
        ffn_hidden_size=hf_config.intermediate_size,
        max_seq_len=hf_config.max_position_embeddings,
        pos_emb="rope", norm="rmsnorm", activation="swiglu",
        use_bias=False,
        tie_embeddings=bool(getattr(hf_config, "tie_word_embeddings", False)),
        rope_theta=float(getattr(hf_config, "rope_theta", 10000.0)),
        rope_scaling=_canon_rope_scaling(hf_config),
        norm_eps=hf_config.rms_norm_eps, dtype="float32")


def params_from_llama(sd: Dict[str, Any], cfg: TransformerConfig) -> PyTree:
    L = cfg.num_layers
    pre = "model." if any(k.startswith("model.") for k in sd) else ""
    lyr = pre + "layers.{}."
    blocks, params = _llama_attn_blocks(sd, cfg, pre)
    blocks.update({
        "w_gate": _stack(sd, lyr + "mlp.gate_proj.weight", L, transpose=True),
        "w_up": _stack(sd, lyr + "mlp.up_proj.weight", L, transpose=True),
        "w_down": _stack(sd, lyr + "mlp.down_proj.weight", L, transpose=True),
    })
    params["blocks"] = blocks
    return params


# --------------------------------------------------------------------------- #
# Mixtral (Llama schema + MoE FFN)
# --------------------------------------------------------------------------- #

def config_from_mixtral(hf_config) -> TransformerConfig:
    cfg = config_from_llama(hf_config)
    return dataclasses.replace(
        cfg,
        n_experts=hf_config.num_local_experts,
        moe_top_k=hf_config.num_experts_per_tok,
        moe_aux_coef=float(getattr(hf_config, "router_aux_loss_coef", 0.02)))


def params_from_mixtral(sd: Dict[str, Any], cfg: TransformerConfig) -> PyTree:
    L, E = cfg.num_layers, cfg.n_experts
    pre = "model." if any(k.startswith("model.") for k in sd) else ""
    moe = pre + "layers.{}.block_sparse_moe."

    def experts(wname):  # HF w1=gate, w2=down, w3=up; nn.Linear [out,in]
        return np.stack([
            np.stack([_np(sd[moe.format(i) + f"experts.{e}.{wname}.weight"]).T
                      for e in range(E)])
            for i in range(L)])

    blocks, params = _llama_attn_blocks(sd, cfg, pre)
    blocks.update({
        "gate_w": _stack(sd, moe + "gate.weight", L, transpose=True),
        "w_gate": experts("w1"),
        "w_down": experts("w2"),
        "w_up": experts("w3"),
    })
    params["blocks"] = blocks
    return params



# --------------------------------------------------------------------------- #
# Qwen2 (Llama schema + attention biases)
# --------------------------------------------------------------------------- #

def config_from_qwen2(hf_config) -> TransformerConfig:
    cfg = config_from_llama(hf_config)
    return dataclasses.replace(cfg, qkv_bias=True)


def params_from_qwen2(sd: Dict[str, Any], cfg: TransformerConfig) -> PyTree:
    L = cfg.num_layers
    params = params_from_llama(sd, cfg)
    pre = "model." if any(k.startswith("model.") for k in sd) else ""
    lyr = pre + "layers.{}."
    params["blocks"].update({
        "bq": _stack(sd, lyr + "self_attn.q_proj.bias", L),
        "bk": _stack(sd, lyr + "self_attn.k_proj.bias", L),
        "bv": _stack(sd, lyr + "self_attn.v_proj.bias", L),
    })
    return params


# --------------------------------------------------------------------------- #
# Qwen2-MoE / Qwen3-MoE (AutoEP presets; reference module_inject/auto_ep_presets/
# {qwen3_moe,qwen3_5_moe}.py detection patterns — here realized as importers)
# --------------------------------------------------------------------------- #

def _assert_homogeneous_moe(hf_config) -> None:
    """The zoo scans a homogeneous layer stack; Qwen-MoE configs that mix
    dense and sparse layers (decoder_sparse_step > 1 or mlp_only_layers)
    can't be stacked."""
    step = int(getattr(hf_config, "decoder_sparse_step", 1) or 1)
    only = list(getattr(hf_config, "mlp_only_layers", []) or [])
    if step != 1 or only:
        raise NotImplementedError(
            f"heterogeneous MoE stack (decoder_sparse_step={step}, "
            f"mlp_only_layers={only}) is not supported by the stacked-layer "
            "zoo; every layer must be sparse")


def config_from_qwen2_moe(hf_config) -> TransformerConfig:
    _assert_homogeneous_moe(hf_config)
    cfg = config_from_llama(hf_config)
    return dataclasses.replace(
        cfg, qkv_bias=True,
        n_experts=hf_config.num_experts,
        moe_top_k=hf_config.num_experts_per_tok,
        moe_ffn_size=hf_config.moe_intermediate_size,
        moe_shared_size=hf_config.shared_expert_intermediate_size,
        moe_shared_gate=True,
        moe_route_norm=bool(hf_config.norm_topk_prob),
        moe_aux_coef=float(getattr(hf_config, "router_aux_loss_coef", 0.001)))


def _llama_attn_blocks(sd: Dict[str, Any], cfg: TransformerConfig,
                       pre: str) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Shared Llama-schema attention/norm/embedding pieces (no FFN)."""
    L = cfg.num_layers
    lyr = pre + "layers.{}."
    blocks = {
        "ln1": {"scale": _stack(sd, lyr + "input_layernorm.weight", L)},
        "ln2": {"scale": _stack(sd, lyr + "post_attention_layernorm.weight", L)},
        "wq": _stack(sd, lyr + "self_attn.q_proj.weight", L, transpose=True),
        "wk": _stack(sd, lyr + "self_attn.k_proj.weight", L, transpose=True),
        "wv": _stack(sd, lyr + "self_attn.v_proj.weight", L, transpose=True),
        "wo": _stack(sd, lyr + "self_attn.o_proj.weight", L, transpose=True),
    }
    params = {
        "tok_emb": _np(sd[pre + "embed_tokens.weight"]),
        "final_norm": {"scale": _np(sd[pre + "norm.weight"])},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _np(sd["lm_head.weight"]).T
    return blocks, params


def _qwen_moe_experts(sd: Dict[str, Any], moe_fmt: str, L, E: int,
                      first: int = 0,
                      names=("gate_proj", "up_proj", "down_proj")):
    """Stack per-expert gate/up/down ModuleList weights → [L, E, in, out]
    (the ``E`` experts from ``first``: a share of the checkpoint's;
    ``names``: what the family calls the three)."""
    def experts(wname):
        return np.stack([
            np.stack([_np(sd[moe_fmt.format(i) + f"experts.{e}.{wname}.weight"]).T
                      for e in range(first, first + E)])
            for i in _layers(L)])

    gate, up, down = names
    return {"w_gate": experts(gate), "w_up": experts(up),
            "w_down": experts(down)}


def params_from_qwen2_moe(sd: Dict[str, Any], cfg: TransformerConfig) -> PyTree:
    L, E = cfg.num_layers, cfg.n_experts
    pre = "model." if any(k.startswith("model.") for k in sd) else ""
    lyr = pre + "layers.{}."
    moe = lyr + "mlp."
    blocks, params = _llama_attn_blocks(sd, cfg, pre)
    blocks.update({
        "bq": _stack(sd, lyr + "self_attn.q_proj.bias", L),
        "bk": _stack(sd, lyr + "self_attn.k_proj.bias", L),
        "bv": _stack(sd, lyr + "self_attn.v_proj.bias", L),
        "gate_w": _stack(sd, moe + "gate.weight", L, transpose=True),
        "sw_gate": _stack(sd, moe + "shared_expert.gate_proj.weight", L,
                          transpose=True),
        "sw_up": _stack(sd, moe + "shared_expert.up_proj.weight", L,
                        transpose=True),
        "sw_down": _stack(sd, moe + "shared_expert.down_proj.weight", L,
                          transpose=True),
        "shared_gate_w": _stack(sd, moe + "shared_expert_gate.weight", L,
                                transpose=True),
    })
    blocks.update(_qwen_moe_experts(sd, moe, L, E))
    params["blocks"] = blocks
    return params


def config_from_qwen3(hf_config) -> TransformerConfig:
    """Qwen3 dense: llama schema + QK-norm + explicit head_dim, no qkv bias."""
    cfg = config_from_llama(hf_config)
    return dataclasses.replace(
        cfg, qk_norm=True, attn_head_dim=getattr(hf_config, "head_dim", None))


def params_from_qwen3(sd: Dict[str, Any], cfg: TransformerConfig) -> PyTree:
    L = cfg.num_layers
    params = params_from_llama(sd, cfg)
    pre = "model." if any(k.startswith("model.") for k in sd) else ""
    lyr = pre + "layers.{}."
    params["blocks"]["q_norm"] = _stack(sd, lyr + "self_attn.q_norm.weight", L)
    params["blocks"]["k_norm"] = _stack(sd, lyr + "self_attn.k_norm.weight", L)
    return params


def config_from_qwen3_moe(hf_config) -> TransformerConfig:
    _assert_homogeneous_moe(hf_config)
    cfg = config_from_llama(hf_config)
    head_dim = getattr(hf_config, "head_dim", None)
    return dataclasses.replace(
        cfg, qk_norm=True, attn_head_dim=head_dim,
        n_experts=hf_config.num_experts,
        moe_top_k=hf_config.num_experts_per_tok,
        moe_ffn_size=hf_config.moe_intermediate_size,
        moe_route_norm=bool(hf_config.norm_topk_prob),
        moe_aux_coef=float(getattr(hf_config, "router_aux_loss_coef", 0.001)))


def params_from_qwen3_moe(sd: Dict[str, Any], cfg: TransformerConfig) -> PyTree:
    L, E = cfg.num_layers, cfg.n_experts
    pre = "model." if any(k.startswith("model.") for k in sd) else ""
    lyr = pre + "layers.{}."
    moe = lyr + "mlp."
    blocks, params = _llama_attn_blocks(sd, cfg, pre)
    blocks.update({
        "gate_w": _stack(sd, moe + "gate.weight", L, transpose=True),
        "q_norm": _stack(sd, lyr + "self_attn.q_norm.weight", L),
        "k_norm": _stack(sd, lyr + "self_attn.k_norm.weight", L),
    })
    blocks.update(_qwen_moe_experts(sd, moe, L, E))
    params["blocks"] = blocks
    return params


# --------------------------------------------------------------------------- #
# Mellum 2 (JetBrains: the Qwen3-MoE block over window and full attention
# layers, rotary by layer type)
# --------------------------------------------------------------------------- #

def config_from_mellum(hf_config) -> TransformerConfig:
    """``model_type`` ``mellum``: the Qwen3-MoE block (grouped-query
    attention under per-head q/k RMSNorm, no biases; a softmax router,
    ``num_experts_per_tok`` of ``num_experts`` SwiGLU experts, weights
    renormalised where ``norm_topk_prob``, no shared expert) in every layer
    (``mlp_layer_types`` all ``sparse``); ``layer_types`` says which layers
    see ``sliding_window`` positions and which every one, and
    ``rope_parameters`` gives each type its own rotary: theta and scaling
    (the full layers' YaRN with its ``attention_factor``), carried as
    ``TransformerConfig.kind_rope``.

    A SHARE of the expert layers (``TransformerConfig.moe_router_experts``):
    ``num_experts`` is then the experts held, ``router_experts`` the
    router's width (the published count) and ``first_expert`` the first
    one held; without ``router_experts`` every expert is held.
    ``router_init_std``, where given, is what a router drawn from scratch
    is drawn with (``TransformerConfig.moe_router_init_std``)."""
    names = {"sliding_attention": "window", "full_attention": "full"}
    kinds = tuple(names[t] for t in hf_config.layer_types)
    L = hf_config.num_hidden_layers
    mlp = list(getattr(hf_config, "mlp_layer_types", None) or ["sparse"] * L)
    if len(kinds) != L or len(mlp) != L or set(mlp) != {"sparse"}:
        raise NotImplementedError(
            f"mellum: layer_types names {len(kinds)} and mlp_layer_types "
            f"{len(mlp)} layers of num_hidden_layers={L}, and every layer's "
            f"FFN is `sparse` in what is written (got {sorted(set(mlp))})")
    if "window" in kinds and not (getattr(hf_config, "use_sliding_window",
                                          True)
                                  and hf_config.sliding_window):
        raise ValueError("mellum: sliding_attention layers need "
                         "use_sliding_window and a sliding_window")

    def rope(section):
        sc = {k: v for k, v in dict(section).items() if v is not None}
        theta = float(sc.pop("rope_theta"))
        if sc.get("rope_type", "default") == "default":
            return theta, None
        from deepspeed_tpu.models.transformer import _scaled_inv_freq

        _scaled_inv_freq(64, theta, sc)         # type / keys validation
        return theta, tuple(sorted(sc.items()))

    ropes = {names[t]: rope(sec)
             for t, sec in dict(hf_config.rope_parameters).items()}
    missing = set(kinds) - set(ropes)
    if missing:
        raise ValueError(f"mellum: rope_parameters has no section for the "
                         f"layers of kind {sorted(missing)}")
    # the model's own table is its first layer's kind's; a kind that
    # differs from it carries its own
    theta, scaling = ropes[kinds[0]]
    held = hf_config.num_experts
    router = int(getattr(hf_config, "router_experts", held))
    h = hf_config.hidden_size
    return TransformerConfig(
        vocab_size=hf_config.vocab_size, hidden_size=h, num_layers=L,
        num_heads=hf_config.num_attention_heads,
        num_kv_heads=hf_config.num_key_value_heads,
        attn_head_dim=int(getattr(hf_config, "head_dim",
                                  h // hf_config.num_attention_heads)),
        ffn_hidden_size=hf_config.intermediate_size,
        max_seq_len=hf_config.max_position_embeddings,
        pos_emb="rope", norm="rmsnorm", activation="swiglu", use_bias=False,
        qkv_bias=bool(getattr(hf_config, "attention_bias", False)),
        tie_embeddings=bool(getattr(hf_config, "tie_word_embeddings", False)),
        rope_theta=theta, rope_scaling=scaling,
        kind_rope=tuple(sorted((k, v) for k, v in ropes.items()
                               if k in kinds and v != (theta, scaling))),
        norm_eps=hf_config.rms_norm_eps, dtype="float32",
        qk_norm=bool(getattr(hf_config, "qk_norm", True)),
        layer_kinds=kinds, attn_window=int(hf_config.sliding_window or 0),
        n_experts=held, moe_top_k=hf_config.num_experts_per_tok,
        moe_ffn_size=hf_config.moe_intermediate_size,
        moe_route_norm=bool(hf_config.norm_topk_prob),
        moe_aux_coef=float(getattr(hf_config, "router_aux_loss_coef", 0.001)),
        moe_dispatch="ragged",
        moe_router_experts=router if router != held else 0,
        moe_first_expert=int(getattr(hf_config, "first_expert", 0)),
        moe_router_init_std=float(getattr(hf_config, "router_init_std", 0.0)))


def params_from_mellum(sd: Dict[str, Any], cfg: TransformerConfig) -> PyTree:
    """The Qwen3-MoE family's tensor names; a share of the experts takes
    its own from the checkpoint's."""
    L = cfg.num_layers
    pre = "model." if any(k.startswith("model.") for k in sd) else ""
    lyr = pre + "layers.{}."
    moe = lyr + "mlp."
    blocks, params = _llama_attn_blocks(sd, cfg, pre)
    blocks["gate_w"] = _stack(sd, moe + "gate.weight", L, transpose=True)
    if cfg.qk_norm:
        blocks["q_norm"] = _stack(sd, lyr + "self_attn.q_norm.weight", L)
        blocks["k_norm"] = _stack(sd, lyr + "self_attn.k_norm.weight", L)
    blocks.update(_qwen_moe_experts(sd, moe, L, cfg.n_experts,
                                    cfg.moe_first_expert))
    params["blocks"] = blocks
    return params


# --------------------------------------------------------------------------- #
# Keye-VL-2 (Kwai: the Qwen3-MoE block under a learned sparse-attention
# indexer; the language model alone)
# --------------------------------------------------------------------------- #

def config_from_keye_vl2(hf_config) -> TransformerConfig:
    """``model_type`` ``KeyeVL2``, the language model (the vision tower is
    not read: a tick's input is token ids, for which the three position
    streams of ``rope_scaling.mrope_section`` carry one index, which is
    ordinary rotary over the whole head): the Qwen3-MoE block (per-head q/k
    RMSNorm, a softmax router over ``num_experts`` with ``norm_topk_prob``,
    no shared expert) in which every layer is ``sparse``: ``sa_config``'s
    indexer (``indexer_num_heads`` query heads and one key head of
    ``indexer_head_dim``) chooses the ``topk`` positions a row attends to
    (``TransformerConfig.sparse_topk``). ``q_chunk_size`` /
    ``kv_chunk_size`` are the tile sizes of the published kernels' loops
    and enter no equation.

    A SHARE of the expert layers (``TransformerConfig.moe_router_experts``):
    ``num_experts`` is then the experts held, ``router_experts`` the
    router's width (the published count) and ``first_expert`` the first
    one held; without ``router_experts`` every expert is held."""
    sa = dict(hf_config.sa_config)
    if int(sa.get("indexer_num_kv_heads", 1)) != 1 \
            or getattr(hf_config, "use_sliding_window", False):
        raise NotImplementedError(
            "KeyeVL2: an indexer of one key head and no sliding window are "
            "what is written")
    scaling = dict(getattr(hf_config, "rope_scaling", None) or {})
    if scaling.get("rope_type", scaling.get("type", "default")) != "default":
        raise NotImplementedError(
            f"KeyeVL2: unscaled rotary is what is written (got {scaling})")
    # (what is left of ``rope_scaling`` is ``mrope_section``: see above)
    plain = copy.copy(hf_config)
    plain.rope_scaling = None
    cfg = config_from_qwen3_moe(plain)
    held = hf_config.num_experts
    router = int(getattr(hf_config, "router_experts", held))
    return dataclasses.replace(
        cfg, layer_kinds=("sparse",) * cfg.num_layers,
        sparse_topk=int(sa["topk"]),
        index_heads=int(sa["indexer_num_heads"]),
        index_head_dim=int(sa["indexer_head_dim"]),
        moe_dispatch="ragged",
        moe_router_experts=router if router != held else 0,
        moe_first_expert=int(getattr(hf_config, "first_expert", 0)))


def params_from_keye_vl2(sd: Dict[str, Any], cfg: TransformerConfig) -> PyTree:
    """The Qwen3-MoE names under the language model's prefix, beside
    ``self_attn.indexer.{wq, wk, k_norm, weights_proj}`` (the indexer as
    DeepSeek sparse attention names it; its queries here come from the
    layer's normed input, ``wq [heads x dim, hidden]``). A share of the
    experts takes its own from the checkpoint's; tensors of the vision
    tower are not read."""
    L = cfg.num_layers
    pre = next((p for p in ("model.language_model.", "language_model.model.",
                            "model.") if any(k.startswith(p) for k in sd)), "")
    lyr = pre + "layers.{}."
    blocks, params = _llama_attn_blocks(sd, cfg, pre)
    blocks.update({
        "gate_w": _stack(sd, lyr + "mlp.gate.weight", L, transpose=True),
        "q_norm": _stack(sd, lyr + "self_attn.q_norm.weight", L),
        "k_norm": _stack(sd, lyr + "self_attn.k_norm.weight", L),
    })
    idx = lyr + "self_attn.indexer."
    blocks.update({
        "idx_wq": _stack(sd, idx + "wq.weight", L, transpose=True),
        "idx_wk": _stack(sd, idx + "wk.weight", L, transpose=True),
        "idx_ww": _stack(sd, idx + "weights_proj.weight", L, transpose=True),
        "idx_k_norm": {"scale": _stack(sd, idx + "k_norm.weight", L),
                       "bias": _stack(sd, idx + "k_norm.bias", L)}})
    blocks.update(_qwen_moe_experts(sd, lyr + "mlp.", L, cfg.n_experts,
                                    cfg.moe_first_expert))
    params["blocks"] = blocks
    return params


# --------------------------------------------------------------------------- #
# DeepSeek V2/V3 (MLA attention + sigmoid/grouped routing + shared experts;
# AutoEP presets module_inject/auto_ep_presets/deepseek_v{2,3}.py)
# --------------------------------------------------------------------------- #

def config_from_deepseek_v3(hf_config) -> TransformerConfig:
    first_dense = int(getattr(hf_config, "first_k_dense_replace", 0) or 0)
    if int(getattr(hf_config, "moe_layer_freq", 1) or 1) != 1:
        raise NotImplementedError(
            f"moe_layer_freq={hf_config.moe_layer_freq}: the zoo's stack is "
            "leading dense layers, then expert layers; interleaved dense "
            "layers are not supported")
    shared = int(getattr(hf_config, "n_shared_experts", 0) or 0)
    return TransformerConfig(
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.hidden_size,
        num_layers=hf_config.num_hidden_layers,
        num_heads=hf_config.num_attention_heads,
        ffn_hidden_size=hf_config.intermediate_size,
        max_seq_len=hf_config.max_position_embeddings,
        pos_emb="rope", norm="rmsnorm", activation="swiglu", use_bias=False,
        tie_embeddings=bool(getattr(hf_config, "tie_word_embeddings", False)),
        rope_theta=float(getattr(hf_config, "rope_theta", 10000.0)),
        norm_eps=hf_config.rms_norm_eps, dtype="float32",
        rope_scaling=_canon_rope_scaling(hf_config),
        mla=True,
        q_lora_rank=getattr(hf_config, "q_lora_rank", None),
        kv_lora_rank=hf_config.kv_lora_rank,
        qk_nope_head_dim=hf_config.qk_nope_head_dim,
        qk_rope_head_dim=hf_config.qk_rope_head_dim,
        v_head_dim=hf_config.v_head_dim,
        rope_interleave=bool(getattr(hf_config, "rope_interleave", True)),
        n_experts=hf_config.n_routed_experts,
        moe_top_k=hf_config.num_experts_per_tok,
        moe_ffn_size=hf_config.moe_intermediate_size,
        moe_shared_size=shared * hf_config.moe_intermediate_size,
        moe_score_func="sigmoid",
        moe_route_norm=bool(hf_config.norm_topk_prob),
        moe_route_scale=float(getattr(hf_config, "routed_scaling_factor", 1.0)),
        moe_gate_bias=True,
        moe_n_group=int(getattr(hf_config, "n_group", 1) or 1),
        moe_topk_group=int(getattr(hf_config, "topk_group", 1) or 1),
        moe_aux_coef=float(getattr(hf_config, "router_aux_loss_coef", 0.001)),
        # the published model has no capacity: never the GShard einsums,
        # which "auto" falls back to where a batch does not divide the mesh
        # and which then drop rows (without this line the parity tests
        # test_hf_import.py::test_first_k_dense_matches_hf and
        # test_latent_moe_serving.py::test_forward_matches_the_reference
        # fail on an eight-device mesh)
        moe_dispatch="ragged",
        first_dense_layers=min(first_dense, hf_config.num_hidden_layers))


def config_from_deepseek_v2(hf_config) -> TransformerConfig:
    """DeepSeek-V2/V2-Lite: same MLA as V3; softmax greedy routing,
    non-interleaved rope, no gate bias. Derives from the V3 mapping and
    overrides the family differences (codebase convention: qwen variants
    derive from config_from_llama the same way)."""
    scoring = getattr(hf_config, "scoring_func", "softmax") or "softmax"
    if scoring != "softmax":
        raise NotImplementedError(
            f"deepseek_v2 scoring_func={scoring!r}: the V2 importer maps "
            "softmax routing; sigmoid-scored configs belong to the "
            "deepseek_v3 importer")
    method = getattr(hf_config, "topk_method", "greedy")
    if method != "greedy":
        raise NotImplementedError(
            f"deepseek_v2 topk_method={method!r}: only 'greedy' routing is "
            "supported (the group-limited variant scores groups by max, "
            "unlike V3's top-2 sum)")
    cfg = config_from_deepseek_v3(hf_config)
    return dataclasses.replace(
        cfg, rope_interleave=False, moe_score_func="softmax",
        moe_gate_bias=False, moe_n_group=1, moe_topk_group=1)


def params_from_deepseek(sd: Dict[str, Any], cfg: TransformerConfig) -> PyTree:
    """Shared V2/V3 weight mapping (V3 adds gate.e_score_correction_bias);
    the leading dense layers (``first_k_dense_replace``) stack under
    ``dense_blocks``, the expert layers under ``blocks``."""
    E = cfg.n_experts
    pre = "model." if any(k.startswith("model.") for k in sd) else ""
    lyr = pre + "layers.{}."
    attn = lyr + "self_attn."
    moe = lyr + "mlp."

    def stack_of(L, experts: bool):
        blocks = {
            "ln1": {"scale": _stack(sd, lyr + "input_layernorm.weight", L)},
            "ln2": {"scale": _stack(
                sd, lyr + "post_attention_layernorm.weight", L)},
            "wkv_a": _stack(sd, attn + "kv_a_proj_with_mqa.weight", L,
                            transpose=True),
            "kv_a_norm": _stack(sd, attn + "kv_a_layernorm.weight", L),
            "wkv_b": _stack(sd, attn + "kv_b_proj.weight", L, transpose=True),
            "wo": _stack(sd, attn + "o_proj.weight", L, transpose=True),
        }
        if cfg.q_lora_rank:
            blocks["wq_a"] = _stack(sd, attn + "q_a_proj.weight", L,
                                    transpose=True)
            blocks["q_a_norm"] = _stack(sd, attn + "q_a_layernorm.weight", L)
            blocks["wq_b"] = _stack(sd, attn + "q_b_proj.weight", L,
                                    transpose=True)
        else:
            blocks["wq"] = _stack(sd, attn + "q_proj.weight", L,
                                  transpose=True)
        if not experts:
            for ours, theirs in (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                                 ("w_down", "down_proj")):
                blocks[ours] = _stack(sd, moe + theirs + ".weight", L,
                                      transpose=True)
            return blocks
        blocks["gate_w"] = _stack(sd, moe + "gate.weight", L, transpose=True)
        if cfg.moe_gate_bias:
            blocks["gate_bias"] = _stack(
                sd, moe + "gate.e_score_correction_bias", L)
        if cfg.moe_shared_size > 0:
            for ours, theirs in (("sw_gate", "gate_proj"), ("sw_up", "up_proj"),
                                 ("sw_down", "down_proj")):
                blocks[ours] = _stack(
                    sd, moe + f"shared_experts.{theirs}.weight", L,
                    transpose=True)
        blocks.update(_qwen_moe_experts(sd, moe, L, E))
        return blocks

    d = cfg.first_dense_layers
    params = {
        "tok_emb": _np(sd[pre + "embed_tokens.weight"]),
        "blocks": stack_of(range(d, cfg.num_layers), True),
        "final_norm": {"scale": _np(sd[pre + "norm.weight"])},
    }
    if d:
        params["dense_blocks"] = stack_of(range(d), False)
    if not cfg.tie_embeddings:
        params["lm_head"] = _np(sd["lm_head.weight"]).T
    return params



# --------------------------------------------------------------------------- #
# Phi (phi-1/1.5/2: parallel block, shared norm, partial rotary, biased head)
# --------------------------------------------------------------------------- #

def config_from_phi(hf_config) -> TransformerConfig:
    head_dim = hf_config.hidden_size // hf_config.num_attention_heads
    return TransformerConfig(
        rope_scaling=_canon_rope_scaling(hf_config),
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.hidden_size,
        num_layers=hf_config.num_hidden_layers,
        num_heads=hf_config.num_attention_heads,
        num_kv_heads=getattr(hf_config, "num_key_value_heads", None),
        ffn_hidden_size=hf_config.intermediate_size,
        max_seq_len=hf_config.max_position_embeddings,
        pos_emb="rope", norm="layernorm", activation="gelu",
        use_bias=True, parallel_block=True, shared_parallel_norm=True,
        rope_fraction=float(getattr(hf_config, "partial_rotary_factor", 0.5)),
        rope_theta=float(getattr(hf_config, "rope_theta", 10000.0)),
        tie_embeddings=False, lm_head_bias=True,
        norm_eps=hf_config.layer_norm_eps, dtype="float32")


def params_from_phi(sd: Dict[str, Any], cfg: TransformerConfig) -> PyTree:
    L = cfg.num_layers
    pre = "model." if any(k.startswith("model.") for k in sd) else ""
    lyr = pre + "layers.{}."
    blocks = {
        "ln1": {"scale": _stack(sd, lyr + "input_layernorm.weight", L),
                "bias": _stack(sd, lyr + "input_layernorm.bias", L)},
        "wq": _stack(sd, lyr + "self_attn.q_proj.weight", L, transpose=True),
        "wk": _stack(sd, lyr + "self_attn.k_proj.weight", L, transpose=True),
        "wv": _stack(sd, lyr + "self_attn.v_proj.weight", L, transpose=True),
        "bq": _stack(sd, lyr + "self_attn.q_proj.bias", L),
        "bk": _stack(sd, lyr + "self_attn.k_proj.bias", L),
        "bv": _stack(sd, lyr + "self_attn.v_proj.bias", L),
        "wo": _stack(sd, lyr + "self_attn.dense.weight", L, transpose=True),
        "bo": _stack(sd, lyr + "self_attn.dense.bias", L),
        "w_up": _stack(sd, lyr + "mlp.fc1.weight", L, transpose=True),
        "b_up": _stack(sd, lyr + "mlp.fc1.bias", L),
        "w_down": _stack(sd, lyr + "mlp.fc2.weight", L, transpose=True),
        "b_down": _stack(sd, lyr + "mlp.fc2.bias", L),
    }
    return {
        "tok_emb": _np(sd[pre + "embed_tokens.weight"]),
        "blocks": blocks,
        "final_norm": {"scale": _np(sd[pre + "final_layernorm.weight"]),
                       "bias": _np(sd[pre + "final_layernorm.bias"])},
        "lm_head": _np(sd["lm_head.weight"]).T,
        "lm_head_b": _np(sd["lm_head.bias"]),
    }


# --------------------------------------------------------------------------- #
# Phi-3 (Llama schema with fused qkv_proj / gate_up_proj)
# --------------------------------------------------------------------------- #

def config_from_phi3(hf_config) -> TransformerConfig:
    return TransformerConfig(
        rope_scaling=_canon_rope_scaling(hf_config),
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.hidden_size,
        num_layers=hf_config.num_hidden_layers,
        num_heads=hf_config.num_attention_heads,
        num_kv_heads=getattr(hf_config, "num_key_value_heads", None),
        ffn_hidden_size=hf_config.intermediate_size,
        max_seq_len=hf_config.max_position_embeddings,
        pos_emb="rope", norm="rmsnorm", activation="swiglu", use_bias=False,
        rope_theta=float(getattr(hf_config, "rope_theta", 10000.0)),
        tie_embeddings=bool(getattr(hf_config, "tie_word_embeddings", False)),
        norm_eps=hf_config.rms_norm_eps, dtype="float32")


def params_from_phi3(sd: Dict[str, Any], cfg: TransformerConfig) -> PyTree:
    L = cfg.num_layers
    pre = "model." if any(k.startswith("model.") for k in sd) else ""
    lyr = pre + "layers.{}."
    qdim = cfg.num_heads * cfg.head_dim
    kvdim = cfg.kv_heads * cfg.head_dim
    f = cfg.ffn_size

    qkv = _stack(sd, lyr + "self_attn.qkv_proj.weight", L, transpose=True)
    gate_up = _stack(sd, lyr + "mlp.gate_up_proj.weight", L, transpose=True)
    blocks = {
        "ln1": {"scale": _stack(sd, lyr + "input_layernorm.weight", L)},
        "ln2": {"scale": _stack(sd, lyr + "post_attention_layernorm.weight", L)},
        "wq": qkv[:, :, :qdim],
        "wk": qkv[:, :, qdim:qdim + kvdim],
        "wv": qkv[:, :, qdim + kvdim:],
        "wo": _stack(sd, lyr + "self_attn.o_proj.weight", L, transpose=True),
        "w_gate": gate_up[:, :, :f],
        "w_up": gate_up[:, :, f:],
        "w_down": _stack(sd, lyr + "mlp.down_proj.weight", L, transpose=True),
    }
    params = {
        "tok_emb": _np(sd[pre + "embed_tokens.weight"]),
        "blocks": blocks,
        "final_norm": {"scale": _np(sd[pre + "norm.weight"])},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _np(sd["lm_head.weight"]).T
    return params


# --------------------------------------------------------------------------- #
# Falcon (fused grouped QKV, parallel block; 7B = MQA + shared norm)
# --------------------------------------------------------------------------- #

def config_from_falcon(hf_config) -> TransformerConfig:
    n_head = hf_config.num_attention_heads
    if getattr(hf_config, "new_decoder_architecture", False):
        n_kv = hf_config.num_kv_heads
        parallel, shared = True, False   # ln_attn + ln_mlp (dual parallel norms)
    else:
        n_kv = 1 if getattr(hf_config, "multi_query", True) else n_head
        # parallel_attn=True → one norm feeds both branches; False → a plain
        # sequential block (falcon-rw)
        parallel = bool(getattr(hf_config, "parallel_attn", True))
        shared = parallel
    return TransformerConfig(
        rope_scaling=_canon_rope_scaling(hf_config),
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.hidden_size,
        num_layers=hf_config.num_hidden_layers,
        num_heads=n_head,
        num_kv_heads=n_kv,
        max_seq_len=getattr(hf_config, "max_position_embeddings", 2048),
        pos_emb="alibi" if getattr(hf_config, "alibi", False) else "rope",
        # HF Falcon adds the alibi tensor with beta=inv_norm_factor — the bias
        # rides inside the 1/sqrt(d) scaling (unlike BLOOM's beta=1)
        alibi_bias_scale=1.0 / (hf_config.hidden_size
                                // hf_config.num_attention_heads) ** 0.5,
        norm="layernorm", activation="gelu",
        use_bias=bool(getattr(hf_config, "bias", False)),
        parallel_block=parallel, shared_parallel_norm=shared,
        rope_theta=float(getattr(hf_config, "rope_theta", 10000.0)),
        tie_embeddings=True,
        norm_eps=hf_config.layer_norm_epsilon, dtype="float32")


def _split_falcon_qkv(w: np.ndarray, cfg: TransformerConfig):
    """Falcon fused query_key_value [out, in] → wq/wk/wv in [in, out] layout.

    Rows are grouped as [n_kv groups × (q_per_group q-heads, 1 k, 1 v)]."""
    h, d = cfg.hidden_size, cfg.head_dim
    n_kv = cfg.kv_heads
    q_per = cfg.num_heads // n_kv
    grouped = w.reshape(n_kv, (q_per + 2) * d, h)
    q = grouped[:, : q_per * d].reshape(n_kv * q_per * d, h)
    k = grouped[:, q_per * d: (q_per + 1) * d].reshape(n_kv * d, h)
    v = grouped[:, (q_per + 1) * d:].reshape(n_kv * d, h)
    return q.T, k.T, v.T


def params_from_falcon(sd: Dict[str, Any], cfg: TransformerConfig) -> PyTree:
    L = cfg.num_layers
    pre = "transformer." if any(k.startswith("transformer.") for k in sd) else ""
    lyr = pre + "h.{}."

    wq, wk, wv = [], [], []
    for i in range(L):
        q, k, v = _split_falcon_qkv(
            _np(sd[lyr.format(i) + "self_attention.query_key_value.weight"]), cfg)
        wq.append(q); wk.append(k); wv.append(v)

    if cfg.parallel_block and not cfg.shared_parallel_norm:
        # new decoder architecture: dual parallel norms
        blocks = {
            "ln1": {"scale": _stack(sd, lyr + "ln_attn.weight", L),
                    "bias": _stack(sd, lyr + "ln_attn.bias", L)},
            "ln2": {"scale": _stack(sd, lyr + "ln_mlp.weight", L),
                    "bias": _stack(sd, lyr + "ln_mlp.bias", L)},
        }
    elif cfg.parallel_block:
        # old arch, parallel_attn: one norm feeds both branches
        blocks = {"ln1": {"scale": _stack(sd, lyr + "input_layernorm.weight", L),
                          "bias": _stack(sd, lyr + "input_layernorm.bias", L)}}
    else:
        # falcon-rw: plain sequential block
        blocks = {
            "ln1": {"scale": _stack(sd, lyr + "input_layernorm.weight", L),
                    "bias": _stack(sd, lyr + "input_layernorm.bias", L)},
            "ln2": {"scale": _stack(sd, lyr + "post_attention_layernorm.weight", L),
                    "bias": _stack(sd, lyr + "post_attention_layernorm.bias", L)},
        }
    blocks.update({
        "wq": np.stack(wq), "wk": np.stack(wk), "wv": np.stack(wv),
        "wo": _stack(sd, lyr + "self_attention.dense.weight", L, transpose=True),
        "w_up": _stack(sd, lyr + "mlp.dense_h_to_4h.weight", L, transpose=True),
        "w_down": _stack(sd, lyr + "mlp.dense_4h_to_h.weight", L, transpose=True),
    })
    return {
        "tok_emb": _np(sd[pre + "word_embeddings.weight"]),
        "blocks": blocks,
        "final_norm": {"scale": _np(sd[pre + "ln_f.weight"]),
                       "bias": _np(sd[pre + "ln_f.bias"])},
    }


# --------------------------------------------------------------------------- #
# OPT (learned positions with offset 2, ReLU)
# --------------------------------------------------------------------------- #

def config_from_opt(hf_config) -> TransformerConfig:
    if hf_config.word_embed_proj_dim != hf_config.hidden_size:
        raise ValueError("OPT word_embed_proj_dim != hidden_size (350m-style "
                         "projection) is not supported")
    if not getattr(hf_config, "do_layer_norm_before", True):
        raise ValueError("OPT with do_layer_norm_before=False is not supported")
    return TransformerConfig(
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.hidden_size,
        num_layers=hf_config.num_hidden_layers,
        num_heads=hf_config.num_attention_heads,
        ffn_hidden_size=hf_config.ffn_dim,
        max_seq_len=hf_config.max_position_embeddings,
        pos_emb="learned", norm="layernorm",
        activation="relu" if hf_config.activation_function == "relu" else "gelu",
        use_bias=True, tie_embeddings=True,
        norm_eps=1e-5, dtype="float32")


def params_from_opt(sd: Dict[str, Any], cfg: TransformerConfig) -> PyTree:
    L = cfg.num_layers
    pre = "model.decoder." if any(k.startswith("model.decoder.") for k in sd) \
        else "decoder." if any(k.startswith("decoder.") for k in sd) else ""
    lyr = pre + "layers.{}."
    blocks = {
        "ln1": {"scale": _stack(sd, lyr + "self_attn_layer_norm.weight", L),
                "bias": _stack(sd, lyr + "self_attn_layer_norm.bias", L)},
        "ln2": {"scale": _stack(sd, lyr + "final_layer_norm.weight", L),
                "bias": _stack(sd, lyr + "final_layer_norm.bias", L)},
        "wq": _stack(sd, lyr + "self_attn.q_proj.weight", L, transpose=True),
        "wk": _stack(sd, lyr + "self_attn.k_proj.weight", L, transpose=True),
        "wv": _stack(sd, lyr + "self_attn.v_proj.weight", L, transpose=True),
        "bq": _stack(sd, lyr + "self_attn.q_proj.bias", L),
        "bk": _stack(sd, lyr + "self_attn.k_proj.bias", L),
        "bv": _stack(sd, lyr + "self_attn.v_proj.bias", L),
        "wo": _stack(sd, lyr + "self_attn.out_proj.weight", L, transpose=True),
        "bo": _stack(sd, lyr + "self_attn.out_proj.bias", L),
        "w_up": _stack(sd, lyr + "fc1.weight", L, transpose=True),
        "b_up": _stack(sd, lyr + "fc1.bias", L),
        "w_down": _stack(sd, lyr + "fc2.weight", L, transpose=True),
        "b_down": _stack(sd, lyr + "fc2.bias", L),
    }
    return {
        "tok_emb": _np(sd[pre + "embed_tokens.weight"]),
        # HF OPT offsets positions by 2 (pad-token legacy) — drop those rows
        "pos_emb": _np(sd[pre + "embed_positions.weight"])[2:],
        "blocks": blocks,
        "final_norm": {"scale": _np(sd[pre + "final_layer_norm.weight"]),
                       "bias": _np(sd[pre + "final_layer_norm.bias"])},
    }


# --------------------------------------------------------------------------- #
# BLOOM (ALiBi, embedding layernorm, per-head-interleaved fused QKV)
# --------------------------------------------------------------------------- #

def config_from_bloom(hf_config) -> TransformerConfig:
    return TransformerConfig(
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.hidden_size,
        num_layers=hf_config.n_layer,
        num_heads=hf_config.n_head,
        max_seq_len=getattr(hf_config, "seq_length", 2048),
        pos_emb="alibi", norm="layernorm", activation="gelu",
        use_bias=True, emb_norm=True, tie_embeddings=True,
        norm_eps=hf_config.layer_norm_epsilon, dtype="float32")


def params_from_bloom(sd: Dict[str, Any], cfg: TransformerConfig) -> PyTree:
    L, h, d = cfg.num_layers, cfg.hidden_size, cfg.head_dim
    n = cfg.num_heads
    pre = "transformer." if any(k.startswith("transformer.") for k in sd) else ""
    lyr = pre + "h.{}."

    # fused QKV rows are interleaved per head: [n_head, 3, head_dim, hidden]
    def split_qkv(i):
        w = _np(sd[lyr.format(i) + "self_attention.query_key_value.weight"])
        b = _np(sd[lyr.format(i) + "self_attention.query_key_value.bias"])
        w = w.reshape(n, 3, d, h)
        b = b.reshape(n, 3, d)
        return (w[:, 0].reshape(n * d, h).T, w[:, 1].reshape(n * d, h).T,
                w[:, 2].reshape(n * d, h).T,
                b[:, 0].reshape(-1), b[:, 1].reshape(-1), b[:, 2].reshape(-1))

    parts = [split_qkv(i) for i in range(L)]
    blocks = {
        "ln1": {"scale": _stack(sd, lyr + "input_layernorm.weight", L),
                "bias": _stack(sd, lyr + "input_layernorm.bias", L)},
        "ln2": {"scale": _stack(sd, lyr + "post_attention_layernorm.weight", L),
                "bias": _stack(sd, lyr + "post_attention_layernorm.bias", L)},
        "wq": np.stack([p[0] for p in parts]),
        "wk": np.stack([p[1] for p in parts]),
        "wv": np.stack([p[2] for p in parts]),
        "bq": np.stack([p[3] for p in parts]),
        "bk": np.stack([p[4] for p in parts]),
        "bv": np.stack([p[5] for p in parts]),
        "wo": _stack(sd, lyr + "self_attention.dense.weight", L, transpose=True),
        "bo": _stack(sd, lyr + "self_attention.dense.bias", L),
        "w_up": _stack(sd, lyr + "mlp.dense_h_to_4h.weight", L, transpose=True),
        "b_up": _stack(sd, lyr + "mlp.dense_h_to_4h.bias", L),
        "w_down": _stack(sd, lyr + "mlp.dense_4h_to_h.weight", L, transpose=True),
        "b_down": _stack(sd, lyr + "mlp.dense_4h_to_h.bias", L),
    }
    return {
        "tok_emb": _np(sd[pre + "word_embeddings.weight"]),
        "emb_norm": {"scale": _np(sd[pre + "word_embeddings_layernorm.weight"]),
                     "bias": _np(sd[pre + "word_embeddings_layernorm.bias"])},
        "blocks": blocks,
        "final_norm": {"scale": _np(sd[pre + "ln_f.weight"]),
                       "bias": _np(sd[pre + "ln_f.bias"])},
    }


# --------------------------------------------------------------------------- #
# GPT-NeoX / Pythia (parallel dual-norm block, partial rotary, fused QKV)
# --------------------------------------------------------------------------- #

def config_from_gpt_neox(hf_config) -> TransformerConfig:
    return TransformerConfig(
        rope_scaling=_canon_rope_scaling(hf_config),
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.hidden_size,
        num_layers=hf_config.num_hidden_layers,
        num_heads=hf_config.num_attention_heads,
        ffn_hidden_size=hf_config.intermediate_size,
        max_seq_len=hf_config.max_position_embeddings,
        pos_emb="rope", norm="layernorm", activation="gelu",
        use_bias=True,
        parallel_block=bool(getattr(hf_config, "use_parallel_residual", True)),
        rope_fraction=float(getattr(hf_config, "rotary_pct", 0.25)),
        rope_theta=float(getattr(hf_config, "rotary_emb_base", 10000.0)),
        tie_embeddings=bool(getattr(hf_config, "tie_word_embeddings", False)),
        norm_eps=hf_config.layer_norm_eps, dtype="float32")


def params_from_gpt_neox(sd: Dict[str, Any], cfg: TransformerConfig) -> PyTree:
    L, h, d, n = cfg.num_layers, cfg.hidden_size, cfg.head_dim, cfg.num_heads
    pre = "gpt_neox." if any(k.startswith("gpt_neox.") for k in sd) else ""
    lyr = pre + "layers.{}."

    # fused QKV interleaved per head, like BLOOM: [n_head, 3, head_dim, hidden]
    def split_qkv(i):
        w = _np(sd[lyr.format(i) + "attention.query_key_value.weight"])
        b = _np(sd[lyr.format(i) + "attention.query_key_value.bias"])
        w = w.reshape(n, 3, d, h)
        b = b.reshape(n, 3, d)
        return (w[:, 0].reshape(n * d, h).T, w[:, 1].reshape(n * d, h).T,
                w[:, 2].reshape(n * d, h).T,
                b[:, 0].reshape(-1), b[:, 1].reshape(-1), b[:, 2].reshape(-1))

    parts = [split_qkv(i) for i in range(L)]
    blocks = {
        "ln1": {"scale": _stack(sd, lyr + "input_layernorm.weight", L),
                "bias": _stack(sd, lyr + "input_layernorm.bias", L)},
        "ln2": {"scale": _stack(sd, lyr + "post_attention_layernorm.weight", L),
                "bias": _stack(sd, lyr + "post_attention_layernorm.bias", L)},
        "wq": np.stack([p[0] for p in parts]),
        "wk": np.stack([p[1] for p in parts]),
        "wv": np.stack([p[2] for p in parts]),
        "bq": np.stack([p[3] for p in parts]),
        "bk": np.stack([p[4] for p in parts]),
        "bv": np.stack([p[5] for p in parts]),
        "wo": _stack(sd, lyr + "attention.dense.weight", L, transpose=True),
        "bo": _stack(sd, lyr + "attention.dense.bias", L),
        "w_up": _stack(sd, lyr + "mlp.dense_h_to_4h.weight", L, transpose=True),
        "b_up": _stack(sd, lyr + "mlp.dense_h_to_4h.bias", L),
        "w_down": _stack(sd, lyr + "mlp.dense_4h_to_h.weight", L, transpose=True),
        "b_down": _stack(sd, lyr + "mlp.dense_4h_to_h.bias", L),
    }
    params = {
        "tok_emb": _np(sd[pre + "embed_in.weight"]),
        "blocks": blocks,
        "final_norm": {"scale": _np(sd[pre + "final_layer_norm.weight"]),
                       "bias": _np(sd[pre + "final_layer_norm.bias"])},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _np(sd["embed_out.weight"]).T
    return params


# --------------------------------------------------------------------------- #
# front door
# --------------------------------------------------------------------------- #

def config_from_exaone(hf_config) -> TransformerConfig:
    """EXAONE-3.x (model_type 'exaone'): the Llama recipe under EXAONE's own
    attribute names — alias them and delegate (reference serves the family
    via inference-v2 model_implementations; v4's post-norm block is a
    different architecture and is refused rather than silently
    mis-imported)."""
    from types import SimpleNamespace

    attrs = dict(vars(hf_config))
    attrs["num_hidden_layers"] = getattr(
        hf_config, "num_layers", getattr(hf_config, "num_hidden_layers",
                                         None))
    attrs["rms_norm_eps"] = float(
        getattr(hf_config, "layer_norm_epsilon",
                getattr(hf_config, "rms_norm_eps", 1e-5)))
    return config_from_llama(SimpleNamespace(**attrs))


def params_from_exaone(sd: Dict[str, Any], cfg: TransformerConfig) -> PyTree:
    """Rename EXAONE-3 keys (transformer.h.N.attn.attention.*, mlp.c_fc_0/1,
    ln_1/ln_2, wte) onto the Llama schema and delegate."""
    ren = {
        "transformer.wte.weight": "model.embed_tokens.weight",
        "transformer.ln_f.weight": "model.norm.weight",
        ".ln_1.weight": ".input_layernorm.weight",
        ".ln_2.weight": ".post_attention_layernorm.weight",
        ".attn.attention.q_proj.": ".self_attn.q_proj.",
        ".attn.attention.k_proj.": ".self_attn.k_proj.",
        ".attn.attention.v_proj.": ".self_attn.v_proj.",
        ".attn.attention.out_proj.": ".self_attn.o_proj.",
        ".mlp.c_fc_0.": ".mlp.gate_proj.",
        ".mlp.c_fc_1.": ".mlp.up_proj.",
        ".mlp.c_proj.": ".mlp.down_proj.",
        "transformer.h.": "model.layers.",
    }
    out = {}
    for k, v in sd.items():
        nk = k
        for old, new in ren.items():
            nk = nk.replace(old, new)
        out[nk] = v
    return params_from_llama(out, cfg)


# --------------------------------------------------------------------------- #
# Phi-4-mini-flash (SambaY: state-space, windowed and shared-cache layers)
# --------------------------------------------------------------------------- #

def phi4flash_layer_kinds(n_layers: int, mb_per_layer: int) -> tuple:
    """The kind of every layer of a ``phi4flash`` stack, by index (the
    family's defaults, ``modeling_phi4flash.py``): the first half + 2
    layers are the self-decoder, Mamba on every ``mb_per_layer``-th index
    from 0 and windowed attention between, its last attention layer full
    and the owner of the one growing cache; the cross-decoder after it
    alternates gated memory units (on the Mamba indices) and cross
    attention over that cache."""
    if mb_per_layer != 2 or n_layers % 4 or n_layers < 4:
        raise NotImplementedError(
            f"phi4flash with mb_per_layer={mb_per_layer}, "
            f"num_hidden_layers={n_layers}: layers pair up and the full "
            "layer's index, depth/2 + 1, is odd (mb_per_layer 2, a depth "
            "that is a multiple of 4)")
    full = n_layers // 2 + 1
    return tuple(
        ("mamba" if l % 2 == 0 else "window" if l < full else "full")
        if l <= full else ("gmu" if l % 2 == 0 else "cross")
        for l in range(n_layers))


def config_from_phi4flash(hf_config) -> TransformerConfig:
    h = hf_config.hidden_size
    window = getattr(hf_config, "sliding_window", None)
    if isinstance(window, (list, tuple)):     # per layer in the published
        window = max(w or 0 for w in window)  # file; one width throughout
    inner = int(getattr(hf_config, "mamba_expand", 2)) * h
    rank = getattr(hf_config, "mamba_dt_rank", "auto")
    return TransformerConfig(
        vocab_size=hf_config.vocab_size, hidden_size=h,
        num_layers=hf_config.num_hidden_layers,
        num_heads=hf_config.num_attention_heads,
        num_kv_heads=hf_config.num_key_value_heads,
        ffn_hidden_size=hf_config.intermediate_size,
        max_seq_len=hf_config.max_position_embeddings,
        pos_emb="none", norm="layernorm", activation="swiglu",
        use_bias=bool(getattr(hf_config, "mlp_bias", False)),
        lm_head_bias=bool(getattr(hf_config, "lm_head_bias", False)),
        tie_embeddings=bool(getattr(hf_config, "tie_word_embeddings", True)),
        norm_eps=hf_config.layer_norm_eps, dtype="float32",
        init_std=float(getattr(hf_config, "initializer_range", 0.02)),
        layer_kinds=phi4flash_layer_kinds(
            hf_config.num_hidden_layers,
            int(getattr(hf_config, "mb_per_layer", 2))),
        attn_window=int(window or 0),
        ssm_inner=inner,
        ssm_state=int(getattr(hf_config, "mamba_d_state", 16)),
        ssm_conv=int(getattr(hf_config, "mamba_d_conv", 4)),
        ssm_dt_rank=-(-h // 16) if rank == "auto" else int(rank))


def params_from_phi4flash(sd, cfg):
    raise NotImplementedError(
        "phi4flash: the config importer is written (random-weight runs at "
        "the published shape); the checkpoint's tensor names are not mapped")


# --------------------------------------------------------------------------- #
# AFMoE (Arcee Trinity: window and full attention layers over expert layers)
# --------------------------------------------------------------------------- #

def config_from_afmoe(hf_config) -> TransformerConfig:
    """``model_type`` ``afmoe``: grouped-query attention with per-head q/k
    RMSNorm and an elementwise sigmoid output gate, under four RMSNorms a
    layer (before and after attention, before and after the FFN);
    ``layer_types`` says which layers see ``sliding_window`` positions
    (rotary applied) and which every one (no rotary at all); the first
    ``num_dense_layers`` layers have a dense SwiGLU FFN, the others
    ``num_experts`` routed experts beside ``num_shared_experts`` shared
    ones, under a sigmoid router with a selection bias; ``mup_enabled``
    multiplies the embedding by ``sqrt(hidden_size)``.

    A SHARE of the expert layers (``TransformerConfig.moe_router_experts``):
    ``num_experts`` is then the experts held, ``router_experts`` the
    router's width (the published count) and ``first_expert`` the first
    one held; without ``router_experts`` every expert is held."""
    kinds = tuple({"sliding_attention": "window", "full_attention": "full"}[t]
                  for t in hf_config.layer_types)
    L = hf_config.num_hidden_layers
    if len(kinds) != L:
        raise ValueError(f"afmoe: layer_types names {len(kinds)} layers of "
                         f"num_hidden_layers={L}")
    if int(getattr(hf_config, "n_group", 1) or 1) != 1 \
            or getattr(hf_config, "score_func", "sigmoid") != "sigmoid" \
            or getattr(hf_config, "rope_scaling", None):
        raise NotImplementedError(
            "afmoe: one routing group, sigmoid scores and unscaled rotary "
            "are what is written")
    held = hf_config.num_experts
    router = int(getattr(hf_config, "router_experts", held))
    h = hf_config.hidden_size
    return TransformerConfig(
        vocab_size=hf_config.vocab_size, hidden_size=h, num_layers=L,
        num_heads=hf_config.num_attention_heads,
        num_kv_heads=hf_config.num_key_value_heads,
        attn_head_dim=int(getattr(hf_config, "head_dim",
                                  h // hf_config.num_attention_heads)),
        ffn_hidden_size=hf_config.intermediate_size,
        max_seq_len=hf_config.max_position_embeddings,
        pos_emb="rope", norm="rmsnorm", activation="swiglu", use_bias=False,
        tie_embeddings=bool(getattr(hf_config, "tie_word_embeddings", False)),
        rope_theta=float(getattr(hf_config, "rope_theta", 10000.0)),
        norm_eps=hf_config.rms_norm_eps, dtype="float32",
        qk_norm=True, post_norms=True, attn_gate=True,
        kind_rope=(("full", None),),
        emb_multiplier=float(h) ** 0.5 if getattr(
            hf_config, "mup_enabled", False) else 1.0,
        layer_kinds=kinds, attn_window=int(hf_config.sliding_window),
        n_experts=held, moe_top_k=hf_config.num_experts_per_tok,
        moe_ffn_size=hf_config.moe_intermediate_size,
        moe_shared_size=int(getattr(hf_config, "num_shared_experts", 0) or 0)
        * hf_config.moe_intermediate_size,
        moe_score_func="sigmoid",
        moe_route_norm=bool(getattr(hf_config, "route_norm", True)),
        moe_route_scale=float(getattr(hf_config, "route_scale", 1.0)),
        moe_gate_bias=True, moe_dispatch="ragged",
        moe_router_experts=router if router != held else 0,
        moe_first_expert=int(getattr(hf_config, "first_expert", 0)),
        first_dense_layers=min(int(hf_config.num_dense_layers), L))


def params_from_afmoe(sd: Dict[str, Any], cfg: TransformerConfig) -> PyTree:
    """The family's tensor names (``modeling_afmoe.py``): four norms a
    layer, ``self_attn.{q,k,v,o}_proj`` + ``gate_proj`` (the output gate) +
    ``{q,k}_norm``; ``mlp.{gate,up,down}_proj`` in a dense layer;
    ``mlp.router.gate``, ``mlp.expert_bias``, ``mlp.experts.<e>`` and
    ``mlp.shared_experts`` in an expert layer. A share of the experts
    takes its own from the checkpoint's."""
    pre = "model." if any(k.startswith("model.") for k in sd) else ""
    lyr = pre + "layers.{}."

    def stack_of(layers: range, experts: bool) -> PyTree:
        L = layers
        blocks = {name: {"scale": _stack(sd, lyr + theirs + ".weight", L)}
                  for name, theirs in (
                      ("ln1", "input_layernorm"),
                      ("ln1_post", "post_attention_layernorm"),
                      ("ln2", "pre_mlp_layernorm"),
                      ("ln2_post", "post_mlp_layernorm"))}
        for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"),
                             ("wv", "v_proj"), ("wo", "o_proj"),
                             ("wg", "gate_proj")):
            blocks[ours] = _stack(sd, lyr + f"self_attn.{theirs}.weight", L,
                                  transpose=True)
        blocks["q_norm"] = _stack(sd, lyr + "self_attn.q_norm.weight", L)
        blocks["k_norm"] = _stack(sd, lyr + "self_attn.k_norm.weight", L)
        mlp = lyr + "mlp."
        if not experts:
            for ours, theirs in (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                                 ("w_down", "down_proj")):
                blocks[ours] = _stack(sd, mlp + theirs + ".weight", L,
                                      transpose=True)
            return blocks
        blocks["gate_w"] = _stack(sd, mlp + "router.gate.weight", L,
                                  transpose=True)
        blocks["gate_bias"] = _stack(sd, mlp + "expert_bias", L)
        for ours, theirs in (("sw_gate", "gate_proj"), ("sw_up", "up_proj"),
                             ("sw_down", "down_proj")):
            blocks[ours] = _stack(sd, mlp + f"shared_experts.{theirs}.weight",
                                  L, transpose=True)
        blocks.update(_qwen_moe_experts(sd, mlp, L, cfg.n_experts,
                                        cfg.moe_first_expert))
        return blocks

    d = cfg.first_dense_layers
    params = {
        "tok_emb": _np(sd[pre + "embed_tokens.weight"]),
        "blocks": stack_of(range(d, cfg.num_layers), True),
        "final_norm": {"scale": _np(sd[pre + "norm.weight"])},
    }
    if d:
        params["dense_blocks"] = stack_of(range(d), False)
    if not cfg.tie_embeddings:
        params["lm_head"] = _np(sd["lm_head.weight"]).T
    return params


# --------------------------------------------------------------------------- #
# LFM2-MoE (Liquid: gated short-convolution layers among attention layers,
# over expert layers)
# --------------------------------------------------------------------------- #

def config_from_lfm2_moe(hf_config) -> TransformerConfig:
    """``model_type`` ``lfm2_moe``: every layer ``x += mixer(operator_norm
    x); x += ffn(ffn_norm x)`` under RMSNorm; ``layer_types`` says which
    layers mix with a gated short convolution of ``conv_L_cache`` taps
    (``conv``) and which with grouped-query attention (per-head q/k RMSNorm,
    then rotary over the whole head); the first ``num_dense_layers`` layers
    have a dense SwiGLU FFN of ``intermediate_size``, the others
    ``num_experts`` routed experts of ``moe_intermediate_size`` under a
    sigmoid router with a selection bias, weights normalised by ``sum +
    1e-6``; no bias anywhere, no shared expert, the head tied."""
    kinds = tuple({"conv": "conv", "full_attention": "full"}[t]
                  for t in hf_config.layer_types)
    L = hf_config.num_hidden_layers
    if len(kinds) != L:
        raise ValueError(f"lfm2_moe: layer_types names {len(kinds)} layers "
                         f"of num_hidden_layers={L}")
    rope = dict(getattr(hf_config, "rope_parameters", None) or {})
    if getattr(hf_config, "conv_bias", False) \
            or rope.get("rope_type", "default") != "default":
        raise NotImplementedError(
            "lfm2_moe: a convolution without bias and unscaled rotary are "
            "what is written")
    h = hf_config.hidden_size
    return TransformerConfig(
        vocab_size=hf_config.vocab_size, hidden_size=h, num_layers=L,
        num_heads=hf_config.num_attention_heads,
        num_kv_heads=hf_config.num_key_value_heads,
        ffn_hidden_size=hf_config.intermediate_size,
        max_seq_len=hf_config.max_position_embeddings,
        pos_emb="rope", norm="rmsnorm", activation="swiglu", use_bias=False,
        tie_embeddings=bool(getattr(hf_config, "tie_word_embeddings", True)),
        rope_theta=float(rope.get("rope_theta", getattr(
            hf_config, "rope_theta", 1000000.0))),
        norm_eps=hf_config.norm_eps, dtype="float32", qk_norm=True,
        layer_kinds=kinds, conv_taps=int(hf_config.conv_L_cache),
        n_experts=hf_config.num_experts,
        moe_top_k=hf_config.num_experts_per_tok,
        moe_ffn_size=hf_config.moe_intermediate_size,
        moe_score_func="sigmoid",
        moe_route_norm=bool(getattr(hf_config, "norm_topk_prob", True)),
        moe_route_norm_eps=1e-6,
        moe_route_scale=float(getattr(hf_config, "routed_scaling_factor",
                                      1.0)),
        moe_gate_bias=bool(getattr(hf_config, "use_expert_bias", True)),
        moe_dispatch="ragged",
        first_dense_layers=min(int(hf_config.num_dense_layers), L))


def params_from_lfm2_moe(sd: Dict[str, Any], cfg: TransformerConfig
                         ) -> PyTree:
    """The family's tensor names (``modeling_lfm2_moe.py``):
    ``operator_norm`` / ``ffn_norm``; ``conv.in_proj`` ``[3 H, H]`` (its
    output chunks ``B, C, x`` in that order), ``conv.conv`` ``[H, 1,
    taps]`` (depthwise, the last tap on the row itself), ``conv.out_proj``;
    ``self_attn.{q,k,v}_proj``, ``out_proj``, ``{q,k}_layernorm``;
    ``feed_forward.w1 / w3 / w2`` (gate, up, down) in a dense layer;
    ``feed_forward.gate``, ``expert_bias`` and ``experts.<e>.w1 / w3 / w2``
    in an expert layer; ``embedding_norm`` after the last layer. The
    mixers' leaves are stacked by mixer (``TransformerConfig.
    mixer_layers``)."""
    pre = "model." if any(k.startswith("model.") for k in sd) else ""
    lyr = pre + "layers.{}."
    ff = lyr + "feed_forward."

    def stack_of(layers: range, experts: bool) -> PyTree:
        kinds = cfg.layer_kinds
        conv = [i for i in layers if kinds[i] == "conv"]
        attn = [i for i in layers if kinds[i] != "conv"]
        blocks = {
            "ln1": {"scale": _stack(sd, lyr + "operator_norm.weight",
                                    layers)},
            "ln2": {"scale": _stack(sd, lyr + "ffn_norm.weight", layers)}}
        if conv:
            blocks["conv"] = {
                "w_in": _stack(sd, lyr + "conv.in_proj.weight", conv,
                               transpose=True),
                # [H, 1, taps] -> [taps, H]
                "conv_w": np.stack([_np(sd[(lyr + "conv.conv.weight")
                                           .format(i)])[:, 0, :].T
                                    for i in conv]),
                "wo": _stack(sd, lyr + "conv.out_proj.weight", conv,
                             transpose=True)}
        if attn:
            blocks["attn"] = {
                ours: _stack(sd, lyr + f"self_attn.{theirs}.weight", attn,
                             transpose=True)
                for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"),
                                     ("wv", "v_proj"), ("wo", "out_proj"))}
            blocks["attn"]["q_norm"] = _stack(
                sd, lyr + "self_attn.q_layernorm.weight", attn)
            blocks["attn"]["k_norm"] = _stack(
                sd, lyr + "self_attn.k_layernorm.weight", attn)
        names = (("w_gate", "w1"), ("w_up", "w3"), ("w_down", "w2"))
        if not experts:
            for ours, theirs in names:
                blocks[ours] = _stack(sd, ff + theirs + ".weight", layers,
                                      transpose=True)
            return blocks
        blocks["gate_w"] = _stack(sd, ff + "gate.weight", layers,
                                  transpose=True)
        if cfg.moe_gate_bias:
            blocks["gate_bias"] = _stack(sd, ff + "expert_bias", layers)
        blocks.update(_qwen_moe_experts(
            sd, ff, layers, cfg.n_experts, names=("w1", "w3", "w2")))
        return blocks

    d = cfg.first_dense_layers
    params = {
        "tok_emb": _np(sd[pre + "embed_tokens.weight"]),
        "blocks": stack_of(range(d, cfg.num_layers), True),
        "final_norm": {"scale": _np(sd[pre + "embedding_norm.weight"])},
    }
    if d:
        params["dense_blocks"] = stack_of(range(d), False)
    if not cfg.tie_embeddings:
        params["lm_head"] = _np(sd["lm_head.weight"]).T
    return params


# --------------------------------------------------------------------------- #
# Kimi-Linear (Moonshot: delta-rule linear-attention layers, three to every
# latent-attention layer without rotary, over expert layers)
# --------------------------------------------------------------------------- #

def config_from_kimi_linear(hf_config) -> TransformerConfig:
    """``model_type`` ``kimi_linear``: every layer ``x += mixer(input_
    layernorm x); x += ffn(post_attention_layernorm x)`` under RMSNorm;
    ``linear_attn_config`` lists (from 1) the layers whose mixer is Kimi
    Delta Attention (``kda_layers``: ``num_heads`` heads of ``head_dim``
    behind convolutions of ``short_conv_kernel_size`` taps; the low-rank
    width of its decay and output gates is ``head_dim``, which the config
    does not carry) and those with latent attention (``full_attn_layers``;
    DeepSeek's, direct queries, and NO rotary where ``mla_use_nope``); the
    first ``first_k_dense_replace`` layers have a dense SwiGLU FFN, the
    others ``num_experts`` routed experts beside ``num_shared_experts``
    shared ones under a sigmoid router with a selection bias, one routing
    group. A SHARE of the expert layers as ``afmoe``'s: ``num_experts`` is
    then the experts held, ``router_experts`` the router's width and
    ``first_expert`` the first one held."""
    la = dict(hf_config.linear_attn_config)
    L = hf_config.num_hidden_layers
    kda, full = set(la["kda_layers"]), set(la["full_attn_layers"])
    if kda & full or kda | full != set(range(1, L + 1)):
        raise ValueError(
            "kimi_linear: kda_layers and full_attn_layers name every layer "
            f"1 .. {L} once (got {sorted(kda)} and {sorted(full)})")
    if getattr(hf_config, "q_lora_rank", None) \
            or getattr(hf_config, "rope_scaling", None) \
            or not getattr(hf_config, "mla_use_nope", False) \
            or int(getattr(hf_config, "num_expert_group", 1) or 1) != 1 \
            or int(getattr(hf_config, "moe_layer_freq", 1) or 1) != 1 \
            or getattr(hf_config, "moe_router_activation_func",
                       "sigmoid") != "sigmoid":
        raise NotImplementedError(
            "kimi_linear: direct queries, latent attention without rotary "
            "(mla_use_nope), one routing group, an expert layer every "
            "layer and sigmoid scores are what is written")
    held = hf_config.num_experts
    router = int(getattr(hf_config, "router_experts", held))
    return TransformerConfig(
        vocab_size=hf_config.vocab_size, hidden_size=hf_config.hidden_size,
        num_layers=L, num_heads=hf_config.num_attention_heads,
        ffn_hidden_size=hf_config.intermediate_size,
        max_seq_len=int(getattr(hf_config, "model_max_length", None)
                        or hf_config.max_position_embeddings),
        pos_emb="none", norm="rmsnorm", activation="swiglu", use_bias=False,
        tie_embeddings=bool(getattr(hf_config, "tie_word_embeddings", False)),
        norm_eps=hf_config.rms_norm_eps, dtype="float32",
        mla=True, q_lora_rank=None, kv_lora_rank=hf_config.kv_lora_rank,
        qk_nope_head_dim=hf_config.qk_nope_head_dim,
        qk_rope_head_dim=hf_config.qk_rope_head_dim,
        v_head_dim=hf_config.v_head_dim, rope_interleave=False,
        layer_kinds=tuple("kda" if i in kda else "latent"
                          for i in range(1, L + 1)),
        kda_heads=int(la["num_heads"]), kda_head_dim=int(la["head_dim"]),
        kda_rank=int(la["head_dim"]),
        kda_conv=int(la["short_conv_kernel_size"]),
        n_experts=held, moe_top_k=hf_config.num_experts_per_token,
        moe_ffn_size=hf_config.moe_intermediate_size,
        moe_shared_size=int(getattr(hf_config, "num_shared_experts", 0) or 0)
        * hf_config.moe_intermediate_size,
        moe_score_func="sigmoid",
        moe_route_norm=bool(getattr(hf_config, "moe_renormalize", True)),
        moe_route_scale=float(getattr(hf_config, "routed_scaling_factor",
                                      1.0)),
        moe_gate_bias=True, moe_dispatch="ragged",
        moe_router_experts=router if router != held else 0,
        moe_first_expert=int(getattr(hf_config, "first_expert", 0)),
        first_dense_layers=min(int(getattr(
            hf_config, "first_k_dense_replace", 0) or 0), L))


#: a ``kda`` layer's leaves as the family's modelling code names them
#: (``self_attn.<name>.weight`` but for the two parameters), and whether the
#: tensor is a matrix ``[out, in]``
_KDA_TENSORS = {
    "wq": ("q_proj.weight", True), "wk": ("k_proj.weight", True),
    "wv": ("v_proj.weight", True), "w_fa": ("f_a_proj.weight", True),
    "w_fb": ("f_b_proj.weight", True), "w_b": ("b_proj.weight", True),
    "w_ga": ("g_a_proj.weight", True), "w_gb": ("g_b_proj.weight", True),
    "wo": ("o_proj.weight", True), "o_norm": ("o_norm.weight", False),
    "a_log": ("A_log", False), "dt_bias": ("dt_bias", False)}


def params_from_kimi_linear(sd: Dict[str, Any], cfg: TransformerConfig
                            ) -> PyTree:
    """The family's tensor names: ``input_layernorm`` /
    ``post_attention_layernorm``; in a ``kda`` layer ``self_attn.{q,k,v}_
    proj``, ``{q,k,v}_conv1d.weight`` ``[N D, 1, taps]`` (depthwise, the
    last tap on the row itself), ``f_a_proj`` / ``f_b_proj`` (the decay),
    ``b_proj``, ``g_a_proj`` / ``g_b_proj`` (the output gate), ``A_log``
    (any shape of ``num_heads`` values), ``dt_bias``, ``o_norm``,
    ``o_proj``; in a latent layer DeepSeek's ``q_proj``,
    ``kv_a_proj_with_mqa``, ``kv_a_layernorm``, ``kv_b_proj``, ``o_proj``;
    ``mlp.{gate,up,down}_proj`` in a dense layer; ``block_sparse_moe.gate``
    with ``e_score_correction_bias``, ``experts.<e>.w1 / w3 / w2`` and
    ``shared_experts.{gate,up,down}_proj`` in an expert layer. The mixers'
    leaves are stacked by mixer (``TransformerConfig.mixer_layers``)."""
    pre = "model." if any(k.startswith("model.") for k in sd) else ""
    lyr = pre + "layers.{}."
    attn = lyr + "self_attn."
    moe = lyr + "block_sparse_moe."

    def stack_of(layers: range, experts: bool) -> PyTree:
        kinds = cfg.layer_kinds
        kda = [i for i in layers if kinds[i] == "kda"]
        lat = [i for i in layers if kinds[i] == "latent"]
        blocks = {
            "ln1": {"scale": _stack(sd, lyr + "input_layernorm.weight",
                                    layers)},
            "ln2": {"scale": _stack(
                sd, lyr + "post_attention_layernorm.weight", layers)}}
        if kda:
            blocks["kda"] = {
                ours: _stack(sd, attn + theirs, kda, transpose=matrix)
                for ours, (theirs, matrix) in _KDA_TENSORS.items()}
            blocks["kda"]["a_log"] = blocks["kda"]["a_log"].reshape(
                len(kda), cfg.kda_heads)
            for x in "qkv":       # [N D, 1, taps] -> [taps, N D]
                blocks["kda"][f"conv_{x}"] = np.stack([
                    _np(sd[(attn + f"{x}_conv1d.weight").format(i)])[:, 0].T
                    for i in kda])
        if lat:
            blocks["attn"] = {
                ours: _stack(sd, attn + theirs + ".weight", lat,
                             transpose=True)
                for ours, theirs in (("wq", "q_proj"),
                                     ("wkv_a", "kv_a_proj_with_mqa"),
                                     ("wkv_b", "kv_b_proj"),
                                     ("wo", "o_proj"))}
            blocks["attn"]["kv_a_norm"] = _stack(
                sd, attn + "kv_a_layernorm.weight", lat)
        names = (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                 ("w_down", "down_proj"))
        if not experts:
            for ours, theirs in names:
                blocks[ours] = _stack(sd, lyr + f"mlp.{theirs}.weight",
                                      layers, transpose=True)
            return blocks
        blocks["gate_w"] = _stack(sd, moe + "gate.weight", layers,
                                  transpose=True)
        blocks["gate_bias"] = _stack(
            sd, moe + "gate.e_score_correction_bias", layers)
        if cfg.moe_shared_size > 0:
            for ours, theirs in names:
                blocks["s" + ours] = _stack(
                    sd, moe + f"shared_experts.{theirs}.weight", layers,
                    transpose=True)
        blocks.update(_qwen_moe_experts(
            sd, moe, layers, cfg.n_experts, first=cfg.moe_first_expert,
            names=("w1", "w3", "w2")))
        return blocks

    d = cfg.first_dense_layers
    params = {
        "tok_emb": _np(sd[pre + "embed_tokens.weight"]),
        "blocks": stack_of(range(d, cfg.num_layers), True),
        "final_norm": {"scale": _np(sd[pre + "norm.weight"])},
    }
    if d:
        params["dense_blocks"] = stack_of(range(d), False)
    if not cfg.tie_embeddings:
        params["lm_head"] = _np(sd["lm_head.weight"]).T
    return params


#: a layer's kind by its letter in ``hybrid_override_pattern``
_NEMOTRON_H_KINDS = {"M": "mamba2", "E": "ffn", "*": "full", "-": "ffn"}


def config_from_nemotron_h(hf_config) -> TransformerConfig:
    """``model_type`` ``nemotron_h``: every layer is ONE RMSNorm and ONE
    sublayer, ``x += f(norm x)``, and ``hybrid_override_pattern`` gives a
    letter a layer: ``M`` a Mamba-2 mixer (``mamba_num_heads`` heads of
    ``mamba_head_dim``, ``n_groups`` groups of ``ssm_state_size``, a
    convolution of ``conv_kernel`` taps with a bias, chunks of
    ``chunk_size``), ``*`` grouped-query attention WITHOUT rotary (the
    family applies none: ``rope_theta`` and ``partial_rotary_factor`` are
    keys it does not read), ``E`` an expert layer (``-``: a dense one)
    whose routed experts take a ``moe_latent_size`` latent of the row and
    activate by a squared ReLU, not gated, beside ``n_shared_experts``
    shared experts ``moe_shared_expert_intermediate_size`` wide on the row
    itself, under a sigmoid router with a selection bias and
    ``routed_scaling_factor``. A SHARE of the expert layers as
    ``kimi_linear``'s: ``n_routed_experts`` is then the experts held,
    ``router_experts`` the router's width and ``first_expert`` the first
    one held. The multi-token-prediction module (``num_nextn_predict_
    layers``, ``mtp_hybrid_override_pattern``) is a draft head beside the
    language model and is left out: its tensors are dropped on import."""
    pattern = hf_config.hybrid_override_pattern
    unknown = set(pattern) - set(_NEMOTRON_H_KINDS)
    held = int(getattr(hf_config, "n_routed_experts", 0) or 0)
    if unknown or len(pattern) != hf_config.num_hidden_layers \
            or (held and "-" in pattern):
        raise ValueError(
            "nemotron_h: hybrid_override_pattern names every layer by one "
            f"of {sorted(_NEMOTRON_H_KINDS)} (`-` in a model without routed "
            f"experts only); got {pattern!r} for num_hidden_layers="
            f"{hf_config.num_hidden_layers}")
    nh, p = hf_config.mamba_num_heads, hf_config.mamba_head_dim
    if any(getattr(hf_config, k, False) for k in (
            "attention_bias", "mamba_proj_bias", "mlp_bias", "use_bias")) \
            or nh * p != int(getattr(hf_config, "expand", 2)) \
            * hf_config.hidden_size \
            or getattr(hf_config, "mamba_hidden_act", "silu") != "silu" \
            or getattr(hf_config, "moe_shared_expert_overlap", False) \
            or int(getattr(hf_config, "n_group", 1) or 1) != 1 \
            or int(getattr(hf_config, "topk_group", 1) or 1) != 1 \
            or getattr(hf_config, "sliding_window", None):
        raise NotImplementedError(
            "nemotron_h: no bias but the convolution's, an inner width of "
            "expand x hidden_size, SiLU in the mixer, one routing group and "
            "no window are what is written")
    router = int(getattr(hf_config, "router_experts", held))
    return TransformerConfig(
        vocab_size=hf_config.vocab_size, hidden_size=hf_config.hidden_size,
        num_layers=len(pattern), num_heads=hf_config.num_attention_heads,
        num_kv_heads=hf_config.num_key_value_heads,
        attn_head_dim=int(getattr(hf_config, "head_dim", None)
                          or hf_config.hidden_size
                          // hf_config.num_attention_heads),
        ffn_hidden_size=hf_config.intermediate_size,
        max_seq_len=hf_config.max_position_embeddings,
        pos_emb="none", norm="rmsnorm",
        activation=getattr(hf_config, "mlp_hidden_act", "relu2"),
        use_bias=False,
        tie_embeddings=bool(getattr(hf_config, "tie_word_embeddings", False)),
        norm_eps=float(getattr(hf_config, "layer_norm_epsilon", None)
                       or hf_config.norm_eps), dtype="float32",
        layer_kinds=tuple(_NEMOTRON_H_KINDS[c] for c in pattern),
        mamba2_heads=nh, mamba2_head_dim=p,
        mamba2_groups=hf_config.n_groups,
        mamba2_state=hf_config.ssm_state_size,
        mamba2_conv=hf_config.conv_kernel,
        mamba2_chunk=int(getattr(hf_config, "chunk_size", 128)),
        n_experts=held, moe_top_k=int(getattr(
            hf_config, "num_experts_per_tok", 2)),
        moe_ffn_size=getattr(hf_config, "moe_intermediate_size", None),
        moe_shared_size=int(getattr(hf_config, "n_shared_experts", 0) or 0)
        * int(getattr(hf_config, "moe_shared_expert_intermediate_size", 0)
              or 0),
        moe_latent_size=int(getattr(hf_config, "moe_latent_size", 0) or 0),
        moe_score_func="sigmoid",
        moe_route_norm=bool(getattr(hf_config, "norm_topk_prob", True)),
        moe_route_scale=float(getattr(hf_config, "routed_scaling_factor",
                                      1.0)),
        moe_gate_bias=bool(held), moe_dispatch="ragged",
        moe_router_experts=router if router != held else 0,
        moe_first_expert=int(getattr(hf_config, "first_expert", 0)))


#: a ``mamba2`` layer's leaves as the family's modelling code names them
#: under ``mixer.``, and whether the tensor is a matrix ``[out, in]``
_MAMBA2_TENSORS = {
    "w_in": ("in_proj.weight", True), "wo": ("out_proj.weight", True),
    "conv_b": ("conv1d.bias", False), "dt_bias": ("dt_bias", False),
    "a_log": ("A_log", False), "skip_scale": ("D", False),
    "gate_norm": ("norm.weight", False)}


def _mamba2_leaves(sd: Dict[str, Any], mix: str, layers) -> Dict[str, Any]:
    """The ``mamba2`` layers' leaves, stacked over ``layers``; ``mix``: the
    mixer's prefix with ``{}`` for the layer's index."""
    leaves = {ours: _stack(sd, mix + theirs, layers, transpose=matrix)
              for ours, (theirs, matrix) in _MAMBA2_TENSORS.items()}
    # [channels, 1, taps] -> [taps, channels]
    leaves["conv_w"] = np.stack([
        _np(sd[(mix + "conv1d.weight").format(i)])[:, 0].T for i in layers])
    return leaves


def params_from_nemotron_h(sd: Dict[str, Any], cfg: TransformerConfig
                           ) -> PyTree:
    """The family's tensor names (ASSUMED from its published modelling
    code; no checkpoint is here to confirm them): ``backbone.embeddings``,
    ``backbone.layers.<i>.norm`` and ``.mixer``, ``backbone.norm_f``,
    ``lm_head``. In an ``M`` layer ``mixer.in_proj`` (``[z | xBC | dt]``),
    ``conv1d.weight`` ``[channels, 1, taps]`` (depthwise, the last tap on
    the row itself) and ``.bias``, ``dt_bias``, ``A_log``, ``D``, ``norm``,
    ``out_proj``; in a ``*`` layer ``{q,k,v,o}_proj``; in an ``E`` layer
    ``gate.weight`` with ``gate.e_score_correction_bias``,
    ``experts.<e>.{up,down}_proj``, ``shared_experts.{up,down}_proj`` and
    the latent's ``fc1_latent_proj`` (down) / ``fc2_latent_proj`` (up).
    ``mtp.*`` (the draft head) is dropped. Leaves are stacked by what a
    layer holds (``TransformerConfig.one_sublayer``)."""
    pre = "backbone." if any(k.startswith("backbone.") for k in sd) else ""
    lyr = pre + "layers.{}."
    mix = lyr + "mixer."
    of = {kind: [i for i, k in enumerate(cfg.layer_kinds) if k == kind]
          for kind in ("mamba2", "full", "ffn")}
    blocks: Dict[str, Any] = {"ln1": {"scale": _stack(
        sd, lyr + "norm.weight", range(cfg.num_layers))}}
    if of["mamba2"]:
        blocks["mamba2"] = _mamba2_leaves(sd, mix, of["mamba2"])
    if of["full"]:
        blocks["attn"] = {
            f"w{x}": _stack(sd, mix + f"{x}_proj.weight", of["full"],
                            transpose=True) for x in "qkvo"}
    if of["ffn"] and cfg.n_experts:
        E, first = cfg.n_experts, cfg.moe_first_expert
        ffn = {"gate_w": _stack(sd, mix + "gate.weight", of["ffn"],
                                transpose=True),
               "gate_bias": _stack(
                   sd, mix + "gate.e_score_correction_bias", of["ffn"])}
        for ours, theirs in (("w_up", "up_proj"), ("w_down", "down_proj")):
            ffn[ours] = np.stack([np.stack([
                _np(sd[(mix + f"experts.{e}.{theirs}.weight").format(i)]).T
                for e in range(first, first + E)]) for i in of["ffn"]])
            if cfg.moe_shared_size:
                ffn["s" + ours] = _stack(
                    sd, mix + f"shared_experts.{theirs}.weight", of["ffn"],
                    transpose=True)
        if cfg.moe_latent_size:
            ffn["latent_down"] = _stack(
                sd, mix + "fc1_latent_proj.weight", of["ffn"], transpose=True)
            ffn["latent_up"] = _stack(
                sd, mix + "fc2_latent_proj.weight", of["ffn"], transpose=True)
        blocks["ffn"] = ffn
    elif of["ffn"]:
        blocks["ffn"] = {
            ours: _stack(sd, mix + f"{theirs}.weight", of["ffn"],
                         transpose=True)
            for ours, theirs in (("w_up", "up_proj"), ("w_down", "down_proj"))}
    params = {"tok_emb": _np(sd[pre + "embeddings.weight"]),
              "blocks": blocks,
              "final_norm": {"scale": _np(sd[pre + "norm_f.weight"])}}
    if not cfg.tie_embeddings:
        params["lm_head"] = _np(sd["lm_head.weight"]).T
    return params


# --------------------------------------------------------------------------- #
# Granite 4.0-H (IBM: a Mamba-2 mixer or attention AND experts beside a
# shared MLP in every block, four scalars of a maximal-update parametrisation)
# --------------------------------------------------------------------------- #

_GRANITE_KINDS = {"mamba": "mamba2", "attention": "full"}


def config_from_granitemoehybrid(hf_config) -> TransformerConfig:
    """``model_type`` ``granitemoehybrid``: every layer is a PAIRED block,
    ``x += r * Mixer(norm x)`` then ``x += r * (Experts(u) + Shared(u))`` on
    ``u = norm x``, ``r`` the ``residual_multiplier``. ``layer_types`` names
    a layer's mixer: ``mamba`` a Mamba-2 mixer (``mamba_n_heads`` heads of
    ``mamba_d_head``, ``mamba_n_groups`` groups of ``mamba_d_state``, a
    convolution of ``mamba_d_conv`` taps with a bias, chunks of
    ``mamba_chunk_size``), ``attention`` grouped-query attention whose
    scores' factor is ``attention_multiplier`` and which sees no positions
    (``position_embedding_type`` ``nope``). ``num_local_experts`` SiLU-gated
    experts ``intermediate_size`` wide, ``num_experts_per_tok`` a token by
    the largest router logits, weighted by a softmax over the chosen logits
    (which IS the softmax over all, top-k, renormalised: that is what is
    computed), beside a gated shared MLP ``shared_intermediate_size`` wide
    added without a gate. The embedding is multiplied by
    ``embedding_multiplier`` and the (tied) head's logits divided by
    ``logits_scaling``. A SHARE of the experts as ``nemotron_h``'s:
    ``num_local_experts`` is then the experts held, ``router_experts`` the
    router's width and ``first_expert`` the first one held."""
    kinds = tuple(hf_config.layer_types)
    if set(kinds) - set(_GRANITE_KINDS) \
            or len(kinds) != hf_config.num_hidden_layers:
        raise ValueError(
            "granitemoehybrid: layer_types names every layer `mamba` or "
            f"`attention`; got {kinds!r} for num_hidden_layers="
            f"{hf_config.num_hidden_layers}")
    nh, p = hf_config.mamba_n_heads, hf_config.mamba_d_head
    pos = getattr(hf_config, "position_embedding_type", "nope")
    held = int(getattr(hf_config, "num_local_experts", 0) or 0)
    if getattr(hf_config, "attention_bias", False) \
            or getattr(hf_config, "mamba_proj_bias", False) \
            or nh * p != int(getattr(hf_config, "mamba_expand", 2)) \
            * hf_config.hidden_size \
            or getattr(hf_config, "hidden_act", "silu") != "silu" \
            or getattr(hf_config, "normalization_function",
                       "rmsnorm") != "rmsnorm" \
            or not getattr(hf_config, "mamba_conv_bias", True) \
            or pos != "nope" or not held \
            or not getattr(hf_config, "tie_word_embeddings", True):
        raise NotImplementedError(
            "granitemoehybrid: RMSNorms, no bias but the convolution's, an "
            "inner width of mamba_expand x hidden_size, SiLU, routed experts, "
            "a tied head and position_embedding_type `nope` are what is "
            "written")
    router = int(getattr(hf_config, "router_experts", held))
    return TransformerConfig(
        vocab_size=hf_config.vocab_size, hidden_size=hf_config.hidden_size,
        num_layers=len(kinds), num_heads=hf_config.num_attention_heads,
        num_kv_heads=hf_config.num_key_value_heads,
        max_seq_len=hf_config.max_position_embeddings,
        pos_emb="none", norm="rmsnorm", activation="swiglu", use_bias=False,
        tie_embeddings=True,
        norm_eps=float(hf_config.rms_norm_eps), dtype="float32",
        layer_kinds=tuple(_GRANITE_KINDS[k] for k in kinds),
        mamba2_heads=nh, mamba2_head_dim=p,
        mamba2_groups=hf_config.mamba_n_groups,
        mamba2_state=hf_config.mamba_d_state,
        mamba2_conv=hf_config.mamba_d_conv,
        mamba2_chunk=int(getattr(hf_config, "mamba_chunk_size", 128)),
        emb_multiplier=float(getattr(hf_config, "embedding_multiplier", 1.0)),
        residual_multiplier=float(getattr(hf_config, "residual_multiplier",
                                          1.0)),
        attn_scale=float(getattr(hf_config, "attention_multiplier", 0.0)
                         or 0.0),
        logits_divisor=float(getattr(hf_config, "logits_scaling", 1.0)),
        n_experts=held,
        moe_top_k=int(hf_config.num_experts_per_tok),
        moe_ffn_size=hf_config.intermediate_size,
        moe_shared_size=int(getattr(hf_config, "shared_intermediate_size", 0)
                            or 0),
        moe_score_func="softmax", moe_route_norm=True,
        moe_dispatch="ragged",
        moe_router_experts=router if router != held else 0,
        moe_first_expert=int(getattr(hf_config, "first_expert", 0)))


def params_from_granitemoehybrid(sd: Dict[str, Any], cfg: TransformerConfig
                                 ) -> PyTree:
    """The family's tensor names (ASSUMED from its published modelling
    code; no checkpoint is here to confirm them): ``model.embed_tokens``,
    ``model.layers.<i>.{input_layernorm, post_attention_layernorm}``,
    ``model.norm``, a tied head. A ``mamba`` layer's mixer under ``mamba.``
    as ``nemotron_h``'s under ``mixer.`` (``_MAMBA2_TENSORS``); an
    ``attention`` layer's ``self_attn.{q,k,v,o}_proj``; every layer's
    ``block_sparse_moe.input_linear [E, 2 F, H]`` (an expert's gate rows,
    then its up rows), ``.output_linear [E, H, F]``, ``.router.layer [E,
    H]`` and ``shared_mlp.input_linear [2 Fs, H]`` / ``.output_linear [H,
    Fs]``. The mixers' leaves are stacked by mixer, everything else over
    all layers (``TransformerConfig.mixer_layers``)."""
    pre = "model." if any(k.startswith("model.") for k in sd) else ""
    lyr = pre + "layers.{}."
    L = cfg.num_layers
    E, first, F, Fs = cfg.n_experts, cfg.moe_first_expert, cfg.moe_ffn, \
        cfg.moe_shared_size
    of = {kind: [i for i, k in enumerate(cfg.layer_kinds) if k == kind]
          for kind in ("mamba2", "full")}
    # [L, E, 2 F, H] -> gate and up [L, E, H, F]
    fused = np.stack([_np(sd[(lyr + "block_sparse_moe.input_linear.weight")
                             .format(i)])[first:first + E] for i in range(L)])
    blocks: Dict[str, Any] = {
        "ln1": {"scale": _stack(sd, lyr + "input_layernorm.weight", L)},
        "ln2": {"scale": _stack(sd, lyr + "post_attention_layernorm.weight",
                                L)},
        "gate_w": _stack(sd, lyr + "block_sparse_moe.router.layer.weight", L,
                         transpose=True),
        "w_gate": fused[:, :, :F].transpose(0, 1, 3, 2),
        "w_up": fused[:, :, F:].transpose(0, 1, 3, 2),
        "w_down": np.stack([
            _np(sd[(lyr + "block_sparse_moe.output_linear.weight").format(i)]
                )[first:first + E].transpose(0, 2, 1) for i in range(L)])}
    if Fs:
        shared = _stack(sd, lyr + "shared_mlp.input_linear.weight", L,
                        transpose=True)                     # [L, H, 2 Fs]
        blocks.update(sw_gate=shared[..., :Fs], sw_up=shared[..., Fs:],
                      sw_down=_stack(
                          sd, lyr + "shared_mlp.output_linear.weight", L,
                          transpose=True))
    if of["mamba2"]:
        blocks["mamba2"] = _mamba2_leaves(sd, lyr + "mamba.", of["mamba2"])
    if of["full"]:
        blocks["attn"] = {
            f"w{x}": _stack(sd, lyr + f"self_attn.{x}_proj.weight",
                            of["full"], transpose=True) for x in "qkvo"}
    return {"tok_emb": _np(sd[pre + "embed_tokens.weight"]),
            "blocks": blocks,
            "final_norm": {"scale": _np(sd[pre + "norm.weight"])}}


# --------------------------------------------------------------------------- #
# Ouro (ByteDance: a LOOPED language model, the Llama schema run several
# times over the same weights)
# --------------------------------------------------------------------------- #

def config_from_ouro(hf_config) -> TransformerConfig:
    """``model_type`` ``ouro``: the Llama block under four RMSNorms a layer
    (before and after attention, before and after the FFN: the "sandwich"),
    the whole stack run ``total_ut_steps`` times over the same weights with
    the final norm after every pass, and an exit gate (``Linear(hidden,
    1)``) whose cumulative probability, at ``early_exit_threshold``,
    chooses the pass whose state feeds the head
    (``TransformerConfig.loop_passes`` / ``exit_threshold``)."""
    if getattr(hf_config, "rope_scaling", None) \
            or getattr(hf_config, "attention_bias", False) \
            or getattr(hf_config, "hidden_act", "silu") != "silu":
        raise NotImplementedError(
            "ouro: unscaled rotary, no attention bias and a SiLU-gated "
            "feed-forward part are what is written")
    return dataclasses.replace(
        config_from_llama(hf_config), post_norms=True,
        attn_head_dim=getattr(hf_config, "head_dim", None),
        loop_passes=int(hf_config.total_ut_steps),
        exit_threshold=float(getattr(hf_config, "early_exit_threshold",
                                     1.0)))


def params_from_ouro(sd: Dict[str, Any], cfg: TransformerConfig) -> PyTree:
    """The family's tensor names (``modeling_ouro.py``): the Llama
    schema's, a layer's four norms ``input_layernorm`` (before attention),
    ``input_layernorm_2`` (after it), ``post_attention_layernorm`` (before
    the FFN), ``post_attention_layernorm_2`` (after it), and
    ``model.early_exit_gate`` beside ``model.norm``."""
    L = cfg.num_layers
    params = params_from_llama(sd, cfg)
    pre = "model." if any(k.startswith("model.") for k in sd) else ""
    lyr = pre + "layers.{}."
    for ours, theirs in (("ln1_post", "input_layernorm_2"),
                         ("ln2_post", "post_attention_layernorm_2")):
        params["blocks"][ours] = {"scale": _stack(sd, lyr + theirs
                                                  + ".weight", L)}
    params["exit_gate"] = {
        "w": _np(sd[pre + "early_exit_gate.weight"]).T,        # [H, 1]
        "b": _np(sd[pre + "early_exit_gate.bias"])}
    return params


_ARCH_TABLE = {
    "afmoe": (config_from_afmoe, params_from_afmoe),
    "granitemoehybrid": (config_from_granitemoehybrid,
                         params_from_granitemoehybrid),
    "KeyeVL2": (config_from_keye_vl2, params_from_keye_vl2),
    "kimi_linear": (config_from_kimi_linear, params_from_kimi_linear),
    "lfm2_moe": (config_from_lfm2_moe, params_from_lfm2_moe),
    "mellum": (config_from_mellum, params_from_mellum),
    "nemotron_h": (config_from_nemotron_h, params_from_nemotron_h),
    "ouro": (config_from_ouro, params_from_ouro),
    "phi4flash": (config_from_phi4flash, params_from_phi4flash),
    "gpt2": (config_from_gpt2, params_from_gpt2),
    "llama": (config_from_llama, params_from_llama),
    "exaone": (config_from_exaone, params_from_exaone),
    "mistral": (config_from_llama, params_from_llama),
    "mixtral": (config_from_mixtral, params_from_mixtral),
    "qwen2": (config_from_qwen2, params_from_qwen2),
    "qwen3": (config_from_qwen3, params_from_qwen3),
    "qwen2_moe": (config_from_qwen2_moe, params_from_qwen2_moe),
    "qwen3_moe": (config_from_qwen3_moe, params_from_qwen3_moe),
    "deepseek_v2": (config_from_deepseek_v2, params_from_deepseek),
    "deepseek_v3": (config_from_deepseek_v3, params_from_deepseek),
    "phi": (config_from_phi, params_from_phi),
    "phi3": (config_from_phi3, params_from_phi3),
    "falcon": (config_from_falcon, params_from_falcon),
    "opt": (config_from_opt, params_from_opt),
    "bloom": (config_from_bloom, params_from_bloom),
    "gpt_neox": (config_from_gpt_neox, params_from_gpt_neox),
    # qwen-1 etc. share the llama schema under other key names; pass
    # arch='llama' explicitly after renaming, or extend this table.
    # (exaone4 is POST-norm — a different block; not silently importable)
}


def _arch_row(hf_config, arch: Optional[str]):
    arch = arch or getattr(hf_config, "model_type", None)
    if arch not in _ARCH_TABLE:
        raise ValueError(
            f"unsupported HF architecture {arch!r}; "
            f"supported: {sorted(_ARCH_TABLE)}")
    return _ARCH_TABLE[arch]


def config_from_hf(hf_config, arch: Optional[str] = None
                   ) -> TransformerConfig:
    """The zoo config for a ``transformers`` config object alone (no
    weights) — random-weight runs at a published shape."""
    return _arch_row(hf_config, arch)[0](hf_config)


def import_hf_model(model, arch: Optional[str] = None
                    ) -> Tuple[TransformerConfig, PyTree]:
    """Convert a ``transformers`` model (or (state_dict, config) pair) into
    (TransformerConfig, zoo params)."""
    if isinstance(model, tuple):
        sd, hf_config = model
    else:
        sd, hf_config = model.state_dict(), model.config
    cfg_fn, params_fn = _arch_row(hf_config, arch)
    cfg = cfg_fn(hf_config)
    return cfg, params_fn(sd, cfg)
