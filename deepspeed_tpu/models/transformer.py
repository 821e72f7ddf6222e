"""Torch-free transformer model zoo (GPT-2 and Llama families).

Role: the reference ships no model zoo for training (users bring HF/Megatron
models; its test fixtures are ``tests/unit/simple_model.py``), but its inference
engine has per-model implementations (``inference/v2/model_implementations/``).
This framework is torch-free, so the model zoo is first-class: functional JAX
models designed for the compiler —

* **scan over layers**: per-layer params are stacked on a leading 'layers' dim and
  the forward is a ``lax.scan`` → O(1) compile time in depth, natural hook for
  pipeline sharding and per-layer remat;
* **logical sharding axes**: every param carries a tuple of logical axis names
  (`("layers", "embed", "heads")`) consumed by ``parallel/partitioning.py`` — the
  AutoTP analog;
* **pluggable attention**: the attention callable can be swapped for the Pallas
  flash kernel, Ulysses all-to-all attention, or ring attention without touching
  the model.

Numerics: matmuls in the compute dtype (bf16 by default) with fp32 softmax and
fp32 layernorm/rmsnorm accumulation — MXU-friendly per the TPU guide.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import threading
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name as _ckpt_name

PyTree = Any
AttentionFn = Callable[..., jax.Array]

# remat="selective": save ONLY the named expensive-to-recompute intermediates
# (attention output, FFN activation) — residual stream + elementwise recompute
# for free, the attention kernel and the big FFN matmul never re-run in bwd.
# Storage per token per layer ≈ (heads·D + ffn) · 2 bytes, far below "none";
# recompute far below "full".
_SELECTIVE_POLICY = jax.checkpoint_policies.save_only_these_names(
    "attn_out", "ffn_act", "moe_gate")

# "moe_selective": selective + the expert grouped-GEMM intermediates
# (moe_up/moe_act, named in moe.layer.ragged_expert_ffn) — backward then
# re-runs NO ragged dots, trading ~200 MB/layer of bf16 residuals for ~25%
# of the expert FLOPs per step. The right default for MoE models where the
# experts dominate FLOPs; dense models save nothing extra under it.
_MOE_SELECTIVE_POLICY = jax.checkpoint_policies.save_only_these_names(
    "attn_out", "ffn_act", "moe_gate", "moe_up", "moe_act")


def _remat_wrap(body, remat: str):
    """Apply the layer-scan remat policy; unknown names raise (a typo must
    not silently disable remat)."""
    if remat in ("none", None):
        return body
    if remat in ("full", "save_nothing"):
        return jax.checkpoint(body)
    if remat == "dots_saveable":
        return jax.checkpoint(body, policy=jax.checkpoint_policies.dots_saveable)
    if remat == "dots_no_batch":
        # save every WEIGHT-matmul output (qkv/attn-proj/ffn projections —
        # "dots with no batch dims"); bwd then re-runs only norms,
        # elementwise and the attention einsums. Cuts nearly all of
        # remat="full"'s ~25% recompute FLOPs at bf16-activation storage
        # cost, without dots_saveable's fp32 attention-score traffic.
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    if remat in ("attn_block", "ffn_block"):
        # structural sub-block checkpoint — applied INSIDE _block_forward
        # around one sub-block; the scan body itself is not rematted, so
        # the other sub-block's activations are saved by ordinary AD and
        # XLA's scan fusion stays intact (the names-policy selective remat
        # disrupts it)
        return body
    if remat == "selective":
        return jax.checkpoint(body, policy=_SELECTIVE_POLICY)
    if remat == "moe_selective":
        return jax.checkpoint(body, policy=_MOE_SELECTIVE_POLICY)
    if remat == "offload_dots":
        # ActivationCheckpointingConfig.policy="offload_dots": the selective
        # saves live in pinned host memory instead of HBM
        policy = jax.checkpoint_policies.save_and_offload_only_these_names(
            names_which_can_be_saved=["moe_gate"],  # tiny dispatch indices
            names_which_can_be_offloaded=["attn_out", "ffn_act"],
            offload_src="device", offload_dst="pinned_host")
        return jax.checkpoint(body, policy=policy)
    raise ValueError(
        f"unknown remat policy {remat!r}; one of none|full|save_nothing|"
        "dots_saveable|dots_no_batch|selective|moe_selective|offload_dots|"
        "attn_block|ffn_block")


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    num_kv_heads: Optional[int] = None  # None → MHA; < num_heads → GQA
    ffn_hidden_size: Optional[int] = None
    max_seq_len: int = 1024
    pos_emb: str = "learned"            # learned | rope | alibi | none
    norm: str = "layernorm"             # layernorm | rmsnorm
    activation: str = "gelu"            # gelu | swiglu | relu | relu2
    use_bias: bool = True
    qkv_bias: bool = False              # bias on q/k/v only (Qwen2-style)
    parallel_block: bool = False        # attn + FFN in parallel (Falcon/NeoX/Phi)
    shared_parallel_norm: bool = False  # parallel block, ONE norm feeds both
                                        # branches (Falcon new-arch, Phi)
    emb_norm: bool = False              # layernorm after embedding (BLOOM)
    alibi_bias_scale: float = 1.0       # Falcon folds 1/sqrt(d) into the bias
    lm_head_bias: bool = False          # bias on the LM head (Phi)
    rope_fraction: float = 1.0          # partial rotary (NeoX 0.25, Phi-2 0.4)
    tie_embeddings: bool = True
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    init_std: float = 0.02
    dtype: str = "bfloat16"             # compute dtype
    remat: str = "none"   # none | full (= save_nothing) | dots_saveable |
    #                         selective (save attn_out+ffn_act) |
    #                         offload_dots (selective saves live on pinned host)
    causal: bool = True                 # False → bidirectional encoder (BERT)
    # QAT activation quantization (reference compression/basic_layer.py
    # QuantAct): fake-quantize the normed hidden stream feeding each
    # block's linears (STE backward). 0 = off; set via the
    # compression_training "activation_quantization" config section.
    act_quant_bits: int = 0
    # MoE (reference deepspeed/moe/; 0 experts → dense FFN)
    n_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_min_capacity: int = 4
    moe_aux_coef: float = 0.01
    moe_dispatch: str = "auto"  # auto | ragged (dropless) | dense (GShard)
    # MoE routing/arch variants (AutoEP presets: mixtral/qwen-moe/deepseek)
    moe_ffn_size: Optional[int] = None  # routed-expert intermediate (≠ dense ffn)
    moe_shared_size: int = 0            # shared-expert intermediate; 0 = none
    moe_shared_gate: bool = False       # sigmoid gate on shared out (Qwen2-MoE)
    moe_score_func: str = "softmax"     # softmax | sigmoid (DeepSeek-V3)
    moe_route_norm: bool = True         # renormalize top-k weights to sum 1
    moe_route_scale: float = 1.0        # routed_scaling_factor (DeepSeek)
    qk_norm: bool = False               # RMSNorm on q/k head dim (Qwen3)
    attn_head_dim: Optional[int] = None  # explicit head dim (Qwen3 ≠ H/N)
    # MLA — Multi-head Latent Attention (DeepSeek V2/V3): queries and KV are
    # projected through low-rank latents; only the latent c_kv (+ the shared
    # rope key) is cached at decode — the 93%-smaller-KV-cache trick.
    mla: bool = False
    q_lora_rank: Optional[int] = None   # None → direct q projection (V2-lite)
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_interleave: bool = True        # DeepSeek stores rope pairs interleaved
    # HF rope_scaling dict, canonicalized to a sorted tuple of items so the
    # frozen config stays hashable (None = unscaled)
    rope_scaling: Optional[Tuple[Tuple[str, Any], ...]] = None
    # DeepSeek-V3 router extras (moe/gating.py)
    moe_gate_bias: bool = False         # e_score_correction_bias parameter
    moe_n_group: int = 1                # node-limited routing groups
    moe_topk_group: int = 1
    # leading dense layers of an expert model (DeepSeek
    # ``first_k_dense_replace``): the stack is then TWO scans, the dense
    # layers under ``params["dense_blocks"]`` (FFN width ``ffn_size``) and
    # the expert layers under ``params["blocks"]``; see ``segments``
    first_dense_layers: int = 0
    # compute-time QKV fusion: one [H, q+k+v] matmul instead of three (the
    # reference's fused-QKV transformer kernels, csrc/transformer
    # attn_quantizer/transform kernels). Params stay separate (importers,
    # TP axes unchanged); the concat happens per layer inside the step and
    # XLA materializes it once per weight version.
    fuse_qkv: bool = False
    # overlap scheduler (parallel/overlap.py; reference stage3 prefetch +
    # IPG buckets): split the layer scan into this many sequential chunk
    # scans so ZeRO-3 gathers one chunk ahead of compute and each chunk's
    # gradient sync is final mid-backward. 0/1 = single scan (today's
    # program). Numerics are identical either way; the engine sets this
    # from stage3_prefetch_bucket_size / reduce_bucket_size.
    scan_chunks: int = 0
    # layers of more than one kind in one stack (``models/hybrid.py``; the
    # ``phi4flash`` family): the kind of EVERY layer, in order. Layers pair
    # up, runs of equal pairs are one scan each (``segments``). Empty: the
    # homogeneous attention stack above.
    layer_kinds: Tuple[str, ...] = ()
    attn_window: int = 0                # positions a ``window`` layer sees
    ssm_inner: int = 0                  # Mamba / gated-unit inner width
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_dt_rank: int = 0
    # set on a SEGMENT's config only: the kinds of one step of its scan
    period: Tuple[str, ...] = ()
    # the standard attention block with more to it (the ``afmoe`` family):
    # RMSNorms after attention and after the FFN too, before each joins the
    # residual stream (``ln1_post`` / ``ln2_post``); an elementwise sigmoid
    # gate on the attention output, projected from the block's normed
    # input (``wg``), ahead of ``wo``; an embedding multiplier
    post_norms: bool = False
    attn_gate: bool = False
    emb_multiplier: float = 1.0
    # three more scalars of the same parametrisation (the
    # ``granitemoehybrid`` family), each a number of the config and never
    # folded into a weight: ``residual_multiplier`` scales what EACH
    # sublayer (the mixer, then the FFN or experts) adds to the residual
    # stream; ``attn_scale`` is the factor of a grouped-query layer's
    # scores (0: ``head_dim ** -0.5``, :attr:`score_scale`);
    # ``logits_divisor`` divides the head's logits. At their defaults
    # nothing is traced
    residual_multiplier: float = 1.0
    attn_scale: float = 0.0
    logits_divisor: float = 1.0
    # rotary by layer kind, where the kinds of ``layer_kinds`` differ in it
    # (:meth:`rope_of`): ``(kind, None)`` is no rotary on that kind's layers
    # (the ``afmoe`` family's full layers), ``(kind, (theta, rope_scaling))``
    # a table of its own (the ``mellum`` family's full layers: YaRN, whose
    # ``attention_factor`` multiplies that table alone); a kind not named
    # rotates by ``rope_theta`` / ``rope_scaling``
    kind_rope: Tuple[Tuple[str, Any], ...] = ()
    # a SHARE of an expert layer (expert parallelism's unit; the one-chip
    # cut of the ``model-configs`` guide, section 4): the router is
    # ``moe_router_experts`` wide and chooses ``moe_top_k`` of all of them;
    # this program holds the ``n_experts`` contiguous experts from
    # ``moe_first_expert`` and adds their part of the result alone.
    # 0: the router is ``n_experts`` wide and every expert is held
    moe_router_experts: int = 0
    moe_first_expert: int = 0
    # standard deviation the router's columns are DRAWN with
    # (``init_params``); 0: ``init_std``. Adam moves a score by about
    # ``0.8 * sqrt(hidden) * lr`` a step whatever the gradient's size, so
    # how many steps a drawn routing lasts is set by how far apart this
    # draws the scores
    moe_router_init_std: float = 0.0
    # what guards the division that normalises a token's chosen scores: 0
    # divides by ``max(sum, 1e-9)``, a value by ``sum + value`` (the form
    # the ``lfm2_moe`` family publishes, 1e-6)
    moe_route_norm_eps: float = 0.0
    # ``conv`` layers among ``layer_kinds`` of the standard block (the
    # ``lfm2_moe`` family): the mixer is a gated short convolution
    # (``models/hybrid.short_conv``) of this many taps, whose state a
    # sequence is its last ``conv_taps - 1`` inputs, whatever its length
    conv_taps: int = 0
    # ``kda`` layers among ``layer_kinds`` of the standard block (the
    # ``kimi_linear`` family): the mixer is Kimi Delta Attention
    # (``models/hybrid.kda_inputs`` .. ``kda_output``), a gated delta rule
    # over ``kda_heads`` heads of ``kda_head_dim`` keys and values behind
    # three short convolutions of ``kda_conv`` taps, its decay and output
    # gates through a width of ``kda_rank``; a sequence's state is a
    # ``[keys, values]`` matrix a head in float32, whatever its length.
    # Beside them ``latent`` layers: the latent attention ``mla`` says,
    # without rotary where ``pos_emb`` is not ``rope``
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_rank: int = 0
    kda_conv: int = 4
    # ``sparse`` layers among ``layer_kinds`` of the standard block (the
    # ``KeyeVL2`` family; DeepSeek sparse attention on grouped queries): a
    # learned INDEXER of ``index_heads`` query heads and one key head, each
    # ``index_head_dim`` wide, scores every earlier position for a row,
    # ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``, and the row
    # attends to the ``sparse_topk`` positions of the largest score alone
    # (the lower position first among equals; to all while it has no
    # more). A position's index key is cached beside its key and value
    sparse_topk: int = 0
    index_heads: int = 0
    index_head_dim: int = 0
    # ``mamba2`` layers among ``layer_kinds`` of the standard block (the
    # ``nemotron_h`` family): the mixer is Mamba-2's state-space duality
    # (``models/hybrid.mamba2_inputs`` .. ``mamba2_output``): ``mamba2_heads``
    # heads of ``mamba2_head_dim`` channels under ONE decay a head, ``B`` and
    # ``C`` of ``mamba2_state`` values shared by the heads of each of
    # ``mamba2_groups`` groups, behind one convolution of ``mamba2_conv``
    # taps; a sequence's state is a ``[channels, state]`` matrix a head in
    # float32 and the convolution's last inputs. ``mamba2_chunk``: rows of
    # a chunk of the chunked form (``ops/pallas/ssd.py``).
    # Beside them ``ffn`` layers: in a stack that names one, EVERY layer is
    # one norm and one sublayer, ``x += f(ln1 x)`` (:attr:`one_sublayer`):
    # a mixer alone, or (``ffn``) the feed-forward part or the experts alone
    mamba2_heads: int = 0
    mamba2_head_dim: int = 0
    mamba2_groups: int = 1
    mamba2_state: int = 0
    mamba2_conv: int = 4
    mamba2_chunk: int = 128
    # the routed experts take a LATENT of the row, ``moe_latent_size`` wide
    # (leaves ``latent_down [H, l]``, ``latent_up [l, H]``: the experts'
    # matrices are ``[E, l, F]`` / ``[E, F, l]``), their weighted sum goes
    # back up through ``latent_up``; the router and the shared expert take
    # the row itself. 0: the experts take the row
    moe_latent_size: int = 0
    # a LOOPED stack (the ``ouro`` family): the ``num_layers`` layers run
    # ``loop_passes`` times over the SAME leaves, the final norm after every
    # pass (it feeds the next), and an exit gate (``params["exit_gate"]``: a
    # float32 linear map of the normed state to one logit) after each. Every
    # pass always runs; the gates choose which pass's state feeds the head
    # (:func:`exit_pdf`, :func:`chosen_pass`): the first whose cumulative
    # exit probability reaches ``exit_threshold``, else the last. A served
    # pass keeps keys and values of its own: cache layer ``t * num_layers +
    # l`` (``models/paged.cache_kinds``). 1: every layer runs once, no gate
    loop_passes: int = 1
    exit_threshold: float = 1.0

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def head_dim(self) -> int:
        if self.attn_head_dim is not None:
            return self.attn_head_dim
        return self.hidden_size // self.num_heads

    @property
    def score_scale(self) -> float:
        """The factor of a grouped-query layer's scores."""
        return self.attn_scale or self.head_dim ** -0.5

    @property
    def rescaled(self) -> bool:
        """One of the four scalars (the embedding's, the residual stream's,
        the scores', the logits') is off its default."""
        return (self.emb_multiplier, self.residual_multiplier,
                self.attn_scale, self.logits_divisor) != (1.0, 1.0, 0.0, 1.0)

    @property
    def moe_ffn(self) -> int:
        """Routed-expert intermediate size."""
        return self.moe_ffn_size if self.moe_ffn_size is not None else self.ffn_size

    @property
    def ffn_size(self) -> int:
        if self.ffn_hidden_size is not None:
            return self.ffn_hidden_size
        if self.activation == "swiglu":
            # Llama sizing: 2/3 * 4H rounded to multiple of 256
            raw = int(8 * self.hidden_size / 3)
            return 256 * ((raw + 255) // 256)
        return 4 * self.hidden_size

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def attn_bias_enabled(self) -> bool:
        return self.use_bias or self.qkv_bias

    @property
    def rope_scaling_dict(self) -> Optional[Dict[str, Any]]:
        return dict(self.rope_scaling) if self.rope_scaling else None

    def rope_of(self, kind: Optional[str]
                ) -> Optional[Tuple[float, Optional[Dict[str, Any]]]]:
        """``(theta, rope_scaling)`` of the rotary table a layer of ``kind``
        rotates by (``kind_rope``, else the model's own), or None where it
        has no rotary."""
        own = dict(self.kind_rope)
        if kind not in own:
            return self.rope_theta, self.rope_scaling_dict
        if own[kind] is None:
            return None
        theta, scaling = own[kind]
        return float(theta), dict(scaling) if scaling else None

    @property
    def mla_scale_mult(self) -> float:
        """DeepSeek yarn: softmax scale gains mscale(factor, mscale_all_dim)²
        on top of the cos/sin attention factor (HF DeepseekV3Attention)."""
        sc = self.rope_scaling_dict
        if not sc or not self.mla:
            return 1.0
        mall = sc.get("mscale_all_dim", 0)
        factor = float(sc.get("factor", 1.0))
        if mall and factor > 1:
            m = 0.1 * float(mall) * math.log(factor) + 1.0
            return m * m
        return 1.0

    @property
    def rope_dim(self) -> int:
        """Rotary dims (even), = head_dim * rope_fraction."""
        d = int(self.head_dim * self.rope_fraction)
        return d - (d % 2)

    @property
    def has_ln2(self) -> bool:
        return not (self.parallel_block and self.shared_parallel_norm) \
            and not self.one_sublayer

    @property
    def router_experts(self) -> int:
        """Outputs of the router: every expert of the model, held or not."""
        return self.moe_router_experts or self.n_experts

    @property
    def standard_blocks(self) -> bool:
        """``layer_kinds`` over ONE block (``_block_forward``; one parameter
        tree a layer, stacked as a homogeneous model's): a kind then says
        what a layer sees and where its cache lives (``window``: its last
        ``attn_window`` positions, a per-sequence ring; ``full``: every
        position, a block range of its own; ``latent``: every position, a
        range of latent blocks of its own, where ``mla``; ``sparse``: the
        ``sparse_topk`` positions its indexer chooses, a block range of
        keys, values and index keys), and which
        attention it computes is the config's. A ``conv`` or ``kda`` layer
        has the block's norms and its FFN or experts around a mixer of its
        own with a state a sequence slot (:func:`mixer_of`: in a segment
        with such layers the mixers' leaves are stacked by mixer,
        ``blocks["attn"]`` over its attention layers, ``blocks["conv"]``
        over its ``conv`` and ``blocks["kda"]`` over its ``kda`` layers).
        False with kinds: every kind a whole layer of its own
        (``models/hybrid.py``, ``params[key][kind]``)."""
        return bool(self.layer_kinds) and not self.ssm_inner \
            and set(self.layer_kinds) <= {"window", "full", "conv", "kda",
                                          "latent", "sparse", "mamba2",
                                          "ffn"}

    @property
    def one_sublayer(self) -> bool:
        """Every layer of the stack is ONE norm and ONE sublayer, ``x +=
        f(ln1 x)``: a mixer alone (``mamba2``, ``full``, ...) or, kind
        ``ffn``, the feed-forward part or the experts alone (the
        ``nemotron_h`` family). A layer holds no leaves for the half it
        lacks: the mixers' leaves are stacked by mixer and the FFNs' over
        the ``ffn`` layers (``blocks["ffn"]``), ``ln1`` over all."""
        return "ffn" in self.layer_kinds

    @property
    def expert_in(self) -> int:
        """Width of the rows the routed experts take."""
        return self.moe_latent_size or self.hidden_size

    @property
    def mixer_layers(self) -> Dict[str, int]:
        """Layers of each mixer in a stack that has ``conv`` or ``kda``
        layers (the leading dims of ``blocks["attn"]`` / ``blocks["conv"]``
        / ``blocks["kda"]``); empty where every layer attends: one
        parameter tree a layer."""
        own = {m: self.layer_kinds.count(m) for m in MIXERS[1:]}
        if not any(own.values()):
            return {}
        own["attn"] = self.num_layers - sum(own.values())
        return {m: own[m] for m in MIXERS if own[m]}

    @property
    def ffn_layers(self) -> int:
        """Layers that hold a feed-forward part (or experts)."""
        return self.layer_kinds.count("ffn") if self.one_sublayer \
            else self.num_layers

    @property
    def segments(self) -> Tuple[Tuple[str, "TransformerConfig"], ...]:
        """The layer stack as (key under ``params``, config of that run of
        layers) in order: one homogeneous scan each. ``num_layers`` of a
        segment's config is the segment's depth: the steps of its scan.

        A stack of ``layer_kinds`` is cut into pairs of layers and each run
        of equal pairs is a segment whose scan steps a PERIOD (the pair) at
        a time; its parameters are ``params[key][kind]``, stacked by step,
        and its config carries ``period`` and the index of its first layer
        (``first_dense_layers`` is free there: no such stack has experts)."""
        if self.layer_kinds and not self.standard_blocks:
            return self._period_segments()
        kinds = self.layer_kinds
        if kinds and len(kinds) != self.num_layers:
            raise ValueError(f"layer_kinds names {len(kinds)} layers of "
                             f"num_layers={self.num_layers}")
        d = self.first_dense_layers
        if not d:
            return (("blocks", self),)
        if not 0 < d < self.num_layers or self.n_experts == 0:
            raise ValueError(
                f"first_dense_layers={d} needs an expert model with more "
                f"than {d} layers (num_layers={self.num_layers}, "
                f"n_experts={self.n_experts})")
        # a segment of standard blocks keeps the kinds of its own layers
        rest = dataclasses.replace(self, first_dense_layers=0,
                                   num_layers=self.num_layers - d,
                                   layer_kinds=kinds[d:])
        return (("dense_blocks", dataclasses.replace(
            rest, num_layers=d, n_experts=0, layer_kinds=kinds[:d])),
            ("blocks", rest))

    def _period_segments(self):
        kinds, L = self.layer_kinds, self.num_layers
        if len(kinds) != L or L % 2 or self.n_experts or self.mla:
            raise ValueError(
                f"layer_kinds names {len(kinds)} layers of num_layers={L}; "
                "a stack of kinds has an even depth, no experts and no "
                "latent attention")
        pairs = [kinds[i:i + 2] for i in range(0, L, 2)]
        out, i = [], 0
        while i < len(pairs):
            n = 1
            while i + n < len(pairs) and pairs[i + n] == pairs[i]:
                n += 1
            if pairs[i][0] == pairs[i][1]:
                raise ValueError(f"a period of one kind twice: {pairs[i]}")
            out.append(("_".join(pairs[i]) + "_blocks", dataclasses.replace(
                self, layer_kinds=(), period=tuple(pairs[i]), num_layers=n,
                first_dense_layers=2 * i)))
            i += n
        return tuple(out)

    def _sublayer_params(self, active: bool = False) -> int:
        """:meth:`num_params` of a stack with ``mamba2`` layers (every norm
        an RMSNorm's one gain, no bias but a convolution's): of single
        sublayers (:attr:`one_sublayer`), or of paired blocks, whose every
        layer holds a second norm and a feed-forward part behind its
        mixer; ``active``: the parameters a token meets (``moe_top_k`` of
        the routed experts of a layer)."""
        from deepspeed_tpu.models.hybrid import mixer_specs

        h, kinds = self.hidden_size, self.layer_kinds
        qdim, kv = self.num_heads * self.head_dim, \
            self.kv_heads * self.head_dim
        mats = 3 if self.activation == "swiglu" else 2
        if self.n_experts:
            routed = self.moe_top_k if active else self.n_experts
            ffn = routed * mats * self.expert_in * self.moe_ffn \
                + h * self.router_experts \
                + (self.router_experts if self.moe_gate_bias else 0) \
                + mats * h * self.moe_shared_size \
                + (2 * h * self.moe_latent_size)
        else:
            ffn = mats * h * self.ffn_size
        per = {"ffn": ffn, "mamba2": sum(
            math.prod(shape) for shape, _, _ in
            mixer_specs(self, "mamba2").values()) if self.mamba2_heads else 0}
        attn = 2 * h * qdim + 2 * h * kv + (
            2 * self.head_dim if self.qk_norm else 0)
        paired = 0 if self.one_sublayer else h + ffn
        layers = sum(h + per.get(kind, attn) + paired for kind in kinds)
        return layers + h + self.vocab_size * h * (
            1 if self.tie_embeddings else 2)

    def num_params(self) -> int:
        if self.layer_kinds and not self.standard_blocks:
            from deepspeed_tpu.models.hybrid import mixer_specs

            h, f = self.hidden_size, self.ffn_size
            norm = 2 * h if self.norm == "layernorm" else h
            ffn = (3 if self.activation == "swiglu" else 2) * h * f
            if self.use_bias:
                ffn += f + h
            total = self.vocab_size * h * (1 if self.tie_embeddings else 2) \
                + norm
            for kind in self.layer_kinds:
                total += 2 * norm + ffn + sum(
                    math.prod(leaf[0]) for leaf in jax.tree.leaves(
                        mixer_specs(self, kind),
                        is_leaf=lambda x: isinstance(x, tuple)))
            return total
        if self.one_sublayer or "mamba2" in self.layer_kinds:
            return self._sublayer_params()
        if self.first_dense_layers:
            shared = dataclasses.replace(self, first_dense_layers=0,
                                         num_layers=0,
                                         layer_kinds=()).num_params()
            return shared + sum(c.num_params() - shared
                                for _, c in self.segments)
        h, f, v, l = self.hidden_size, self.ffn_size, self.vocab_size, self.num_layers
        if self.mla:
            dn, dr, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                          self.v_head_dim)
            kvr, N = self.kv_lora_rank, self.num_heads
            qout = N * (dn + dr)
            if self.q_lora_rank:
                per_layer = (h * self.q_lora_rank + self.q_lora_rank
                             + self.q_lora_rank * qout)
            else:
                per_layer = h * qout
            per_layer += (h * (kvr + dr) + kvr + kvr * N * (dn + dv)
                          + N * dv * h)
        else:
            kv = self.kv_heads * self.head_dim
            qdim = self.num_heads * self.head_dim
            per_layer = h * qdim + 2 * h * kv + qdim * h  # q, k, v, o
            if self.attn_gate:
                per_layer += h * qdim
        if self.qk_norm:
            per_layer += 2 * self.head_dim
        if "sparse" in self.layer_kinds:
            # the indexer: its queries, its one key under a LayerNorm, the
            # heads' weights
            hi, di = self.index_heads, self.index_head_dim
            per_layer += h * hi * di + h * di + 2 * di + h * hi
        attn = per_layer
        ffn_mats = 3 if self.activation == "swiglu" else 2
        if self.n_experts > 0:
            per_layer += self.n_experts * ffn_mats * h * self.moe_ffn \
                + h * self.router_experts
            per_layer += ffn_mats * h * self.moe_shared_size  # shared expert
            if self.moe_shared_gate:
                per_layer += h
            if self.moe_gate_bias:
                per_layer += self.router_experts
        else:
            per_layer += ffn_mats * h * f
        per_layer += (2 * h if self.has_ln2 else h)  # norms
        if self.post_norms:
            per_layer += 2 * h
        total = l * per_layer + v * h + 2 * h
        # a ``conv`` or ``kda`` layer has its mixer where the others have
        # their attention
        from deepspeed_tpu.models.hybrid import mixer_specs

        total += sum(
            n * (sum(math.prod(shape) for shape, _, _ in
                     mixer_specs(self, kind).values()) - attn)
            for kind, n in self.mixer_layers.items() if kind != "attn")
        if self.emb_norm:
            total += 2 * h
        if not self.tie_embeddings:
            total += v * h
        if self.pos_emb == "learned":
            total += self.max_seq_len * h
        if self.loop_passes > 1:
            # the exit gate and its bias; and the count is the leaves' own
            # here: an RMSNorm's final norm is one gain (the ``2 * h`` above
            # reckons a LayerNorm's two for every model)
            total += h + 1 - (h if self.norm == "rmsnorm" else 0)
        return total


# --------------------------------------------------------------------------- #
# init
# --------------------------------------------------------------------------- #

#: the standard block's attention leaves: in a stack with ``conv`` layers
#: they are ``blocks["attn"]``, stacked over the attention layers alone
_ATTN_LEAVES = ("wq", "wk", "wv", "wo", "q_norm", "k_norm", "wq_a",
                "q_a_norm", "wq_b", "wkv_a", "kv_a_norm", "wkv_b")
#: the feed-forward part's leaves: in a stack of single sublayers they are
#: ``blocks["ffn"]``, stacked over the ``ffn`` layers alone
_FFN_LEAVES = ("gate_w", "gate_bias", "w_up", "w_down", "w_gate", "sw_up",
               "sw_down", "sw_gate", "shared_gate_w", "latent_down",
               "latent_up")
#: the sub-trees of a segment's blocks that are stacked by mixer (the last
#: is no mixer: the feed-forward parts of a stack of single sublayers)
MIXERS = ("attn", "conv", "kda", "mamba2", "ffn")


def mixer_of(kind: str) -> str:
    """The mixer a layer of ``kind`` computes: whose leaves it reads in a
    stack whose mixers' leaves are stacked apart (``ffn``: the layer is a
    feed-forward part alone, ``TransformerConfig.one_sublayer``)."""
    return kind if kind in MIXERS[1:] else "attn"


def _check_kinds_of_blocks(cfg: TransformerConfig) -> None:
    """What a stack of ``layer_kinds`` over the standard block cannot be
    yet, said precisely."""
    kinds = set(cfg.layer_kinds)
    if ("latent" in kinds and not cfg.mla) or (cfg.mla and kinds
                                               & {"window", "full"}):
        raise NotImplementedError(
            "latent attention (mla) among layer_kinds is the kind `latent`, "
            "beside `kda` or `conv` layers: latent and grouped-query "
            "attention layers in one stack, and a latent layer under a "
            f"window, are not written (mla={cfg.mla}, kinds {sorted(kinds)})")
    if "sparse" in kinds and (
            kinds != {"sparse"} or cfg.mla or cfg.pos_emb != "rope"
            or cfg.attn_scale
            or not (cfg.sparse_topk and cfg.index_heads
                    and cfg.index_head_dim)):
        raise NotImplementedError(
            "sparse layers are a whole stack of grouped-query layers under "
            "rotary (every layer holds an indexer's leaves), with "
            "sparse_topk, index_heads and index_head_dim, their scores' "
            "factor head_dim ** -0.5 (got kinds "
            f"{sorted(kinds)}, mla={cfg.mla}, pos_emb={cfg.pos_emb!r}, "
            f"{cfg.sparse_topk}, {cfg.index_heads}, {cfg.index_head_dim}, "
            f"attn_scale={cfg.attn_scale})")
    own = kinds & set(MIXERS[1:])
    if own and (cfg.attn_bias_enabled or cfg.use_bias or cfg.attn_gate
                or cfg.post_norms or cfg.parallel_block):
        raise NotImplementedError(
            f"{' and '.join(sorted(own))} layers stand in a stack of plain "
            "sequential blocks: biases, an output gate on attention, "
            "post-norms and the parallel residual are not written beside "
            "them")
    if cfg.one_sublayer and (cfg.first_dense_layers or cfg.mla
                             or kinds - {"ffn", "mamba2", "full", "window"}):
        raise NotImplementedError(
            "a stack that names `ffn` layers is one of single sublayers (a "
            "mixer OR a feed-forward part a layer): `mamba2`, `full` and "
            "`window` mixers, no leading dense segment, no latent attention "
            f"(got kinds {sorted(kinds)}, first_dense_layers="
            f"{cfg.first_dense_layers}, mla={cfg.mla})")
    if "mamba2" in kinds and (
            kinds - {"mamba2", "full", "window", "ffn"}
            or cfg.first_dense_layers or cfg.mla or cfg.norm != "rmsnorm"):
        raise NotImplementedError(
            "mamba2 layers stand beside `full` and `window` layers under "
            "RMSNorms (alone in a layer beside `ffn` layers, or each layer "
            "a mixer and a feed-forward part), with no leading dense "
            f"segment and no latent attention (got kinds {sorted(kinds)}, "
            f"first_dense_layers={cfg.first_dense_layers}, mla={cfg.mla}, "
            f"norm={cfg.norm!r})")
    if "mamba2" in kinds and not (
            cfg.mamba2_heads and cfg.mamba2_head_dim and cfg.mamba2_state
            and cfg.mamba2_conv >= 2
            and cfg.mamba2_heads % cfg.mamba2_groups == 0):
        raise ValueError(
            "mamba2 layers need mamba2_heads (a multiple of mamba2_groups), "
            "mamba2_head_dim, mamba2_state and mamba2_conv >= 2 (got "
            f"{cfg.mamba2_heads}, {cfg.mamba2_groups}, {cfg.mamba2_head_dim}, "
            f"{cfg.mamba2_state}, {cfg.mamba2_conv})")
    if "conv" in kinds and cfg.conv_taps < 2:
        raise NotImplementedError(
            f"conv layers need conv_taps >= 2 (got {cfg.conv_taps})")
    if "kda" in kinds and not (cfg.kda_heads and cfg.kda_head_dim
                               and cfg.kda_rank and cfg.kda_conv >= 2):
        raise ValueError(
            "kda layers need kda_heads, kda_head_dim, kda_rank and "
            f"kda_conv >= 2 (got {cfg.kda_heads}, {cfg.kda_head_dim}, "
            f"{cfg.kda_rank}, {cfg.kda_conv})")


def _check_loop(cfg: TransformerConfig, what: Optional[str] = None) -> None:
    """What a looped stack (``loop_passes`` > 1) cannot be yet, said
    precisely; ``what``: a path that assumes ONE application a layer and
    refuses every looped config by name."""
    R = cfg.loop_passes
    if R == 1:
        return
    if R < 1 or not 0.0 <= cfg.exit_threshold <= 1.0:
        raise ValueError(
            f"loop_passes={R}, exit_threshold={cfg.exit_threshold}: a stack "
            "runs one pass or more and exits at a cumulative probability "
            "in [0, 1]")
    if what is not None:
        raise NotImplementedError(
            f"{what} applies each layer's weights once a token; a looped "
            f"stack (loop_passes={R}) is run whole by forward() and served "
            "by FastGenEngine, whose pool keeps a cache layer a (pass, "
            "layer)")
    if cfg.layer_kinds or cfg.first_dense_layers or cfg.mla \
            or cfg.n_experts:
        raise NotImplementedError(
            f"a looped stack (loop_passes={R}) is one homogeneous stack of "
            "grouped-query attention blocks with dense feed-forward parts: "
            "layer kinds, leading dense layers, latent attention and "
            "experts are not written under a loop (got layer_kinds="
            f"{bool(cfg.layer_kinds)}, first_dense_layers="
            f"{cfg.first_dense_layers}, mla={cfg.mla}, n_experts="
            f"{cfg.n_experts})")


def pass_scope(cfg: TransformerConfig, t: int):
    """The scope a looped stack's pass ``t`` runs under (``pass<t>``: a
    trace tells pass 0's attention from pass 3's by its path); nothing for
    a stack that runs once."""
    return jax.named_scope(f"pass{t}") if cfg.loop_passes > 1 \
        else contextlib.nullcontext()


def loop_norm(x: jax.Array, params: PyTree, cfg: TransformerConfig
              ) -> jax.Array:
    """The final norm as a looped stack applies it after EVERY pass (under
    the scope ``loop_norm``: the normed state feeds the next pass, the
    exit gate and, if chosen, the head, which norms nothing again)."""
    with jax.named_scope("loop_norm"):
        return _norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)


def exit_pdf(gate_logits: jax.Array) -> jax.Array:
    """The exit distribution of a looped stack from its passes' gate logits
    ``[..., R]`` (float32): ``lambda_t = sigmoid(logit_t)``; ``p_t =
    lambda_t * prod_{s<t} (1 - lambda_s)`` for ``t < R - 1`` and the last
    pass takes what remains, ``p_{R-1} = prod_{s<R-1} (1 - lambda_s)``."""
    lam = jax.nn.sigmoid(gate_logits.astype(jnp.float32))
    stay = jnp.cumprod(1.0 - lam[..., :-1], axis=-1)       # after pass t
    before = jnp.concatenate([jnp.ones_like(lam[..., :1]), stay], axis=-1)
    return jnp.concatenate([lam[..., :-1] * before[..., :-1],
                            before[..., -1:]], axis=-1)


def chosen_pass(pdf: jax.Array, threshold: float) -> jax.Array:
    """The pass whose state feeds the head, ``int32[...]``: the first whose
    cumulative exit probability reaches ``threshold``, else the last."""
    reached = jnp.cumsum(pdf, axis=-1) >= jnp.float32(threshold)
    return jnp.where(reached.any(axis=-1), jnp.argmax(reached, axis=-1),
                     pdf.shape[-1] - 1).astype(jnp.int32)


def loop_exit(params: PyTree, states: jax.Array, cfg: TransformerConfig
              ) -> Tuple[jax.Array, jax.Array]:
    """A looped stack's exit rule on its passes' normed states ``[..., R,
    H]``: (the chosen pass's state ``[..., H]``, the exit distribution
    ``[..., R]`` float32). The gate and the distribution are float32
    whatever the stream's type; the threshold is the config's."""
    with jax.named_scope("exit_gate"):
        gate = params["exit_gate"]
        logits = jnp.einsum(
            "...rh,h->...r", states.astype(jnp.float32),
            gate["w"].astype(jnp.float32)[:, 0],
            precision=lax.Precision.HIGHEST) + gate["b"].astype(jnp.float32)
        pdf = exit_pdf(logits)
        at = chosen_pass(pdf, cfg.exit_threshold)
        chosen = jnp.take_along_axis(
            states, at[..., None, None], axis=-2)[..., 0, :]
    return chosen, pdf


def init_params(cfg: TransformerConfig, rng: jax.Array) -> PyTree:
    """fp32 master parameters. Output projections scaled by 1/sqrt(2L) (GPT-2)."""
    _check_loop(cfg)
    if cfg.layer_kinds and not cfg.standard_blocks:
        return _init_kinds(cfg, rng)
    if cfg.one_sublayer or "mamba2" in cfg.layer_kinds:
        _check_kinds_of_blocks(cfg)
    if cfg.first_dense_layers:
        (dkey, dcfg), (_, rest) = cfg.segments
        params = init_params(rest, rng)
        # the dense layers' blocks alone: no embedding is built for them
        params[dkey] = init_params(
            dataclasses.replace(dcfg, vocab_size=1, tie_embeddings=True,
                                pos_emb="none", emb_norm=False),
            jax.random.fold_in(rng, 1))["blocks"]
        return params
    h, f, L = cfg.hidden_size, cfg.ffn_size, cfg.num_layers
    qdim = cfg.num_heads * cfg.head_dim
    kvdim = cfg.kv_heads * cfg.head_dim
    std = cfg.init_std
    out_std = std / math.sqrt(2 * L)
    keys = jax.random.split(rng, 16)

    def norm_init(shape):
        p = {"scale": jnp.ones(shape, jnp.float32)}
        if cfg.norm == "layernorm":
            p["bias"] = jnp.zeros(shape, jnp.float32)
        return p

    def dense(key, shape, s):
        return jax.random.normal(key, shape, jnp.float32) * s

    block = {"ln1": norm_init((L, h))}
    # in a stack with ``conv`` layers the mixers' leaves are stacked by
    # mixer (``cfg.mixer_layers``): attention's over the attention layers
    mixers = cfg.mixer_layers
    if cfg.layer_kinds:
        _check_kinds_of_blocks(cfg)
    La = mixers.get("attn", 0) if mixers else L
    if cfg.mla:
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        kvr, N = cfg.kv_lora_rank, cfg.num_heads
        qout = N * (dn + dr)
        if cfg.q_lora_rank:
            block["wq_a"] = dense(keys[0], (La, h, cfg.q_lora_rank), std)
            block["q_a_norm"] = jnp.ones((La, cfg.q_lora_rank), jnp.float32)
            block["wq_b"] = dense(keys[15], (La, cfg.q_lora_rank, qout), std)
        else:
            block["wq"] = dense(keys[0], (La, h, qout), std)
        block["wkv_a"] = dense(keys[1], (La, h, kvr + dr), std)
        block["kv_a_norm"] = jnp.ones((La, kvr), jnp.float32)
        block["wkv_b"] = dense(keys[2], (La, kvr, N * (dn + dv)), std)
        block["wo"] = dense(keys[3], (La, N * dv, h), out_std)
    else:
        block.update({
            "wq": dense(keys[0], (La, h, qdim), std),
            "wk": dense(keys[1], (La, h, kvdim), std),
            "wv": dense(keys[2], (La, h, kvdim), std),
            "wo": dense(keys[3], (La, qdim, h), out_std),
        })
        if cfg.attn_gate:
            block["wg"] = dense(jax.random.fold_in(rng, 16), (L, h, qdim),
                                std)
    if cfg.has_ln2:
        block["ln2"] = norm_init((L, h))
    if cfg.post_norms:
        block["ln1_post"] = norm_init((L, h))
        block["ln2_post"] = norm_init((L, h))
    if cfg.qk_norm:
        block["q_norm"] = jnp.ones((La, cfg.head_dim), jnp.float32)
        block["k_norm"] = jnp.ones((La, cfg.head_dim), jnp.float32)
    if "sparse" in cfg.layer_kinds:
        hi, di = cfg.index_heads, cfg.index_head_dim
        block.update({
            "idx_wq": dense(jax.random.fold_in(rng, 18), (L, h, hi * di), std),
            "idx_wk": dense(jax.random.fold_in(rng, 19), (L, h, di), std),
            "idx_ww": dense(jax.random.fold_in(rng, 20), (L, h, hi), std),
            "idx_k_norm": {"scale": jnp.ones((L, di), jnp.float32),
                           "bias": jnp.zeros((L, di), jnp.float32)}})
    E = cfg.n_experts
    # a stack of single sublayers holds a feed-forward part in its ``ffn``
    # layers alone
    Lf = cfg.ffn_layers
    if E > 0:
        # MoE FFN: per-expert weights (no biases), router gate per layer
        fe = cfg.moe_ffn
        R = cfg.router_experts
        gate = dense(keys[10], (Lf, h, R), cfg.moe_router_init_std or std)
        if R > E:
            # a SHARE drawn from scratch is one of EQUAL shares: the columns
            # of the experts held, repeated over the router's width. Equal
            # scores stand together in a row's top-k, so a row sends each
            # share ``moe_top_k * E / R`` of its pairs (exactly, where
            # ``R / E`` divides ``moe_top_k``), as a router trained under
            # its balance term spreads them. With every column a draw of
            # its own the share's work is the draw's: rows at initialisation
            # choose much alike, and a layer of a quarter held 0.2 % to
            # 53 % of the pairs (PERF.md, PR 49). Training unties them.
            gate = jnp.tile(gate[..., :E], (1, 1, -(-R // E)))[..., :R]
        block["gate_w"] = gate
        he = cfg.expert_in
        block["w_up"] = dense(keys[4], (Lf, E, he, fe), std)
        block["w_down"] = dense(keys[5], (Lf, E, fe, he), out_std)
        if cfg.activation == "swiglu":
            block["w_gate"] = dense(keys[6], (Lf, E, he, fe), std)
        if cfg.moe_latent_size:
            block["latent_down"] = dense(jax.random.fold_in(rng, 1021),
                                         (Lf, h, he), std)
            block["latent_up"] = dense(jax.random.fold_in(rng, 1022),
                                       (Lf, he, h), out_std)
        fs = cfg.moe_shared_size
        if fs > 0:
            # always-on shared expert (Qwen2-MoE/DeepSeek)
            block["sw_up"] = dense(keys[11], (Lf, h, fs), std)
            block["sw_down"] = dense(keys[12], (Lf, fs, h), out_std)
            if cfg.activation == "swiglu":
                block["sw_gate"] = dense(keys[13], (Lf, h, fs), std)
            if cfg.moe_shared_gate:
                block["shared_gate_w"] = dense(keys[14], (Lf, h, 1), std)
        if cfg.moe_gate_bias:
            block["gate_bias"] = jnp.zeros((Lf, cfg.router_experts),
                                           jnp.float32)
    else:
        block["w_up"] = dense(keys[4], (Lf, h, f), std)
        block["w_down"] = dense(keys[5], (Lf, f, h), out_std)
        if cfg.activation == "swiglu":
            block["w_gate"] = dense(keys[6], (Lf, h, f), std)
    if cfg.attn_bias_enabled:
        block["bq"] = jnp.zeros((L, qdim), jnp.float32)
        block["bk"] = jnp.zeros((L, kvdim), jnp.float32)
        block["bv"] = jnp.zeros((L, kvdim), jnp.float32)
    if cfg.use_bias:
        block["bo"] = jnp.zeros((L, h), jnp.float32)
        if E == 0:
            block["b_up"] = jnp.zeros((L, f), jnp.float32)
            block["b_down"] = jnp.zeros((L, h), jnp.float32)

    if mixers:
        from deepspeed_tpu.models.hybrid import init_leaf, mixer_specs

        attn = {k: block.pop(k) for k in _ATTN_LEAVES if k in block}
        if La:
            block["attn"] = attn
        if cfg.one_sublayer:
            block["ffn"] = {k: block.pop(k) for k in _FFN_LEAVES
                            if k in block}
        for m, kind in enumerate(k for k in MIXERS[1:-1] if k in mixers):
            block[kind] = {
                name: init_leaf(how, (mixers[kind],) + shape,
                                jax.random.fold_in(rng, 17 + 32 * m + i),
                                std, out_std)
                for i, (name, (shape, _, how)) in enumerate(
                    sorted(mixer_specs(cfg, kind).items()))}

    params = {
        "tok_emb": dense(keys[7], (cfg.vocab_size, h), std),
        "blocks": block,
        "final_norm": norm_init((h,)),
    }
    if cfg.pos_emb == "learned":
        params["pos_emb"] = dense(keys[8], (cfg.max_seq_len, h), std)
    if cfg.emb_norm:
        params["emb_norm"] = norm_init((h,))
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(keys[9], (h, cfg.vocab_size), std)
        if cfg.lm_head_bias:
            params["lm_head_b"] = jnp.zeros((cfg.vocab_size,), jnp.float32)
    if cfg.loop_passes > 1:
        params["exit_gate"] = {
            "w": dense(jax.random.fold_in(rng, 2048), (h, 1), std),
            "b": jnp.zeros((1,), jnp.float32)}
    return params


def param_logical_axes(cfg: TransformerConfig) -> PyTree:
    """Logical axis names per parameter dim (consumed by the sharding policy)."""
    if cfg.layer_kinds and not cfg.standard_blocks:
        return _kinds_tree(cfg, lambda shape, axes, how, n: ("layers",) + axes,
                           lambda shape, axes: axes)
    if cfg.first_dense_layers:
        (dkey, dcfg), (_, rest) = cfg.segments
        axes = param_logical_axes(rest)
        axes[dkey] = param_logical_axes(dcfg)["blocks"]
        return axes

    def norm_axes(prefix):
        p = {"scale": prefix + ("embed",)}
        if cfg.norm == "layernorm":
            p["bias"] = prefix + ("embed",)
        return p

    lyr = ("layers",)
    block = {"ln1": norm_axes(lyr)}
    if cfg.mla:
        # latent projections: ranks are shared (replicated); the per-head
        # output dims carry the 'heads' axis for TP
        if cfg.q_lora_rank:
            block["wq_a"] = lyr + ("embed", None)
            block["q_a_norm"] = lyr + (None,)
            block["wq_b"] = lyr + (None, "heads")
        else:
            block["wq"] = lyr + ("embed", "heads")
        block["wkv_a"] = lyr + ("embed", None)
        block["kv_a_norm"] = lyr + (None,)
        block["wkv_b"] = lyr + (None, "heads")
        block["wo"] = lyr + ("heads", "embed")
    else:
        block.update({
            "wq": lyr + ("embed", "heads"),
            "wk": lyr + ("embed", "kv_heads"),
            "wv": lyr + ("embed", "kv_heads"),
            "wo": lyr + ("heads", "embed"),
        })
        if cfg.attn_gate:
            block["wg"] = lyr + ("embed", "heads")
    if cfg.has_ln2:
        block["ln2"] = norm_axes(lyr)
    if cfg.post_norms:
        block["ln1_post"] = norm_axes(lyr)
        block["ln2_post"] = norm_axes(lyr)
    if cfg.qk_norm:
        block["q_norm"] = lyr + (None,)
        block["k_norm"] = lyr + (None,)
    if "sparse" in cfg.layer_kinds:
        block.update({"idx_wq": lyr + ("embed", None),
                      "idx_wk": lyr + ("embed", None),
                      "idx_ww": lyr + ("embed", None),
                      "idx_k_norm": {"scale": lyr + (None,),
                                     "bias": lyr + (None,)}})
    if cfg.n_experts > 0:
        block["gate_w"] = lyr + ("embed", None)
        block["w_up"] = lyr + ("expert", "embed", "mlp")
        block["w_down"] = lyr + ("expert", "mlp", "embed")
        if cfg.activation == "swiglu":
            block["w_gate"] = lyr + ("expert", "embed", "mlp")
        if cfg.moe_shared_size > 0:
            block["sw_up"] = lyr + ("embed", "mlp")
            block["sw_down"] = lyr + ("mlp", "embed")
            if cfg.activation == "swiglu":
                block["sw_gate"] = lyr + ("embed", "mlp")
            if cfg.moe_shared_gate:
                block["shared_gate_w"] = lyr + ("embed", None)
        if cfg.moe_gate_bias:
            block["gate_bias"] = lyr + (None,)
        if cfg.moe_latent_size:
            block["latent_down"] = lyr + ("embed", None)
            block["latent_up"] = lyr + (None, "embed")
    else:
        block["w_up"] = lyr + ("embed", "mlp")
        block["w_down"] = lyr + ("mlp", "embed")
        if cfg.activation == "swiglu":
            block["w_gate"] = lyr + ("embed", "mlp")
    if cfg.attn_bias_enabled:
        block.update({
            "bq": lyr + ("heads",), "bk": lyr + ("kv_heads",),
            "bv": lyr + ("kv_heads",),
        })
    if cfg.use_bias:
        block["bo"] = lyr + ("embed",)
        if cfg.n_experts == 0:
            block.update({"b_up": lyr + ("mlp",), "b_down": lyr + ("embed",)})
    if cfg.mixer_layers:
        from deepspeed_tpu.models.hybrid import mixer_specs

        attn = {k: block.pop(k) for k in _ATTN_LEAVES if k in block}
        if "attn" in cfg.mixer_layers:
            block["attn"] = attn
        if cfg.one_sublayer:
            block["ffn"] = {k: block.pop(k) for k in _FFN_LEAVES
                            if k in block}
        for kind in (k for k in MIXERS[1:-1] if k in cfg.mixer_layers):
            block[kind] = {name: lyr + axes for name, (_, axes, _) in
                           mixer_specs(cfg, kind).items()}
    axes = {
        "tok_emb": ("vocab", "embed"),
        "blocks": block,
        "final_norm": norm_axes(()),
    }
    if cfg.pos_emb == "learned":
        axes["pos_emb"] = ("seq", "embed")
    if cfg.emb_norm:
        axes["emb_norm"] = norm_axes(())
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
        if cfg.lm_head_bias:
            axes["lm_head_b"] = ("vocab",)
    if cfg.loop_passes > 1:
        axes["exit_gate"] = {"w": ("embed", None), "b": (None,)}
    return axes


def _kinds_tree(cfg: TransformerConfig, stacked: Callable,
                top: Callable) -> PyTree:
    """The parameter tree of a stack of ``layer_kinds``, leaf by leaf:
    ``stacked(shape, axes, how, n)`` for a leaf of ``n`` stacked layers,
    ``top(shape, axes)`` for the embedding, the head and the final norm."""
    from deepspeed_tpu.models.hybrid import mixer_specs

    h, f = cfg.hidden_size, cfg.ffn_size

    def norm(make):
        p = {"scale": make((h,), ("embed",), "ones")}
        if cfg.norm == "layernorm":
            p["bias"] = make((h,), ("embed",), "zeros")
        return p

    def layer(kind, n):
        make = lambda shape, axes, how: stacked(shape, axes, how, n)  # noqa
        lp = jax.tree.map(lambda leaf: make(*leaf), mixer_specs(cfg, kind),
                          is_leaf=lambda x: isinstance(x, tuple))
        lp.update(ln1=norm(make), ln2=norm(make),
                  w_up=make((h, f), ("embed", "mlp"), "std"),
                  w_down=make((f, h), ("mlp", "embed"), "out"))
        if cfg.activation == "swiglu":
            lp["w_gate"] = make((h, f), ("embed", "mlp"), "std")
        if cfg.use_bias:
            lp["b_up"] = make((f,), ("mlp",), "zeros")
            lp["b_down"] = make((h,), ("embed",), "zeros")
        return lp

    tree = {key: {kind: layer(kind, seg.num_layers) for kind in seg.period}
            for key, seg in cfg.segments}
    tree["tok_emb"] = top((cfg.vocab_size, h), ("vocab", "embed"))
    tree["final_norm"] = norm(lambda shape, axes, how: top(shape, axes))
    if not cfg.tie_embeddings:
        tree["lm_head"] = top((h, cfg.vocab_size), ("embed", "vocab"))
    return tree


def _init_kinds(cfg: TransformerConfig, rng: jax.Array) -> PyTree:
    from deepspeed_tpu.models.hybrid import init_leaf

    if cfg.pos_emb != "none" or cfg.emb_norm or cfg.lm_head_bias \
            or cfg.qk_norm or cfg.attn_bias_enabled or cfg.rescaled:
        raise NotImplementedError(
            "a stack of layer_kinds has no positional encoding, no "
            "embedding norm, no attention or head bias, no qk-norm and no "
            "multiplier on its embedding, residual stream, scores or logits")
    std, out_std = cfg.init_std, cfg.init_std / math.sqrt(2 * cfg.num_layers)
    count = [0]

    def key():
        count[0] += 1
        return jax.random.fold_in(rng, count[0])

    tree = _kinds_tree(
        cfg,
        lambda shape, axes, how, n: init_leaf(how, (n,) + shape, key(), std,
                                              out_std),
        lambda shape, axes: init_leaf("std", shape, key(), std, out_std))
    tree["final_norm"] = {
        k: (jnp.ones if k == "scale" else jnp.zeros)(
            (cfg.hidden_size,), jnp.float32) for k in tree["final_norm"]}
    return tree


# --------------------------------------------------------------------------- #
# building blocks
# --------------------------------------------------------------------------- #

def _norm(x: jax.Array, p: Dict[str, jax.Array], kind: str, eps: float) -> jax.Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        out = x32 * lax.rsqrt(var + eps) * p["scale"]
    else:
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
        out = (x32 - mean) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    return out.astype(dtype)


def _lm_head_of(params: PyTree, cfg: TransformerConfig) -> jax.Array:
    """LM head matrix [H, V]; dequantizes a weight-only-quantized head."""
    if cfg.tie_embeddings:
        return params["tok_emb"].T
    head = params["lm_head"]
    if isinstance(head, dict):
        from deepspeed_tpu.ops.quantization import dequantize_weight

        return dequantize_weight(head, cfg.compute_dtype)
    return head


def scale_residual(part: jax.Array, cfg: TransformerConfig) -> jax.Array:
    """What a sublayer adds to the residual stream: its output times
    ``cfg.residual_multiplier`` (at 1: the output itself, nothing traced)."""
    if cfg.residual_multiplier == 1.0:
        return part
    return part * jnp.asarray(cfg.residual_multiplier, part.dtype)


def divide_logits(logits: jax.Array, divisor: float) -> jax.Array:
    """Float32 logits (or their gradient) over ``TransformerConfig.
    logits_divisor`` (at 1: themselves, nothing traced)."""
    return logits if divisor == 1.0 else logits / jnp.float32(divisor)


def lm_logits(x: jax.Array, head: jax.Array, cfg: TransformerConfig
              ) -> jax.Array:
    """The head over final-normed rows: :func:`head_matmul` (float32) and
    the config's ``logits_divisor`` on its result."""
    return divide_logits(head_matmul(x, head.astype(x.dtype)),
                         cfg.logits_divisor)


def _head_rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """QK-norm (Qwen3): RMSNorm over the head dim of [B,S,N,D] q/k."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps) * scale).astype(dtype)


def _scaled_inv_freq(head_dim: int, theta: float,
                     scaling: Optional[Dict[str, Any]]):
    """Inverse rope frequencies with HF-compatible scaling (numpy, trace-time
    constants). Supports the types real checkpoints use: 'default',
    'linear', 'llama3' (Llama-3.x piecewise wavelength scaling), 'yarn'
    (NTK interpolation/extrapolation blend + attention factor — DeepSeek,
    Qwen-long). Mirrors ``transformers/modeling_rope_utils.py``.

    → (inv_freq [D/2] np.float32, attention_factor float — multiplies the
    cos/sin tables, HF convention)."""
    import numpy as _onp

    inv = 1.0 / (theta ** (_onp.arange(0, head_dim, 2, dtype=_onp.float64)
                           / head_dim))
    if not scaling:
        return inv.astype(_onp.float32), 1.0
    sc = dict(scaling)
    rtype = sc.get("rope_type", sc.get("type", "default"))
    factor = float(sc.get("factor", 1.0))
    if rtype == "default":
        return inv.astype(_onp.float32), 1.0
    if rtype == "linear":
        return (inv / factor).astype(_onp.float32), 1.0
    if rtype == "llama3":
        low_f = float(sc["low_freq_factor"])
        high_f = float(sc["high_freq_factor"])
        old_ctx = float(sc["original_max_position_embeddings"])
        wavelen = 2 * math.pi / inv
        out = _onp.where(wavelen > old_ctx / low_f, inv / factor, inv)
        smooth = (old_ctx / wavelen - low_f) / (high_f - low_f)
        smoothed = (1 - smooth) * out / factor + smooth * out
        medium = (wavelen >= old_ctx / high_f) & (wavelen <= old_ctx / low_f)
        out = _onp.where(medium, smoothed, out)
        return out.astype(_onp.float32), 1.0
    if rtype == "yarn":
        d2 = head_dim // 2
        old_ctx = float(sc.get("original_max_position_embeddings") or 0) or None
        max_pos = old_ctx if old_ctx else float(sc.get("max_position_embeddings", 2048))
        mscale = sc.get("mscale")
        mscale_all = sc.get("mscale_all_dim")

        def get_mscale(scale, m=1.0):
            return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0

        att = sc.get("attention_factor")
        if att is None:
            if mscale and mscale_all:
                att = get_mscale(factor, mscale) / get_mscale(factor, mscale_all)
            else:
                att = get_mscale(factor)
        beta_fast = float(sc.get("beta_fast") or 32)
        beta_slow = float(sc.get("beta_slow") or 1)

        def corr_dim(rot):
            return (head_dim * math.log(max_pos / (rot * 2 * math.pi))
                    ) / (2 * math.log(theta))

        low = max(math.floor(corr_dim(beta_fast)), 0)
        high = min(math.ceil(corr_dim(beta_slow)), head_dim - 1)
        if low == high:
            high += 0.001
        ramp = _onp.clip((_onp.arange(d2, dtype=_onp.float64) - low)
                         / (high - low), 0, 1)
        extrap_mask = 1 - ramp
        out = (inv / factor) * (1 - extrap_mask) + inv * extrap_mask
        return out.astype(_onp.float32), float(att)
    raise NotImplementedError(
        f"rope_scaling type {rtype!r} is not implemented "
        "(supported: default, linear, llama3, yarn)")


def rope_table(seq_len: int, head_dim: int, theta: float,
               scaling: Optional[Dict[str, Any]] = None
               ) -> Tuple[jax.Array, jax.Array]:
    inv_freq, att = _scaled_inv_freq(head_dim, theta, scaling)
    t = jnp.arange(seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, jnp.asarray(inv_freq))          # [S, D/2]
    return jnp.cos(freqs) * att, jnp.sin(freqs) * att


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: [B, S, N, D]; rotates pairs (interleaved halves convention).
    When the tables cover fewer dims than D (partial rotary, NeoX/Phi), the
    trailing dims pass through unrotated."""
    rot = 2 * cos.shape[-1]
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    d2 = rot // 2
    x1, x2 = x_rot[..., :d2], x_rot[..., d2:]
    cos = cos[None, :, None, :].astype(x.dtype)
    sin = sin[None, :, None, :].astype(x.dtype)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    if x_pass.shape[-1]:
        out = jnp.concatenate([out, x_pass], axis=-1)
    return out


def alibi_slopes(n_heads: int) -> jax.Array:
    """ALiBi per-head slopes (BLOOM/press-et-al formula, incl. non-pow2)."""
    def pow2_slopes(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start * (start ** i) for i in range(n)]

    if math.log2(n_heads).is_integer():
        sl = pow2_slopes(n_heads)
    else:
        base = 2 ** math.floor(math.log2(n_heads))
        sl = pow2_slopes(base)
        extra = pow2_slopes(2 * base)[0::2][: n_heads - base]
        sl = sl + extra
    return jnp.asarray(sl, jnp.float32)


def alibi_bias(n_heads: int, seq_len: int) -> jax.Array:
    """[N, S, S] additive attention bias: slope * (key_pos - query_pos)."""
    slopes = alibi_slopes(n_heads)
    rel = (jnp.arange(seq_len)[None, :] - jnp.arange(seq_len)[:, None])
    return slopes[:, None, None] * rel[None].astype(jnp.float32)


@jax.custom_vjp
def head_matmul(x: jax.Array, w: jax.Array) -> jax.Array:
    """LM-head projection: MXU-speed matmul with fp32 accumulation.

    ``x @ w`` with inputs kept in the compute dtype (bf16 → MXU) and the
    product accumulated/returned in fp32. The custom VJP casts the fp32
    cotangent back to the compute dtype so BOTH backward matmuls also hit the
    MXU — naive fp32 upcasting makes the vocab projection (the largest matmul
    in small/mid LMs) run at the ~8×-slower fp32 rate on TPU in fwd and bwd.
    """
    return jnp.matmul(x, w, preferred_element_type=jnp.float32)


def _head_matmul_fwd(x, w):
    return head_matmul(x, w), (x, w)


def _head_matmul_bwd(res, g):
    x, w = res
    gl = g.astype(x.dtype)
    dx = jnp.matmul(gl, w.T, preferred_element_type=jnp.float32).astype(x.dtype)
    x2 = x.reshape(-1, x.shape[-1])
    g2 = gl.reshape(-1, gl.shape[-1])
    dw = jnp.matmul(x2.T, g2, preferred_element_type=jnp.float32).astype(w.dtype)
    return dx, dw


head_matmul.defvjp(_head_matmul_fwd, _head_matmul_bwd)


def dot_product_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                          causal: bool = True,
                          segment_mask: Optional[jax.Array] = None,
                          bias: Optional[jax.Array] = None,
                          window: int = 0,
                          scale: Optional[float] = None) -> jax.Array:
    """Reference (XLA-fused) attention. q:[B,S,N,D] k,v:[B,S,K,D]. fp32 softmax.
    ``bias``: additive [N, S, S] (ALiBi) applied before masking. ``window``:
    a causal row sees its last ``window`` positions, itself included (0:
    every earlier one). ``scale``: the scores' factor (None: ``D ** -0.5``)."""
    B, S, N, D = q.shape
    K = k.shape[2]
    if K != N:
        k = jnp.repeat(k, N // K, axis=2)
        v = jnp.repeat(v, N // K, axis=2)
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    scores = jnp.einsum("bsnd,btnd->bnst", q, k).astype(jnp.float32) * scale
    if bias is not None:
        scores = scores + bias[None]
    if causal:
        mask = jnp.tril(jnp.ones((S, S), jnp.bool_))
        if window:
            mask &= ~jnp.tril(jnp.ones((S, S), jnp.bool_), -window)
        scores = jnp.where(mask[None, None], scores, -1e30)
    if segment_mask is not None:
        scores = jnp.where(segment_mask[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bnst,btnd->bsnd", probs, v)


def _rope_deinterleave(x: jax.Array) -> jax.Array:
    """DeepSeek stores rope dims as interleaved (re,im) pairs; permute to the
    half-split layout rotate_half rope expects (HF
    ``apply_rotary_pos_emb_interleave``)."""
    *lead, d = x.shape
    return x.reshape(*lead, d // 2, 2).swapaxes(-1, -2).reshape(*lead, d)


def _mla_q(h: jax.Array, lp: Dict[str, jax.Array], cfg: TransformerConfig,
           rope_fn) -> jax.Array:
    """MLA query path: (optional) low-rank q projection + decoupled rope on
    the pe dims → [B, S, N, dn+dr] (HF ``DeepseekV3Attention.forward``)."""
    B, S, _ = h.shape
    dt = h.dtype
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    if cfg.q_lora_rank:
        qa = h @ lp["wq_a"].astype(dt)
        qa = _head_rmsnorm(qa, lp["q_a_norm"], cfg.norm_eps)
        q = qa @ lp["wq_b"].astype(dt)
    else:
        q = h @ lp["wq"].astype(dt)
    q = q.reshape(B, S, cfg.num_heads, dn + dr)
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    if cfg.rope_interleave:
        q_pe = _rope_deinterleave(q_pe)
    return jnp.concatenate([q_nope, rope_fn(q_pe)], axis=-1)


def _mla_latents(h: jax.Array, lp: Dict[str, jax.Array],
                 cfg: TransformerConfig, rope_fn
                 ) -> Tuple[jax.Array, jax.Array]:
    """MLA KV latents: normed c_kv [B, S, kvr] + post-rope shared key
    [B, S, 1, dr] — exactly what the decode path caches."""
    dt = h.dtype
    kvr = cfg.kv_lora_rank
    kv_a = h @ lp["wkv_a"].astype(dt)                 # [B, S, kvr+dr]
    c_kv = _head_rmsnorm(kv_a[..., :kvr], lp["kv_a_norm"], cfg.norm_eps)
    k_pe = kv_a[..., kvr:][:, :, None, :]             # [B, S, 1, dr] shared
    if cfg.rope_interleave:
        k_pe = _rope_deinterleave(k_pe)
    return c_kv, rope_fn(k_pe)


def _mla_expand(c_kv: jax.Array, k_pe: jax.Array,
                lp: Dict[str, jax.Array], cfg: TransformerConfig
                ) -> Tuple[jax.Array, jax.Array]:
    """Latents → full per-head k [B, S, N, dn+dr] and v [B, S, N, dv]."""
    dt = c_kv.dtype
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    B, S = c_kv.shape[:2]
    N = cfg.num_heads
    kv = (c_kv @ lp["wkv_b"].astype(dt)).reshape(B, S, N, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_pe, (B, S, N, dr))], axis=-1)
    return k, kv[..., dn:]


def _mla_qkv(h: jax.Array, lp: Dict[str, jax.Array], cfg: TransformerConfig,
             rope_fn) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Full MLA projections for the training/prefill path."""
    q = _mla_q(h, lp, cfg, rope_fn)
    c_kv, k_pe = _mla_latents(h, lp, cfg, rope_fn)
    k, v = _mla_expand(c_kv, k_pe, lp, cfg)
    return q, k, v


def _mla_absorbed_attention(q: jax.Array, ckv: jax.Array, kpe: jax.Array,
                            lp: Dict[str, jax.Array], cfg: TransformerConfig,
                            positions: jax.Array, scale_mult: float
                            ) -> jax.Array:
    """Weight-absorbed MLA decode (the DeepSeek inference trick): fold
    W_uk into the query and W_uv into the output so attention runs ENTIRELY
    in the latent space — per step the cache is read once at width kvr+dr
    and the O(M·N·(dn+dv)) k/v re-expansion never happens.

    q: [B,T,N,dn+dr] (post-rope); ckv: [B,M,kvr] (normed latents);
    kpe: [B,M,dr] (post-rope shared key); → [B,T,N,dv].
    """
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    kvr, N = cfg.kv_lora_rank, cfg.num_heads
    B, T = q.shape[:2]
    M = ckv.shape[1]
    dt = q.dtype
    w_kv = lp["wkv_b"].astype(dt).reshape(kvr, N, dn + dv)
    w_uk, w_uv = w_kv[..., :dn], w_kv[..., dn:]          # [kvr, N, dn/dv]
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    # absorb: q ↦ latent space (per head)
    q_lat = jnp.einsum("btnd,knd->btnk", q_nope, w_uk)   # [B,T,N,kvr]
    scale = scale_mult / math.sqrt(dn + dr)
    scores = (jnp.einsum("btnk,bmk->bntm", q_lat, ckv)
              + jnp.einsum("btnr,bmr->bntm", q_pe, kpe)
              ).astype(jnp.float32) * scale
    mask = jnp.arange(M)[None, None, None, :] <= positions[:, None, :, None]
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(dt)
    out_lat = jnp.einsum("bntm,bmk->btnk", probs, ckv)   # [B,T,N,kvr]
    return jnp.einsum("btnk,knd->btnd", out_lat, w_uv)   # [B,T,N,dv]


def index_projections(h: jax.Array, lp: Dict[str, jax.Array],
                      cfg: TransformerConfig,
                      rope: Tuple[jax.Array, jax.Array],
                      positions: Optional[jax.Array] = None
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """A ``sparse`` layer's indexer from its normed rows ``h [B, S, H]``:
    (queries ``[B, S, heads, d]``, the one key ``[B, S, d]`` under a
    LayerNorm with gain and bias, the heads' weights ``[B, S, heads]``),
    queries and key rotated over their ``d`` columns at ``positions [B,
    S]`` (None: 0 .. S-1) by ``rope``, the cos and sin tables of ``d``
    columns."""
    dt = cfg.compute_dtype
    B, S = h.shape[:2]
    q = (h @ lp["idx_wq"].astype(dt)).reshape(
        B, S, cfg.index_heads, cfg.index_head_dim)
    k = _norm(h @ lp["idx_wk"].astype(dt), lp["idx_k_norm"], "layernorm",
              cfg.norm_eps)[:, :, None, :]
    if positions is None:
        q, k = apply_rope(q, *rope), apply_rope(k, *rope)
    else:
        q = apply_rope_at(q, *rope, positions)
        k = apply_rope_at(k, *rope, positions)
    return q, k[:, :, 0], h @ lp["idx_ww"].astype(dt)


def index_scores(q: jax.Array, k: jax.Array, w: jax.Array) -> jax.Array:
    """``I[b, t, s] = sum_j w[b, t, j] relu(q[b, t, j] . k[b, s])`` in
    float32, ``[B, S, S]``: what :func:`index_projections` returns, every
    row against every position (a caller keeps the causal part)."""
    s = jnp.einsum("btjd,bsd->btjs", q, k,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("btjs,btj->bts", jax.nn.relu(s),
                      w.astype(jnp.float32))


def chosen_positions(scores: jax.Array, topk: int) -> jax.Array:
    """The positions each row of a causal batch attends to, ``[B, S, S]``
    bool: of ``scores [B, S, S]`` the ``topk`` largest among ``s <= t``,
    the lower position first among equals (``lax.top_k``'s own order);
    every ``s <= t`` while ``t + 1 <= topk``."""
    S = scores.shape[-1]
    causal = jnp.tril(jnp.ones((S, S), jnp.bool_))
    if S <= topk:
        return jnp.broadcast_to(causal, scores.shape)
    _, idx = lax.top_k(jnp.where(causal, scores, -jnp.inf), topk)
    rows = jnp.arange(S)[None, :, None]
    picked = jnp.zeros(scores.shape, jnp.bool_).at[
        jnp.arange(scores.shape[0])[:, None, None], rows, idx].set(True)
    return picked & causal


def _block_forward(x: jax.Array, lp: Dict[str, jax.Array], cfg: TransformerConfig,
                   cos: Optional[jax.Array], sin: Optional[jax.Array],
                   attention_fn: AttentionFn, kind: Optional[str] = None
                   ) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
    """One transformer block; lp holds this layer's (unstacked) params.
    Returns (output, moe aux loss — 0.0 for dense blocks, the layer's
    meter — ``moe.layer.held_meter`` of a share of an expert layer, None
    for every other block: a tree with no leaf, so a scan or a checkpoint
    carries it at no cost and a dense program is what it was).

    ``kind`` (a layer of ``cfg.layer_kinds`` over this one block,
    ``cfg.standard_blocks``): ``window`` sees its last ``cfg.attn_window``
    positions, ``full`` every one, both through ``attention_fn`` (``window=``
    names the window; ``cos`` / ``sin`` are the KIND's tables, None where
    it has no rotary: ``cfg.rope_of``);
    ``sparse`` the positions its indexer chooses (:func:`index_scores`,
    :func:`chosen_positions`), under an explicit mask in plain jnp: it has
    no kernel;
    ``conv`` has a gated short convolution where the others attend
    (``hybrid.short_conv``; ``lp`` then holds that mixer's leaves), ``kda``
    Kimi Delta Attention (``hybrid.kda_inputs`` .. ``kda_output``, every
    sequence of the batch a run from a zero state), ``latent`` the latent
    attention of ``cfg.mla``, and the norms, the residual form and the FFN
    or experts are the block's.

    Sequential (GPT/Llama) or parallel (Falcon/NeoX/Phi: attn and FFN both
    branch off the residual stream and are summed back).

    Weight-only-quantized params ({"q","scale","zero"} subtrees —
    ``ops/quantization.py weight_quantize_groupwise``) dequantize HERE, per
    layer inside the scan: at most one layer of fp weights is live."""
    from deepspeed_tpu.ops.quantization import dequant_params

    B, S, H = x.shape
    dt = cfg.compute_dtype
    lp = dequant_params(lp, dt)

    def proj(name, inp, shape):
        w = lp[f"w{name}"].astype(dt)
        out = inp @ w
        if (cfg.attn_bias_enabled if name in ("q", "k", "v") else cfg.use_bias):
            out = out + lp[f"b{name}"].astype(dt)
        return out.reshape(shape)

    structural = cfg.remat in ("attn_block", "ffn_block")
    if structural and (cfg.mla or cfg.parallel_block):
        raise ValueError(
            f"remat={cfg.remat!r} (structural sub-block checkpoint) supports "
            "the sequential non-MLA block only; use full/selective for "
            "MLA/parallel-block models")

    def _aq(h):
        # QAT activation fake-quant on the linears' inputs (QuantAct
        # placement: after the norm, before every projection); STE backward
        if not cfg.act_quant_bits:
            return h
        from deepspeed_tpu.compression.quantize import fake_quant_symmetric

        return fake_quant_symmetric(
            h, float(2 ** (cfg.act_quant_bits - 1) - 1))

    def add(x, *parts):
        """The residual stream and what the sublayers add to it, each
        times ``cfg.residual_multiplier``."""
        for part in parts:
            x = x + scale_residual(part, cfg)
        return x

    # the scopes a device trace sorts a block's operations by
    # (``attn`` / ``mlp``, under the engine's ``loss_and_grads``)
    with jax.named_scope({"mamba2": "ssd", "ffn": "mlp"}.get(
            kind, kind if kind in MIXERS[1:] else "attn")):
        h = _aq(_norm(x, lp["ln1"], cfg.norm, cfg.norm_eps))
    if kind == "ffn":
        # a stack of single sublayers: the feed-forward part alone
        with contextlib.nullcontext() if cfg.n_experts \
                else jax.named_scope("mlp"):
            down, aux, meter = _ffn_metered(h, lp, cfg)
        return add(x, down), aux, meter
    if cfg.mla and kind in (None, "latent"):
        with jax.named_scope("attn"):
            # no rotary where the model has none (``pos_emb`` "none": the
            # 64 "rope" values of a query and of the shared key are kept
            # and unrotated)
            q, k, v = _mla_qkv(h, lp, cfg, (lambda t: t) if cos is None
                               else lambda t: apply_rope(t, cos, sin))
            if cfg.mla_scale_mult != 1.0:
                q = q * jnp.asarray(cfg.mla_scale_mult, q.dtype)
            # flash kernels assume one head dim; MLA's split qk/v dims run
            # on the XLA reference attention (scale = 1/sqrt(dn+dr) from
            # q's D)
            attn = dot_product_attention(q, k, v, causal=cfg.causal)
            attn = attn.reshape(B, S, cfg.num_heads * cfg.v_head_dim)
            attn = _ckpt_name(attn, "attn_out")
            attn_out = attn @ lp["wo"].astype(dt)
            x = add(x, attn_out)
        with jax.named_scope("mlp"):
            h2 = _aq(_norm(x, lp["ln2"], cfg.norm, cfg.norm_eps))
            down, aux, meter = _ffn_metered(h2, lp, cfg)
            return add(x, down), aux, meter

    @jax.named_scope("attn")
    def _attn_from_norm(h):
        if cfg.fuse_qkv:
            qdim = cfg.num_heads * cfg.head_dim
            kvdim = cfg.kv_heads * cfg.head_dim
            wqkv = jnp.concatenate(
                [lp["wq"].astype(dt), lp["wk"].astype(dt), lp["wv"].astype(dt)],
                axis=-1)
            qkv = h @ wqkv
            if cfg.attn_bias_enabled:
                qkv = qkv + jnp.concatenate(
                    [lp["bq"], lp["bk"], lp["bv"]], axis=-1).astype(dt)
            q = qkv[..., :qdim].reshape(B, S, cfg.num_heads, cfg.head_dim)
            k = qkv[..., qdim:qdim + kvdim].reshape(
                B, S, cfg.kv_heads, cfg.head_dim)
            v = qkv[..., qdim + kvdim:].reshape(
                B, S, cfg.kv_heads, cfg.head_dim)
        else:
            q = proj("q", h, (B, S, cfg.num_heads, cfg.head_dim))
            k = proj("k", h, (B, S, cfg.kv_heads, cfg.head_dim))
            v = proj("v", h, (B, S, cfg.kv_heads, cfg.head_dim))
        if cfg.qk_norm:
            q = _head_rmsnorm(q, lp["q_norm"], cfg.norm_eps)
            k = _head_rmsnorm(k, lp["k_norm"], cfg.norm_eps)
        if cfg.pos_emb == "rope" and cos is not None:
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
        attn_kwargs = {}
        if cfg.pos_emb == "alibi":
            attn_kwargs["bias"] = \
                alibi_bias(cfg.num_heads, S) * cfg.alibi_bias_scale
        if kind == "window":
            attn_kwargs["window"] = cfg.attn_window
        if cfg.attn_scale:
            attn_kwargs["scale"] = cfg.attn_scale
        if kind == "sparse":
            # no kernel takes a choice of positions: plain jnp
            from deepspeed_tpu.models.hybrid import windowed_attention

            with jax.named_scope("index"):
                scores = index_scores(*index_projections(
                    h, lp, cfg, rope_table(
                        S, cfg.index_head_dim, cfg.rope_theta,
                        cfg.rope_scaling_dict)))
            with jax.named_scope("select"):
                chosen = chosen_positions(scores, cfg.sparse_topk)
            attn = windowed_attention(q, k, v, cfg.head_dim ** -0.5, 0,
                                      chosen)
        else:
            attn = attention_fn(q, k, v, causal=cfg.causal, **attn_kwargs)
        attn = attn.reshape(B, S, cfg.num_heads * cfg.head_dim)
        if cfg.attn_gate:
            attn = attn * jax.nn.sigmoid(h @ lp["wg"].astype(dt))
        attn = _ckpt_name(attn, "attn_out")
        attn_out = attn @ lp["wo"].astype(dt)
        if cfg.use_bias:
            attn_out = attn_out + lp["bo"].astype(dt)
        if cfg.post_norms:
            attn_out = _norm(attn_out, lp["ln1_post"], cfg.norm,
                             cfg.norm_eps)
        return attn_out

    @jax.named_scope("conv")
    def _conv_from_norm(h):
        # every sequence of the batch a run of rows from position 0
        from deepspeed_tpu.models import hybrid as HY

        runs = HY.runs_of(jnp.repeat(jnp.arange(B, dtype=jnp.int32), S),
                          jnp.tile(jnp.arange(S, dtype=jnp.int32), B))
        mixed, _ = HY.short_conv(
            h.reshape(B * S, H), lp, runs,
            (jnp.zeros((B * S, H), dt),) * (cfg.conv_taps - 1))
        return (mixed @ lp["wo"].astype(dt)).reshape(B, S, H)

    @jax.named_scope("kda")
    def _kda_from_norm(h):
        from deepspeed_tpu.models import hybrid as HY

        owner = jnp.repeat(jnp.arange(B, dtype=jnp.int32), S)
        runs = HY.runs_of(owner, jnp.tile(jnp.arange(S, dtype=jnp.int32), B))
        hr = h.reshape(B * S, H)
        (n, d, _), (kept, channels) = HY.kda_state_shapes(cfg)
        inputs, _ = HY.kda_inputs(
            hr, lp, cfg, runs, (jnp.zeros((B * S, channels), dt),) * kept)
        # a row of state a sequence (row 0 is the pad rows')
        o, _ = HY.delta_rule(*inputs, runs,
                             jnp.zeros((B + 1, n, d, d), jnp.float32),
                             owner + 1)
        return (HY.kda_output(o, hr, lp, cfg)
                @ lp["wo"].astype(dt)).reshape(B, S, H)

    @jax.named_scope("ssd")
    def _mamba2_from_norm(h):
        from deepspeed_tpu.models import hybrid as HY

        owner = jnp.repeat(jnp.arange(B, dtype=jnp.int32), S)
        runs = HY.runs_of(owner, jnp.tile(jnp.arange(S, dtype=jnp.int32), B))
        hr = h.reshape(B * S, H)
        matrix, (kept, channels) = HY.mamba2_state_shapes(cfg)
        (xs, *rest), z, _ = HY.mamba2_inputs(
            hr, lp, cfg, runs, (jnp.zeros((B * S, channels), dt),) * kept)
        # a row of state a sequence (row 0 is the pad rows')
        y, _ = HY.ssd(xs, *rest, runs,
                      jnp.zeros((B + 1,) + matrix, jnp.float32), owner + 1,
                      chunk=cfg.mamba2_chunk)
        return (HY.mamba2_output(y, xs, z, lp, cfg)
                @ lp["wo"].astype(dt)).reshape(B, S, H)

    if kind == "conv":
        attn_out = _conv_from_norm(h)
    elif kind == "kda":
        attn_out = _kda_from_norm(h)
    elif kind == "mamba2":
        attn_out = _mamba2_from_norm(h)
    elif cfg.remat == "attn_block":
        # structural remat: bwd recomputes ONLY norm1 → attention → wo
        # (~37% of layer FLOPs at 4h² vs FFN's 8h²); every FFN intermediate
        # stays saved by the scan's AD — no names policy, so XLA's scan
        # fusion is untouched. Memory ≈ 10·B·S·H bf16 per layer.
        attn_out = jax.checkpoint(
            lambda xin: _attn_from_norm(
                _aq(_norm(xin, lp["ln1"], cfg.norm, cfg.norm_eps))))(x)
    else:
        attn_out = _attn_from_norm(h)

    if cfg.one_sublayer:
        return add(x, attn_out), jnp.float32(0.0), None
    if cfg.parallel_block:
        with jax.named_scope("mlp"):
            h2 = h if cfg.shared_parallel_norm else \
                _aq(_norm(x, lp["ln2"], cfg.norm, cfg.norm_eps))
            down, aux, meter = _ffn_metered(h2, lp, cfg)
            return add(x, attn_out, down), aux, meter

    x = add(x, attn_out)

    @jax.named_scope("mlp")
    def _ffn_delta(xr):
        h2 = _aq(_norm(xr, lp["ln2"], cfg.norm, cfg.norm_eps))
        down, aux, meter = _ffn_metered(h2, lp, cfg)
        if cfg.post_norms:
            down = _norm(down, lp["ln2_post"], cfg.norm, cfg.norm_eps)
        return down, aux, meter

    if cfg.remat == "ffn_block":
        # converse structural remat: bwd recomputes norm2 → FFN (~63% of
        # layer FLOPs); attention residuals (q/k/v/out + flash lse) stay
        # saved. Memory ≈ 6·B·S·H bf16 per layer — the cheaper-storage,
        # smaller-win sibling of attn_block.
        down, aux, meter = jax.checkpoint(_ffn_delta)(x)
    else:
        down, aux, meter = _ffn_delta(x)
    return add(x, down), aux, meter


def _ffn(h: jax.Array, lp: Dict[str, jax.Array], cfg: TransformerConfig
         ) -> Tuple[jax.Array, jax.Array]:
    """Dense or MoE FFN on normed input; returns (output, aux loss)."""
    return _ffn_metered(h, lp, cfg)[:2]


def _ffn_metered(h: jax.Array, lp: Dict[str, jax.Array],
                 cfg: TransformerConfig
                 ) -> Tuple[jax.Array, jax.Array, Optional[jax.Array]]:
    """:func:`_ffn` and the layer's meter (:func:`_block_forward`)."""
    dt = cfg.compute_dtype
    aux, meter = jnp.float32(0.0), None
    if cfg.n_experts > 0:
        from deepspeed_tpu.moe.layer import moe_ffn

        experts = {k_: lp[k_] for k_ in ("w_up", "w_down", "w_gate") if k_ in lp}
        shared = {k_: lp[k_] for k_ in ("sw_up", "sw_down", "sw_gate",
                                        "shared_gate_w") if k_ in lp}
        # flat rows ``[T, H]`` (a caller outside the block) are one batch
        down, aux, meter = moe_ffn(
            h.reshape((-1,) + h.shape[-2:]), lp["gate_w"], experts,
            activation=cfg.activation,
            k=cfg.moe_top_k, capacity_factor=cfg.moe_capacity_factor,
            min_capacity=cfg.moe_min_capacity,
            score_func=cfg.moe_score_func, route_norm=cfg.moe_route_norm,
            route_scale=cfg.moe_route_scale, shared=shared or None,
            gate_bias=lp.get("gate_bias"), n_group=cfg.moe_n_group,
            topk_group=cfg.moe_topk_group, dispatch=cfg.moe_dispatch,
            route_norm_eps=cfg.moe_route_norm_eps,
            first_expert=cfg.moe_first_expert, with_meter=True,
            latent={k_: lp[k_] for k_ in ("latent_down", "latent_up")
                    if k_ in lp} or None)
        down = down.reshape(h.shape)
    else:
        up = h @ lp["w_up"].astype(dt)
        if cfg.use_bias:
            up = up + lp["b_up"].astype(dt)
        if cfg.activation == "swiglu":
            gate = h @ lp["w_gate"].astype(dt)
            act = jax.nn.silu(gate) * up
        elif cfg.activation == "relu":
            act = jax.nn.relu(up)
        elif cfg.activation == "relu2":
            act = jnp.square(jax.nn.relu(up))
        else:
            act = jax.nn.gelu(up, approximate=True)
        act = _ckpt_name(act, "ffn_act")
        down = act @ lp["w_down"].astype(dt)
        if cfg.use_bias:
            down = down + lp["b_down"].astype(dt)
    return down, aux, meter


def _require_one_stack(cfg: TransformerConfig, what: str) -> None:
    _check_loop(cfg, what)
    if cfg.layer_kinds:
        raise NotImplementedError(
            f"{what} runs one homogeneous layer stack; a stack of layer "
            "kinds (state-space, windowed and shared-cache layers; window "
            "and full attention layers in one stack; short-convolution or "
            "delta-rule layers beside attention or latent-attention "
            "layers) is served by FastGenEngine and run whole by forward()")
    if cfg.first_dense_layers:
        raise NotImplementedError(
            f"{what} runs one homogeneous layer stack; a model with leading "
            f"dense layers (first_dense_layers={cfg.first_dense_layers}) is "
            "served by FastGenEngine and trained without pipeline stages")
    if cfg.rescaled:
        raise NotImplementedError(
            f"{what} applies no multiplier to the embedding, the residual "
            "stream, the attention scores or the logits (emb_multiplier="
            f"{cfg.emb_multiplier}, residual_multiplier="
            f"{cfg.residual_multiplier}, attn_scale={cfg.attn_scale}, "
            f"logits_divisor={cfg.logits_divisor}); forward() and "
            "FastGenEngine do")


# --------------------------------------------------------------------------- #
# forward
# --------------------------------------------------------------------------- #

# What the layers of one forward pass meter of themselves
# (``_block_forward``'s third value, stacked by layer), handed to whoever
# asked at the SAME trace level as the call of ``forward_hidden``: the
# training step opens ``collect_meters`` around the model's ``loss_fn``
# inside the function it differentiates and returns the dict as that
# function's auxiliary output, so the values leave the compiled step in its
# ``metrics`` (no host callback; whatever wraps or replaces a spec's
# ``loss_fn`` is metered alike as long as it calls ``forward_hidden`` in
# its own trace). One list a thread: a trace runs on the thread that asked.
_METER_SINKS = threading.local()


@contextlib.contextmanager
def collect_meters():
    """``with collect_meters() as meters``: the dict that the calls of
    :func:`forward_hidden` inside the block fill (``moe_held``:
    ``int32[expert layers, held + 2]``, a ``moe.layer.held_meter`` a layer
    that holds a share of its experts; nothing for any other model)."""
    sinks = _METER_SINKS.__dict__.setdefault("sinks", [])
    sinks.append({})
    try:
        yield sinks[-1]
    finally:
        sinks.pop()


def _emit_meters(**meters) -> None:
    sinks = getattr(_METER_SINKS, "sinks", None)
    if sinks:
        sinks[-1].update({k: v for k, v in meters.items() if v is not None})


def forward_hidden(params: PyTree, tokens: jax.Array, cfg: TransformerConfig,
                   attention_fn: Optional[AttentionFn] = None,
                   activation_constraint: Optional[Callable[[jax.Array], jax.Array]] = None,
                   pld_keep: Optional[jax.Array] = None,
                   random_ltd_idx: Optional[jax.Array] = None,
                   param_sync: Optional[Callable[[PyTree], PyTree]] = None
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """tokens [B, S] int32 → (final hidden [B, S, H], lm head [H, vocab],
    moe aux loss — summed over layers, 0.0 for dense models).

    ``pld_keep`` [L] float 0/1: progressive-layer-drop mask — a dropped layer
    contributes identity (reference ``runtime/progressive_layer_drop.py``;
    under jit both branches are computed, so PLD acts as the stochastic-depth
    regularizer, not a compute saver — documented TPU semantics).
    ``random_ltd_idx`` [K] sorted positions: random-LTD — the MIDDLE layers
    (all but first and last) run on only these K tokens; dropped tokens skip
    the middle stack via gather/scatter (reference ``data_routing/`` +
    ``csrc/random_ltd``; here the drop set is shared across the middle stack
    so the scan keeps uniform shapes).

    ``cfg.scan_chunks > 1`` splits the layer scan into that many
    sequential chunk scans (``parallel/overlap.py`` even-split) so the
    ZeRO-3 gather of chunk k+1 and the gradient sync of chunk k can
    overlap chunk-adjacent compute; ``param_sync`` (engine-injected,
    ``make_grad_sync``) wraps each chunk's sliced params so its gradient
    sharding constraint is emitted mid-backward. Both are identities —
    the chunked forward is numerically the single-scan forward. The
    random-LTD path keeps its own first/middle/last split and ignores
    chunking (its stacks are already scan-segmented).

    A looped stack (``cfg.loop_passes`` > 1) runs the stack that many times
    over the same leaves, the final norm after every pass, and returns the
    state of the pass its exit gates choose (:func:`loop_exit`), normed
    already; PLD, random-LTD and chunking refuse it by name."""
    _check_loop(cfg)
    if cfg.layer_kinds:
        if pld_keep is not None or random_ltd_idx is not None \
                or param_sync is not None:
            raise NotImplementedError(
                "progressive layer drop, random-LTD and the chunked "
                "gradient sync assume one homogeneous stack; a stack of "
                "layer kinds runs without them")
        constrain = activation_constraint or (lambda x: x)
        if cfg.standard_blocks:
            return _forward_blocks_of_kinds(params, tokens, cfg, constrain,
                                            attention_fn)
        return _forward_kinds(params, tokens, cfg, constrain)
    for used, what in ((pld_keep is not None, "progressive layer drop"),
                       (random_ltd_idx is not None, "random-LTD"),
                       (cfg.scan_chunks > 1 or param_sync is not None,
                        "the chunked layer scan (scan_chunks)")):
        if used:
            _check_loop(cfg, what)
    attention_fn = attention_fn or dot_product_attention
    constrain = activation_constraint or (lambda x: x)
    dt = cfg.compute_dtype
    B, S = tokens.shape
    # a leading dense segment (``cfg.segments``) is one plain scan ahead of
    # the stack; chunking, the sync hook and random-LTD act on the rest
    lead = cfg.segments[:-1]
    full_cfg, cfg = cfg, cfg.segments[-1][1]
    L = cfg.num_layers
    if lead and random_ltd_idx is not None:
        raise NotImplementedError(
            "random-LTD over a stack with leading dense layers "
            "(first_dense_layers) is unsupported: its first/middle/last "
            "split assumes one homogeneous stack")

    with jax.named_scope("embed"):
        x = params["tok_emb"].astype(dt)[tokens]
        if cfg.emb_multiplier != 1.0:
            x = x * jnp.asarray(cfg.emb_multiplier, dt)
        if cfg.pos_emb == "learned":
            x = x + params["pos_emb"].astype(dt)[:S][None]
        if cfg.emb_norm:
            x = _norm(x, params["emb_norm"], cfg.norm, cfg.norm_eps)
        x = constrain(x)

    cos = sin = None
    if cfg.pos_emb == "rope":
        rd = cfg.qk_rope_head_dim if cfg.mla else cfg.rope_dim
        cos, sin = rope_table(S, rd, cfg.rope_theta, cfg.rope_scaling_dict)

    def make_body(cos_b, sin_b, with_pld: bool, cfg=cfg):
        def body(carry, xs):
            if with_pld:
                layer_params, keep = xs
            else:
                layer_params, keep = xs, None
            y, aux, meter = _block_forward(carry, layer_params, cfg, cos_b,
                                           sin_b, attention_fn)
            if keep is not None:
                k = keep.astype(y.dtype)   # don't promote the bf16 carry
                y = k * y + (1 - k) * carry
                aux = keep * aux
            return constrain(y), (aux, meter)

        return _remat_wrap(body, cfg.remat)

    with_pld = pld_keep is not None

    def run(x, blocks, cos_b, sin_b, keep):
        xs = (blocks, keep) if with_pld else blocks
        return lax.scan(make_body(cos_b, sin_b, with_pld), x, xs)

    def run_chunked(x, blocks, cos_b, sin_b, keep):
        """Sequential per-chunk scans (overlap scheduler granularity).
        Exactly ``run`` when one chunk and no sync hook."""
        from deepspeed_tpu.parallel.overlap import even_chunk_bounds

        bounds = even_chunk_bounds(L, max(cfg.scan_chunks, 1))
        if len(bounds) <= 1 and param_sync is None:
            return run(x, blocks, cos_b, sin_b, keep)
        aux_parts, meter_parts = [], []
        for start, stop in bounds:
            blk = jax.tree.map(lambda p: p[start:stop], blocks)
            if param_sync is not None:
                blk = param_sync(blk)
            kk = keep[start:stop] if keep is not None else None
            x, (aux, meters) = run(x, blk, cos_b, sin_b, kk)
            aux_parts.append(aux)
            meter_parts.append(meters)
        return x, (jnp.concatenate([a.reshape(-1) for a in aux_parts]),
                   _cat_meters(meter_parts))

    if random_ltd_idx is not None and cfg.pos_emb == "alibi":
        raise NotImplementedError(
            "random-LTD with ALiBi positions is unsupported: the middle-stack "
            "bias would be computed from compacted indices (rope tables are "
            "index-gathered; ALiBi distances cannot be)")
    R, states = full_cfg.loop_passes, []
    for t in range(R):     # once; a looped stack: the SAME leaves every pass
        with pass_scope(full_cfg, t):
            for key, seg in lead:
                xs = params[key]
                if with_pld:
                    xs = (xs, pld_keep[:seg.num_layers])
                    pld_keep = pld_keep[seg.num_layers:]
                x, _ = lax.scan(make_body(cos, sin, with_pld, seg), x, xs)
            if random_ltd_idx is None or L < 3:
                x, (auxes, meters) = run_chunked(x, params["blocks"], cos,
                                                 sin, pld_keep)
                aux_total = jnp.sum(auxes)
            else:
                blk = params["blocks"]
                first = jax.tree.map(lambda p: p[:1], blk)
                middle = jax.tree.map(lambda p: p[1:L - 1], blk)
                last = jax.tree.map(lambda p: p[L - 1:], blk)
                k1 = k2 = k3 = None
                if with_pld:
                    k1, k2, k3 = (pld_keep[:1], pld_keep[1:L - 1],
                                  pld_keep[L - 1:])
                cos_k = sin_k = None
                if cos is not None:
                    cos_k, sin_k = cos[random_ltd_idx], sin[random_ltd_idx]
                x, (a1, m1) = run(x, first, cos, sin, k1)
                xk = jnp.take(x, random_ltd_idx, axis=1)      # gather kept
                xk, (a2, m2) = run(xk, middle, cos_k, sin_k, k2)
                x = x.at[:, random_ltd_idx].set(xk)           # scatter back
                x, (a3, m3) = run(x, last, cos, sin, k3)
                aux_total = jnp.sum(a1) + jnp.sum(a2) + jnp.sum(a3)
                meters = _cat_meters([m1, m2, m3])
        if R > 1:
            # the final norm after EVERY pass: it feeds the next
            x = loop_norm(x, params, cfg)
            states.append(x)

    _emit_meters(moe_held=meters)
    head = _lm_head_of(params, full_cfg)
    if R > 1:
        # the gates choose which pass's state feeds the head; it is normed
        return loop_exit(params, jnp.stack(states, axis=-2),
                         full_cfg)[0], head, aux_total
    x = _norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    return x, head, aux_total


def _cat_meters(parts: Sequence[Optional[jax.Array]]) -> Optional[jax.Array]:
    """Runs of layers' meters ``[layers of the run, ...]`` as one array in
    layer order; None where no layer of any run meters itself."""
    parts = [p for p in parts if p is not None]
    return jnp.concatenate(parts) if parts else None


def kind_runs(kinds: Sequence[str]) -> List[Tuple[int, Tuple[str, ...], int]]:
    """A run of layer kinds as scans: ``(first layer, period, steps)``
    each, in order. The period is the shortest the run repeats with from
    its first layer on (any rotation of the model's own: a run may start
    inside one); layers past the last whole period are a run of their own
    (a stack need not end on a period's boundary)."""
    out, first, kinds = [], 0, tuple(kinds)
    while kinds:
        p = next(p for p in range(1, len(kinds) + 1)
                 if all(kinds[i] == kinds[i - p]
                        for i in range(p, len(kinds))))
        steps = len(kinds) // p
        out.append((first, kinds[:p], steps))
        first, kinds = first + p * steps, kinds[p * steps:]
    return out


def scan_periods(body_of: Callable, carry, blocks: PyTree,
                 kinds: Sequence[str], by_step: bool = False):
    """Scan a segment of standard blocks a PERIOD of its kinds at a time:
    ``body_of(period, first layer of the run)(carry, lps)`` takes the
    period's layers' parameters stacked ``[len(period), ...]``
    (:func:`period_layer` takes one layer's out of them). One scan a run of
    :func:`kind_runs`; where a step's leaves come from is read off the
    shapes, a leaf at a time:

    - a run that is the WHOLE of a leaf ``[layers, ...]``: the leaf read as
      ``[steps, period, ...]`` is the scan's operand (no copy). Leaves
      stacked by mixer (``blocks["attn"]`` / ``["conv"]`` / ``["mamba2"]``)
      count their own layers: those ahead of the run, those of a period (a
      run may hold none: its steps then take no such leaf);
    - ``by_step``: the leaves are stacked by step already (a homogeneous
      stack's, one layer a step; ``blocks[kind]`` of a stack whose kinds
      are whole layers of their own): the scan's operand as they are;
    - a CUT, a run that is part of its leaf. Of one step: the static slice
      ``a[ahead:ahead + per]`` is the operand (a loop of one trip is
      inlined and the slice feeds its reader). Of more: the leaf stays
      WHOLE outside the loop and step ``s`` takes its ``per`` layers at
      ``ahead + s * per`` inside the body, which is what ``lax.scan`` does
      with an operand and what XLA reads in place; a slice ahead of the
      loop is the operand of a ``while`` and is materialised: the run's
      weights copied every call (PR 62: 0.97 GB a tick). Under
      ``jax.grad`` the in-place form is right and wasteful: each step's
      cotangent is padded to the whole leaf before it is added (no
      training cell has such a run)."""
    kinds, outs = tuple(kinds), []
    for first, period, steps in kind_runs(kinds):
        body, xs = body_of(period, first), blocks
        if not by_step:
            # mixer -> (its layers ahead of the run, its layers a period)
            span = {m: (sum(mixer_of(k) == m for k in kinds[:first]),
                        sum(mixer_of(k) == m for k in period))
                    for m in MIXERS if m in blocks}
            leaves, tree = jax.tree_util.tree_flatten_with_path(
                {k: v for k, v in blocks.items()
                 if k not in span or span[k][1]})
            where = [span.get(path[0].key, (first, len(period)))
                     for path, _ in leaves]
            leaves = [a for _, a in leaves]
            # the scan's operands; None: a cut of several steps
            given = [a.reshape((steps, per) + a.shape[1:])
                     if per * steps == a.shape[0] else
                     a[ahead:ahead + per].reshape((1, per) + a.shape[1:])
                     if steps == 1 else None
                     for a, (ahead, per) in zip(leaves, where)]
            xs = tree.unflatten(given)
            if any(g is None for g in given):
                def body(carry, xs, body=body):
                    s, given = xs
                    return body(carry, tree.unflatten([
                        lax.dynamic_slice_in_dim(a, ahead + s * per, per)
                        if g is None else g for g, a, (ahead, per) in zip(
                            tree.flatten_up_to(given), leaves, where)]))

                xs = jnp.arange(steps), xs
        carry, out = lax.scan(body, carry, xs)
        outs.append(out)
    return carry, outs


def period_layer(lps: PyTree, period: Sequence[str], i: int) -> PyTree:
    """Layer ``i`` of a period's parameters as :func:`scan_periods` hands
    them to a step, as ONE flat tree: the leaves every layer has and, in a
    stack whose mixers' leaves are stacked apart, those of the layer's own
    mixer (the layer's index among its mixer's in the period). ``lps`` is
    ``[len(period), ...]`` a leaf whichever way it reached the step: a
    whole run's and a one-step cut's as the scan's operand, a cut of
    several steps' as the body's own slice of the whole leaf (``a[i]`` of
    it is then a slice of that slice, which XLA reads in place); a
    ``by_step`` stack's steps take their layers themselves and do not
    come here."""
    mixer = mixer_of(period[i])
    if mixer not in lps:
        return jax.tree.map(lambda a: a[i], lps)
    lp = jax.tree.map(lambda a: a[i],
                      {k: v for k, v in lps.items() if k not in MIXERS})
    j = sum(mixer_of(k) == mixer for k in period[:i])
    lp.update(jax.tree.map(lambda a: a[j], lps[mixer]))
    return lp


def _forward_blocks_of_kinds(params: PyTree, tokens: jax.Array,
                             cfg: TransformerConfig, constrain: Callable,
                             attention_fn: AttentionFn = None
                             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``forward_hidden`` of ``layer_kinds`` over the standard block
    (``cfg.standard_blocks``): each segment (leading dense layers, then
    the stack) scanned a period of its kinds at a time, ``_block_forward``
    told each layer's kind and handed its kind's rotary tables
    (``cfg.rope_of``; none for a kind without rotary). Remat is a LAYER's, not a period's: a
    step's backward holds one layer's intermediates, whatever the period."""
    attention_fn = attention_fn or dot_product_attention
    dt = cfg.compute_dtype
    B, S = tokens.shape
    with jax.named_scope("embed"):
        x = params["tok_emb"].astype(dt)[tokens]
        if cfg.emb_multiplier != 1.0:
            x = x * jnp.asarray(cfg.emb_multiplier, dt)
        x = constrain(x)
    # (kinds that share theta and scaling trace the same constants: XLA
    # keeps one table)
    ropes = {}
    for kind in dict.fromkeys(cfg.layer_kinds):    # in the stack's own order
        of = cfg.rope_of(kind) if cfg.pos_emb == "rope" else None
        ropes[kind] = rope_table(S, cfg.rope_dim, *of) if of \
            else (None, None)
    aux_total, meter_parts = jnp.float32(0.0), []
    for key, seg in cfg.segments:
        def body_of(period, _, seg=seg):
            def layer_of(kind):
                def layer(x, lp):
                    y, a, meter = _block_forward(x, lp, seg, *ropes[kind],
                                                 attention_fn, kind)
                    return constrain(y), (a, meter)

                return _remat_wrap(layer, cfg.remat)

            layers = [layer_of(kind) for kind in period]

            def body(x, lps):
                aux, meters = jnp.float32(0.0), []
                for i, layer in enumerate(layers):
                    x, (a, meter) = layer(x, period_layer(lps, period, i))
                    aux = aux + a
                    meters.append(meter)
                # the layers of a period that meter themselves (all or
                # none of them; of a stack of single sublayers its ``ffn``
                # layers)
                meters = [m for m in meters if m is not None]
                return x, (aux, jnp.stack(meters) if meters else None)

            return body

        x, outs = scan_periods(body_of, x, params[key], seg.layer_kinds)
        aux_total = aux_total + sum(jnp.sum(a) for a, _ in outs)
        # a run's meters [steps, period, ...] are its layers' in order
        meter_parts += [m if m is None else m.reshape((-1,) + m.shape[2:])
                        for _, m in outs]
    _emit_meters(moe_held=_cat_meters(meter_parts))
    x = _norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    return x, _lm_head_of(params, cfg), aux_total


def _forward_kinds(params: PyTree, tokens: jax.Array, cfg: TransformerConfig,
                   constrain: Callable) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``forward_hidden`` of a stack of ``layer_kinds``: the whole
    sequence at once, no cache. The state-space layers see the batch as
    ``B * S`` rows in runs of ``S`` from position 0 (``models/hybrid.py``);
    attention is plain jnp under an explicit mask, whatever
    ``attention_fn`` the caller named: the family's differential heads of
    ``2 D`` columns over a cache two layers share have no flash kernel
    (the window is not what stands in the way: the kernel has one)."""
    from deepspeed_tpu.models import hybrid as HY

    dt = cfg.compute_dtype
    B, S = tokens.shape
    H, N, K, D = cfg.hidden_size, cfg.num_heads, cfg.kv_heads, cfg.head_dim
    with jax.named_scope("embed"):
        x = constrain(params["tok_emb"].astype(dt)[tokens])
    positions = jnp.tile(jnp.arange(S, dtype=jnp.int32), B)
    runs = HY.runs_of(jnp.repeat(jnp.arange(B, dtype=jnp.int32), S),
                      positions)
    conv0 = (jnp.zeros((B * S, cfg.ssm_inner), dt),) * (cfg.ssm_conv - 1)
    ssm0 = jnp.zeros((B * S, cfg.ssm_state, cfg.ssm_inner), jnp.float32)

    def attend(h, lp, kind, layer, shared):
        q = HY.paired_queries((h @ lp["wq"].astype(dt)).reshape(B * S, N, D))
        if kind != "cross":
            shared = tuple(
                HY.paired_cache((h @ lp[w].astype(dt)).reshape(B, S, K, D))
                for w in ("wk", "wv"))
        o = HY.windowed_attention(
            q.reshape(B, S, N, 2 * D), *shared, D ** -0.5,
            cfg.attn_window if kind == "window" else 0)
        o = HY.differential_merge(o.reshape(B * S, N, 2 * D), lp, layer,
                                  cfg.norm_eps)
        return o.astype(dt), shared

    def make_body(seg):
        def body(carry, xs):
            x, memory, shared = carry
            lps, step = xs
            for i, kind in enumerate(seg.period):
                lp = lps[kind]
                h = _norm(x, lp["ln1"], seg.norm, seg.norm_eps).reshape(
                    B * S, H)
                if kind == "mamba":
                    with jax.named_scope("ssm"):
                        mix, memory, _, _ = HY.mamba(h, lp, seg, runs, conv0,
                                                     ssm0)
                elif kind == "gmu":
                    with jax.named_scope("gmu"):
                        mix = HY.gmu(h, lp, memory)
                else:
                    with jax.named_scope("attn"):
                        mix, shared = attend(
                            h, lp, kind,
                            seg.first_dense_layers + 2 * step + i, shared)
                x = x + (mix @ lp["wo"].astype(dt)).reshape(B, S, H)
                with jax.named_scope("mlp"):
                    h2 = _norm(x, lp["ln2"], seg.norm, seg.norm_eps)
                    x = constrain(x + _ffn(h2, lp, seg)[0])
            return (x, memory, shared), None

        return _remat_wrap(body, cfg.remat)

    kv = jnp.zeros((B, S, K // 2, 2 * D), dt)
    carry = (x, jnp.zeros((B * S, cfg.ssm_inner), dt), (kv, kv))
    for key, seg in cfg.segments:
        carry, _ = lax.scan(make_body(seg), carry,
                            (params[key], jnp.arange(seg.num_layers)))
    x = _norm(carry[0], params["final_norm"], cfg.norm, cfg.norm_eps)
    return x, _lm_head_of(params, cfg), jnp.float32(0.0)


def forward(params: PyTree, tokens: jax.Array, cfg: TransformerConfig,
            attention_fn: Optional[AttentionFn] = None,
            activation_constraint: Optional[Callable[[jax.Array], jax.Array]] = None
            ) -> jax.Array:
    """tokens [B, S] int32 → logits [B, S, vocab] in fp32."""
    x, head, _ = forward_hidden(params, tokens, cfg, attention_fn,
                                activation_constraint)
    logits = lm_logits(x, head, cfg)
    if cfg.lm_head_bias:
        logits = logits + params["lm_head_b"].astype(jnp.float32)
    return logits


# --------------------------------------------------------------------------- #
# KV-cache decode path (inference)
# --------------------------------------------------------------------------- #

def apply_rope_at(x: jax.Array, cos_table: jax.Array, sin_table: jax.Array,
                  positions: jax.Array) -> jax.Array:
    """Rotate x [B, T, N, D] at absolute ``positions`` [B, T]; partial rotary
    (tables narrower than D/2) passes trailing dims through."""
    rot = 2 * cos_table.shape[-1]
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    d2 = rot // 2
    cos = cos_table[positions][:, :, None, :].astype(x.dtype)  # [B,T,1,rot/2]
    sin = sin_table[positions][:, :, None, :].astype(x.dtype)
    x1, x2 = x_rot[..., :d2], x_rot[..., d2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    if x_pass.shape[-1]:
        out = jnp.concatenate([out, x_pass], axis=-1)
    return out


def init_kv_cache(cfg: TransformerConfig, batch_size: int, max_len: int,
                  dtype=None) -> Dict[str, jax.Array]:
    """Layer-stacked KV cache (the blocked-KV analog of the reference's
    ``inference/v2/ragged/kv_cache.py`` — slot-contiguous, length-masked)."""
    dt = dtype or cfg.compute_dtype
    if cfg.mla:
        # MLA caches the LATENT: c_kv [kvr] + shared rope key [dr] per token
        # (the DeepSeek small-cache trick) — stored under the same "k"/"v"
        # keys (head dim 1) so the decode scan plumbing is unchanged
        L, B, M = cfg.num_layers, batch_size, max_len
        return {"k": jnp.zeros((L, B, M, 1, cfg.kv_lora_rank), dt),
                "v": jnp.zeros((L, B, M, 1, cfg.qk_rope_head_dim), dt)}
    shape = (cfg.num_layers, batch_size, max_len, cfg.kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def cached_attention(q: jax.Array, kc: jax.Array, vc: jax.Array,
                     positions: jax.Array,
                     alibi: Optional[jax.Array] = None) -> jax.Array:
    """q [B,T,N,D] at abs ``positions`` [B,T] against cache [B,M,K,D]; causal
    mask = cache index <= query position (fp32 softmax). ``alibi``: [N] slopes;
    bias = slope * (cache_pos - query_pos)."""
    B, T, N, D = q.shape
    M, K = kc.shape[1], kc.shape[2]
    if K != N:
        kc = jnp.repeat(kc, N // K, axis=2)
        vc = jnp.repeat(vc, N // K, axis=2)
    scale = 1.0 / math.sqrt(D)
    scores = jnp.einsum("btnd,bmnd->bntm", q, kc).astype(jnp.float32) * scale
    if alibi is not None:
        rel = (jnp.arange(M)[None, None, :]
               - positions[:, :, None]).astype(jnp.float32)   # [B,T,M]
        scores = scores + alibi[None, :, None, None] * rel[:, None]
    mask = jnp.arange(M)[None, None, None, :] <= positions[:, None, :, None]
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bntm,bmnd->btnd", probs, vc)


def forward_decode(params: PyTree, tokens: jax.Array,
                   cache: Dict[str, jax.Array], pos: jax.Array,
                   cfg: TransformerConfig
                   ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Incremental forward: write new tokens' K/V into the cache and attend.

    tokens [B, T] arriving at positions ``pos[b] .. pos[b]+T-1``; pos [B] int32.
    Works for prefill (T = padded prompt len, pos = 0) and decode (T = 1).
    Returns (logits [B, T, vocab] fp32, updated cache). Parity: the reference's
    inference transformer containers (``module_inject/containers``,
    ``inference/v2/model_implementations``).
    """
    _require_one_stack(cfg, "forward_decode (the v1 slot engine)")
    B, T = tokens.shape
    dt = cfg.compute_dtype
    M = cache["k"].shape[2]
    positions = pos[:, None] + jnp.arange(T)[None]          # [B, T]

    x = params["tok_emb"].astype(dt)[tokens]
    if cfg.pos_emb == "learned":
        x = x + params["pos_emb"].astype(dt)[positions]
    if cfg.emb_norm:
        x = _norm(x, params["emb_norm"], cfg.norm, cfg.norm_eps)

    cos_t = sin_t = None
    if cfg.pos_emb == "rope":
        rd = cfg.qk_rope_head_dim if cfg.mla else cfg.rope_dim
        cos_t, sin_t = rope_table(M, rd, cfg.rope_theta, cfg.rope_scaling_dict)
    slopes = (alibi_slopes(cfg.num_heads) * cfg.alibi_bias_scale
              if cfg.pos_emb == "alibi" else None)

    def write(c, new, p):
        return lax.dynamic_update_slice(c, new, (p, 0, 0))

    def body(x, scans):
        from deepspeed_tpu.ops.quantization import dequant_params

        lp, kc, vc = scans
        lp = dequant_params(lp, dt)   # weight-only quant: per-layer dequant
        h = _norm(x, lp["ln1"], cfg.norm, cfg.norm_eps)

        if cfg.mla:
            # kc holds c_kv [B,M,1,kvr]; vc holds the post-rope shared key
            # [B,M,1,dr]. Write the new latents, then: DECODE (T==1) runs
            # WEIGHT-ABSORBED attention directly on the latent cache (W_uk
            # folded into q, W_uv into the output — the per-step k/v
            # re-expansion never happens); PREFILL (T>1) expands once and
            # attends normally — absorbed scores cost O(T·M·N·kvr) which
            # loses to the one-time O(M) expansion for long prompts.
            rope_fn = lambda t: apply_rope_at(t, cos_t, sin_t, positions)
            qf = _mla_q(h, lp, cfg, rope_fn)
            c_kv, k_pe = _mla_latents(h, lp, cfg, rope_fn)
            kc = jax.vmap(write)(kc, c_kv[:, :, None, :].astype(kc.dtype), pos)
            vc = jax.vmap(write)(vc, k_pe.astype(vc.dtype), pos)
            if T == 1:
                attn = _mla_absorbed_attention(
                    qf, kc[:, :, 0].astype(dt), vc[:, :, 0].astype(dt), lp,
                    cfg, positions, cfg.mla_scale_mult)
            else:
                k_full, v_full = _mla_expand(
                    kc[:, :, 0].astype(dt), vc.astype(dt), lp, cfg)
                if cfg.mla_scale_mult != 1.0:
                    qf = qf * jnp.asarray(cfg.mla_scale_mult, qf.dtype)
                attn = cached_attention(qf, k_full, v_full, positions)
            attn = attn.reshape(B, T, cfg.num_heads * cfg.v_head_dim)
            x = x + attn @ lp["wo"].astype(dt)
            h2 = _norm(x, lp["ln2"], cfg.norm, cfg.norm_eps)
            down, _ = _ffn(h2, lp, cfg)
            return x + down, (kc, vc)

        def proj(name, shape):
            w = lp[f"w{name}"].astype(dt)
            out = h @ w
            if (cfg.attn_bias_enabled if name in ("q", "k", "v")
                    else cfg.use_bias):
                out = out + lp[f"b{name}"].astype(dt)
            return out.reshape(shape)

        q = proj("q", (B, T, cfg.num_heads, cfg.head_dim))
        k = proj("k", (B, T, cfg.kv_heads, cfg.head_dim))
        v = proj("v", (B, T, cfg.kv_heads, cfg.head_dim))
        if cfg.qk_norm:
            q = _head_rmsnorm(q, lp["q_norm"], cfg.norm_eps)
            k = _head_rmsnorm(k, lp["k_norm"], cfg.norm_eps)
        if cfg.pos_emb == "rope":
            q = apply_rope_at(q, cos_t, sin_t, positions)
            k = apply_rope_at(k, cos_t, sin_t, positions)
        kc = jax.vmap(write)(kc, k.astype(kc.dtype), pos)
        vc = jax.vmap(write)(vc, v.astype(vc.dtype), pos)
        attn = cached_attention(q, kc, vc, positions, alibi=slopes)
        attn = attn.reshape(B, T, cfg.num_heads * cfg.head_dim)
        attn_out = attn @ lp["wo"].astype(dt)
        if cfg.use_bias:
            attn_out = attn_out + lp["bo"].astype(dt)
        if cfg.parallel_block:
            h2 = h if cfg.shared_parallel_norm else \
                _norm(x, lp["ln2"], cfg.norm, cfg.norm_eps)
            down, _ = _ffn(h2, lp, cfg)
            return x + attn_out + down, (kc, vc)
        x = x + attn_out
        h2 = _norm(x, lp["ln2"], cfg.norm, cfg.norm_eps)
        down, _ = _ffn(h2, lp, cfg)
        return x + down, (kc, vc)

    x, (new_k, new_v) = lax.scan(body, x, (params["blocks"], cache["k"], cache["v"]))
    x = _norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    head = _lm_head_of(params, cfg)
    logits = head_matmul(x, head.astype(x.dtype))
    if cfg.lm_head_bias:
        logits = logits + params["lm_head_b"].astype(jnp.float32)
    return logits, {"k": new_k, "v": new_v}


def _pipeline_parts(params: PyTree, tokens: jax.Array, cfg: TransformerConfig,
                    mesh, n_micro, attention_fn, activation_constraint,
                    loss_mask):
    """Shared scaffolding for the GPipe and 1F1B schedules: embedding,
    microbatched inputs, extra params, stage_fn and finalize_fn. Both
    schedules MUST consume this so the 1F1B-vs-GPipe parity tests stay
    meaningful."""
    _require_one_stack(cfg, "the pipeline schedule")
    from deepspeed_tpu.comm.mesh import PIPE_AXIS, get_mesh_manager
    from deepspeed_tpu.parallel.pipeline import microbatch

    if mesh is None:
        mesh = get_mesh_manager().mesh
    n_stages = mesh.shape[PIPE_AXIS]
    if cfg.num_layers % n_stages != 0:
        raise ValueError(
            f"num_layers {cfg.num_layers} not divisible by pipe={n_stages}")
    if cfg.lm_head_bias:
        raise NotImplementedError(
            "lm_head_bias unsupported in the pipelined path")
    attention_fn = attention_fn or dot_product_attention
    constrain = activation_constraint or (lambda x: x)
    dt = cfg.compute_dtype
    B, S = tokens.shape
    M = n_micro or n_stages

    def embed(embp, toks):
        e = embp["tok_emb"].astype(dt)[toks]
        if cfg.pos_emb == "learned":
            e = e + embp["pos_emb"].astype(dt)[:S][None]
        if cfg.emb_norm:
            e = _norm(e, embp["emb_norm"], cfg.norm, cfg.norm_eps)
        return constrain(e)

    emb_keys = ["tok_emb"]
    if cfg.pos_emb == "learned":
        emb_keys.append("pos_emb")
    if cfg.emb_norm:
        emb_keys.append("emb_norm")
    embp = {k: params[k] for k in emb_keys}

    x = embed(embp, tokens)
    cos = sin = None
    if cfg.pos_emb == "rope":
        rd = cfg.qk_rope_head_dim if cfg.mla else cfg.rope_dim
        cos, sin = rope_table(S, rd, cfg.rope_theta, cfg.rope_scaling_dict)

    head = _lm_head_of(params, cfg)
    inputs = {"x": microbatch(x, M), "tokens": microbatch(tokens, M)}
    if loss_mask is not None:
        inputs["loss_mask"] = microbatch(loss_mask, M)
    extra = {"final_norm": params["final_norm"], "head": head}
    if cos is not None:
        extra["cos"], extra["sin"] = cos, sin

    def stage_fn(x_in, blocks_l, ex):
        def body(carry, lp):
            y, aux, _ = _block_forward(carry, lp, cfg, ex.get("cos"),
                                       ex.get("sin"), attention_fn)
            return constrain(y), aux

        body = _remat_wrap(body, cfg.remat)
        y, auxes = lax.scan(body, x_in, blocks_l)
        return y, jnp.sum(auxes)

    def logits_fn(y, ex):
        """ONE head implementation for every pipeline schedule (training
        loss and forward-only inference must agree). Plain dot (not the
        custom-vjp head_matmul): inside the pipe shard_map the replicated
        head's cotangent needs the automatic varying->replicated psum,
        which a custom_vjp would bypass."""
        h = _norm(y, ex["final_norm"], cfg.norm, cfg.norm_eps)
        return jnp.matmul(h, ex["head"].astype(h.dtype),
                          preferred_element_type=jnp.float32)

    def finalize_fn(y, micro, ex):
        return causal_lm_loss(logits_fn(y, ex), micro["tokens"],
                              micro.get("loss_mask"))

    return mesh, M, embed, embp, inputs, extra, stage_fn, finalize_fn, \
        logits_fn


def pipelined_lm_loss(params: PyTree, tokens: jax.Array, cfg: TransformerConfig,
                      mesh=None, n_micro: Optional[int] = None,
                      attention_fn: Optional[AttentionFn] = None,
                      activation_constraint: Optional[Callable] = None,
                      loss_mask: Optional[jax.Array] = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """Causal-LM loss with the layer stack pipelined over the 'pipe' mesh axis
    (GPipe forward wavefront — the InferenceSchedule analog; backward via
    autodiff). Returns (loss, moe_aux).
    See ``parallel/pipeline.py`` (reference ``runtime/pipe/engine.py:337``).
    """
    from deepspeed_tpu.parallel.pipeline import pipelined_apply

    mesh, M, _, _, inputs, extra, stage_fn, finalize_fn, _ = _pipeline_parts(
        params, tokens, cfg, mesh, n_micro, attention_fn,
        activation_constraint, loss_mask)
    return pipelined_apply(inputs, params["blocks"], extra, stage_fn,
                           finalize_fn, mesh)


def pipelined_lm_logits(params: PyTree, tokens: jax.Array,
                        cfg: TransformerConfig, mesh=None,
                        n_micro: Optional[int] = None,
                        attention_fn: Optional[AttentionFn] = None,
                        activation_constraint: Optional[Callable] = None
                        ) -> jax.Array:
    """Forward-only pipelined logits (reference ``runtime/pipe/schedule.py:135
    InferenceSchedule``): batched inference across the 'pipe' mesh axis —
    fill wavefront only, no backward machinery. Returns [B, S, vocab] fp32.
    """
    from deepspeed_tpu.parallel.pipeline import pipelined_infer

    mesh, M, _, _, inputs, extra, stage_fn, _, logits_fn = _pipeline_parts(
        params, tokens, cfg, mesh, n_micro, attention_fn,
        activation_constraint, None)

    out = pipelined_infer(inputs, params["blocks"], extra, stage_fn,
                          logits_fn, mesh)                # [M, B/M, S, V]
    B, S = tokens.shape
    return out.reshape(B, S, -1)


def pipelined_lm_loss_and_grads(params: PyTree, tokens: jax.Array,
                                cfg: TransformerConfig, mesh=None,
                                n_micro: Optional[int] = None,
                                attention_fn: Optional[AttentionFn] = None,
                                activation_constraint: Optional[Callable] = None,
                                loss_mask: Optional[jax.Array] = None,
                                loss_scale=None
                                ) -> Tuple[jax.Array, PyTree]:
    """1F1B pipelined loss AND grads (reference ``runtime/pipe/schedule.py:189``
    ``TrainSchedule``): explicit backward schedule with O(P) activation
    residency instead of letting autodiff reverse the GPipe wavefront (O(M)).
    Returns (loss incl. any MoE aux term, grads w.r.t. ``params`` — same
    tree, fp32 leaves). Not supported: ``lm_head_bias`` models (same as the
    GPipe path)."""
    from deepspeed_tpu.parallel.pipeline import pipelined_train_1f1b

    mesh, M, embed, embp, inputs, extra, stage_fn, finalize_fn, _ = \
        _pipeline_parts(params, tokens, cfg, mesh, n_micro, attention_fn,
                        activation_constraint, loss_mask)
    dt = cfg.compute_dtype

    def input_grad_fn(dx, micro, acc):
        if dx is None:   # zeros accumulators (also defines the out structure)
            return jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), embp)
        _, vjp = jax.vjp(lambda ep: embed(ep, micro["tokens"]), embp)
        (d,) = vjp(dx.astype(dt))
        return jax.tree.map(lambda a, g: a + g.astype(jnp.float32), acc, d)

    aux_seed = None
    if cfg.n_experts > 0:
        aux_seed = jnp.float32(cfg.moe_aux_coef) * (
            loss_scale if loss_scale is not None else 1.0)

    loss, aux, gblocks, gextra, gemb = pipelined_train_1f1b(
        inputs, params["blocks"], extra, stage_fn, finalize_fn, input_grad_fn,
        mesh, loss_scale=loss_scale, aux_seed=aux_seed)
    if cfg.n_experts > 0:
        # keep the reported loss comparable with the GPipe path (loss_fn
        # adds the aux term there)
        loss = loss + cfg.moe_aux_coef * aux

    grads: Dict[str, Any] = {"blocks": gblocks,
                             "final_norm": gextra["final_norm"]}
    g_tok = gemb["tok_emb"]
    if cfg.tie_embeddings:
        g_tok = g_tok + gextra["head"].T
    else:
        grads["lm_head"] = gextra["head"]
    grads["tok_emb"] = g_tok
    if cfg.pos_emb == "learned":
        grads["pos_emb"] = gemb["pos_emb"]
    if cfg.emb_norm:
        grads["emb_norm"] = gemb["emb_norm"]
    missing = set(params) - set(grads)
    if missing:
        raise NotImplementedError(
            f"pipelined grads missing for param groups {sorted(missing)}")
    grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
    return loss, grads


def fused_lm_loss(hidden: jax.Array, head: jax.Array, tokens: jax.Array,
                  loss_mask: Optional[jax.Array] = None,
                  logits_divisor: float = 1.0) -> jax.Array:
    """Head projection + next-token CE with a custom VJP tuned for HBM.

    torch-autocast semantics (the reference's fp16/bf16 engines compute
    logits in the low-precision dtype and CE upcasts for the softmax —
    ``torch.nn.CrossEntropyLoss`` under ``autocast``): logits live in the
    COMPUTE dtype (bf16), softmax statistics accumulate in fp32. vs the
    exact-fp32-logits path (``head_matmul`` + ``causal_lm_loss``) this
    halves every [B,S,V] buffer and the custom backward materializes ONE
    bf16 grad-logits array (softmax − onehot fused into its producing
    pass) instead of AD's fp32 grad + scatter-add + convert chain.
    Loss delta vs the exact path is the bf16 logit rounding (~1e-3),
    identical in class to the r2 ``head_matmul`` bf16-cotangent change.
    ``logits_divisor`` (``TransformerConfig.logits_divisor``) divides the
    logits ahead of the softmax, and their gradient on the way back."""
    B, S, H = hidden.shape
    mask = (jnp.ones((B, S), jnp.float32) if loss_mask is None
            else loss_mask.astype(jnp.float32))

    @jax.custom_vjp
    def _loss(x, w):
        return _fwd(x, w)[0]

    def _fwd(x, w):
        dt = x.dtype
        wc = w.astype(dt)
        xs = x[:, :-1]
        tgt = tokens[:, 1:]
        # one bf16 [B,S-1,V] buffer; the f32-accumulated matmul casts in
        # its epilogue, logsumexp upconverts in its reduce
        logits = divide_logits(
            jnp.matmul(xs, wc, preferred_element_type=jnp.float32),
            logits_divisor).astype(dt)
        lf = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(lf, axis=-1)
        picked = jnp.take_along_axis(lf, tgt[..., None], axis=-1)[..., 0]
        m = mask[:, 1:]
        cnt = jnp.maximum(jnp.sum(m), 1.0)
        loss = jnp.sum((logz - picked) * m) / cnt
        return loss, (logits, logz, xs, wc, tgt, m, cnt)

    def _bwd(res, g):
        logits, logz, xs, wc, tgt, m, cnt = res
        dt = xs.dtype
        coef = (m * (g / cnt))[..., None]
        one = (lax.broadcasted_iota(jnp.int32, logits.shape, 2)
               == tgt[..., None])
        # single fused pass: read bf16 logits, exp, subtract onehot, scale,
        # write bf16 grad-logits — feeds both backward matmuls
        gl = divide_logits(
            (jnp.exp(logits.astype(jnp.float32) - logz[..., None])
             - one.astype(jnp.float32)) * coef, logits_divisor).astype(dt)
        dx = jnp.matmul(gl, wc.T, preferred_element_type=jnp.float32) \
            .astype(dt)
        dw = jnp.matmul(xs.reshape(-1, xs.shape[-1]).T,
                        gl.reshape(-1, gl.shape[-1]),
                        preferred_element_type=jnp.float32)
        dx = jnp.pad(dx, ((0, 0), (0, 1), (0, 0)))
        return dx, dw.astype(head.dtype)

    _loss.defvjp(_fwd, _bwd)
    return _loss(hidden, head)


def causal_lm_loss(logits: jax.Array, tokens: jax.Array,
                   loss_mask: Optional[jax.Array] = None) -> jax.Array:
    """Next-token cross entropy; stable log-softmax in fp32."""
    targets = tokens[:, 1:]
    logits = logits[:, :-1]
    # logsumexp - picked (not log_softmax + gather): avoids materializing a
    # second [B, S, V] log-prob buffer — HBM bandwidth is the constraint here.
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - picked
    if loss_mask is not None:
        mask = loss_mask[:, 1:].astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


# --------------------------------------------------------------------------- #
# presets (names mirror the driver's milestone configs, BASELINE.md)
# --------------------------------------------------------------------------- #

PRESETS: Dict[str, TransformerConfig] = {
    "tiny": TransformerConfig(vocab_size=512, hidden_size=64, num_layers=2,
                              num_heads=4, max_seq_len=128),
    "tiny_llama": TransformerConfig(vocab_size=512, hidden_size=64, num_layers=2,
                                    num_heads=4, num_kv_heads=2, max_seq_len=128,
                                    pos_emb="rope", norm="rmsnorm",
                                    activation="swiglu", use_bias=False,
                                    tie_embeddings=False),
    "gpt2_125m": TransformerConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                                   num_heads=12, max_seq_len=1024),
    "gpt2_350m": TransformerConfig(vocab_size=50304, hidden_size=1024, num_layers=24,
                                   num_heads=16, max_seq_len=1024),
    "gpt2_1p5b": TransformerConfig(vocab_size=50304, hidden_size=1600, num_layers=48,
                                   num_heads=25, max_seq_len=1024),
    "bert_large": TransformerConfig(vocab_size=30528, hidden_size=1024, num_layers=24,
                                    num_heads=16, max_seq_len=512, causal=False),
    # llama-style model sized so fp32 master + Adam moments + fp32 grads fit a
    # single 16G-HBM chip (ZeRO-3 single-host bench; ~665M params ≈ 12G state)
    "llama_750m": TransformerConfig(vocab_size=32000, hidden_size=1536,
                                    num_layers=20, num_heads=12,
                                    ffn_hidden_size=4096, max_seq_len=2048,
                                    pos_emb="rope", norm="rmsnorm",
                                    activation="swiglu", use_bias=False,
                                    tie_embeddings=False),
    # mixtral-style MoE sized for one chip (4 experts, top-2)
    "moe_350m": TransformerConfig(vocab_size=32000, hidden_size=768,
                                  num_layers=12, num_heads=12, max_seq_len=1024,
                                  use_bias=False, n_experts=4, moe_top_k=2),
    # larger-expert MoE (~2B total / ~0.7B active): hidden 1536 (head_dim
    # 128) and expert-ffn 6144 put the grouped GEMM at shapes where it
    # matches dense matmul throughput (46-55 TF/s grouped vs 52 dense at
    # [32k,1536]x[8,1536,6144], same-harness A/B) — at moe_350m's K=768
    # shapes grouped and dense measure in the SAME low band, i.e. the
    # contraction itself is the ceiling (earlier-round figures, not
    # re-measured on this round's code)
    "moe_1b": TransformerConfig(vocab_size=32000, hidden_size=1536,
                                num_layers=12, num_heads=12, max_seq_len=1024,
                                ffn_hidden_size=6144, use_bias=False,
                                n_experts=8, moe_top_k=2),
    # north-star-scale single-chip model (BASELINE.md): ~3.1B params with
    # MXU-aligned shapes — head_dim 128, ffn 8192 (the open-llama-3B layout's
    # head_dim 100 wastes MXU lanes; this keeps every contraction 128-tiled)
    "llama_3b": TransformerConfig(vocab_size=32000, hidden_size=3072,
                                  num_layers=26, num_heads=24,
                                  ffn_hidden_size=8192, max_seq_len=2048,
                                  pos_emb="rope", norm="rmsnorm",
                                  activation="swiglu", use_bias=False,
                                  tie_embeddings=False),
    "llama2_7b": TransformerConfig(vocab_size=32000, hidden_size=4096, num_layers=32,
                                   num_heads=32, ffn_hidden_size=11008,
                                   max_seq_len=4096, pos_emb="rope", norm="rmsnorm",
                                   activation="swiglu", use_bias=False,
                                   tie_embeddings=False),
    "llama2_13b": TransformerConfig(vocab_size=32000, hidden_size=5120, num_layers=40,
                                    num_heads=40, ffn_hidden_size=13824,
                                    max_seq_len=4096, pos_emb="rope", norm="rmsnorm",
                                    activation="swiglu", use_bias=False,
                                    tie_embeddings=False),
    "tiny_moe": TransformerConfig(vocab_size=512, hidden_size=64, num_layers=2,
                                  num_heads=4, max_seq_len=128, use_bias=False,
                                  n_experts=4, moe_top_k=2),
    "mixtral_8x7b": TransformerConfig(vocab_size=32000, hidden_size=4096,
                                      num_layers=32, num_heads=32, num_kv_heads=8,
                                      ffn_hidden_size=14336, max_seq_len=4096,
                                      pos_emb="rope", norm="rmsnorm",
                                      activation="swiglu", use_bias=False,
                                      tie_embeddings=False,
                                      n_experts=8, moe_top_k=2),
}


def get_model_config(name: str, **overrides) -> TransformerConfig:
    if name not in PRESETS:
        raise ValueError(f"unknown model preset {name!r}; available: {sorted(PRESETS)}")
    return dataclasses.replace(PRESETS[name], **overrides)
