"""HF weight-import tests: converted zoo logits must match ``transformers``
outputs on randomly-initialized tiny configs (no network needed).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.hf_import import import_hf_model


def _compare_logits(hf_model, tokens_np, cfg, params, rtol=2e-4, atol=2e-4):
    hf_model.eval()
    with torch.no_grad():
        want = hf_model(torch.from_numpy(tokens_np)).logits.float().numpy()
    got = np.asarray(T.forward(params, jnp.asarray(tokens_np), cfg))
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol)


class TestGPT2Import:
    def test_logits_match(self):
        hf_cfg = transformers.GPT2Config(
            vocab_size=128, n_positions=64, n_embd=32, n_layer=2, n_head=4)
        torch.manual_seed(0)
        model = transformers.GPT2LMHeadModel(hf_cfg)
        cfg, params = import_hf_model(model)
        assert cfg.num_layers == 2 and cfg.pos_emb == "learned"
        tokens = np.random.default_rng(0).integers(0, 128, (2, 16), dtype=np.int32)
        _compare_logits(model, tokens, cfg, params)


class TestLlamaImport:
    @pytest.mark.parametrize("kv_heads", [4, 2])  # MHA and GQA
    def test_logits_match(self, kv_heads):
        hf_cfg = transformers.LlamaConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=kv_heads, max_position_embeddings=64,
            tie_word_embeddings=False)
        torch.manual_seed(1)
        model = transformers.LlamaForCausalLM(hf_cfg)
        cfg, params = import_hf_model(model)
        assert cfg.norm == "rmsnorm" and cfg.activation == "swiglu"
        tokens = np.random.default_rng(1).integers(0, 128, (2, 16), dtype=np.int32)
        _compare_logits(model, tokens, cfg, params)

    def test_generate_from_imported(self):
        hf_cfg = transformers.LlamaConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=4, max_position_embeddings=64,
            tie_word_embeddings=False)
        torch.manual_seed(2)
        model = transformers.LlamaForCausalLM(hf_cfg)
        cfg, params = import_hf_model(model)

        from deepspeed_tpu.inference import InferenceEngine

        eng = InferenceEngine(cfg, params=params, mesh=None)
        ours = eng.generate([[3, 1, 4, 1, 5]], max_new_tokens=6)[0]

        with torch.no_grad():
            hf_out = model.generate(
                torch.tensor([[3, 1, 4, 1, 5]]), max_new_tokens=6,
                do_sample=False, use_cache=True)
        theirs = hf_out[0, 5:].tolist()
        assert ours == theirs


class TestMistralImport:
    def test_logits_match(self):
        hf_cfg = transformers.MistralConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=64,
            sliding_window=None, tie_word_embeddings=False)
        torch.manual_seed(3)
        model = transformers.MistralForCausalLM(hf_cfg)
        cfg, params = import_hf_model(model)
        tokens = np.random.default_rng(3).integers(0, 128, (2, 16), dtype=np.int32)
        _compare_logits(model, tokens, cfg, params)


class TestMixtralImport:
    def test_logits_match_generous_capacity(self):
        """Mixtral MoE: with capacity >= all tokens nothing is dropped, so the
        dense-dispatch MoE must reproduce HF's per-token expert mixing."""
        hf_cfg = transformers.MixtralConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=64,
            num_local_experts=4, num_experts_per_tok=2,
            tie_word_embeddings=False)
        torch.manual_seed(4)
        model = transformers.MixtralForCausalLM(hf_cfg)
        cfg, params = import_hf_model(model)
        assert cfg.n_experts == 4 and cfg.moe_top_k == 2
        import dataclasses

        cfg = dataclasses.replace(cfg, moe_capacity_factor=64.0)
        tokens = np.random.default_rng(4).integers(0, 128, (2, 16), dtype=np.int32)
        _compare_logits(model, tokens, cfg, params, rtol=5e-4, atol=5e-4)


class TestQwen2Import:
    def test_logits_match(self):
        hf_cfg = transformers.Qwen2Config(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=64, tie_word_embeddings=False)
        torch.manual_seed(3)
        model = transformers.Qwen2ForCausalLM(hf_cfg)
        cfg, params = import_hf_model(model)
        assert cfg.qkv_bias and not cfg.use_bias
        tokens = np.random.default_rng(3).integers(0, 128, (2, 16), dtype=np.int32)
        _compare_logits(model, tokens, cfg, params)


class TestPhiImport:
    def test_logits_match(self):
        hf_cfg = transformers.PhiConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            max_position_embeddings=64, partial_rotary_factor=0.5)
        torch.manual_seed(4)
        model = transformers.PhiForCausalLM(hf_cfg)
        cfg, params = import_hf_model(model)
        assert cfg.parallel_block and cfg.shared_parallel_norm
        assert cfg.rope_dim == 4  # head_dim 8 * 0.5
        tokens = np.random.default_rng(4).integers(0, 128, (2, 16), dtype=np.int32)
        _compare_logits(model, tokens, cfg, params)


class TestPhi3Import:
    def test_logits_match(self):
        hf_cfg = transformers.Phi3Config(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=64, tie_word_embeddings=False,
            pad_token_id=0)
        torch.manual_seed(5)
        model = transformers.Phi3ForCausalLM(hf_cfg)
        cfg, params = import_hf_model(model)
        tokens = np.random.default_rng(5).integers(0, 128, (2, 16), dtype=np.int32)
        _compare_logits(model, tokens, cfg, params)


class TestFalconImport:
    @pytest.mark.parametrize("new_arch,multi_query,alibi", [
        (False, True, False),   # falcon-7b style: MQA, shared norm, rope
        (True, False, False),   # falcon-40b style: GQA groups, dual norms
        (False, False, True),   # falcon-rw style: MHA + alibi
    ])
    def test_logits_match(self, new_arch, multi_query, alibi):
        hf_cfg = transformers.FalconConfig(
            vocab_size=128, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=4, num_kv_heads=2 if new_arch else None,
            new_decoder_architecture=new_arch, multi_query=multi_query,
            alibi=alibi, parallel_attn=True, bias=False,
            max_position_embeddings=64)
        torch.manual_seed(6)
        model = transformers.FalconForCausalLM(hf_cfg)
        cfg, params = import_hf_model(model)
        tokens = np.random.default_rng(6).integers(0, 128, (2, 16), dtype=np.int32)
        _compare_logits(model, tokens, cfg, params, rtol=5e-4, atol=5e-4)


class TestOPTImport:
    def test_logits_match(self):
        hf_cfg = transformers.OPTConfig(
            vocab_size=128, hidden_size=32, ffn_dim=64, num_hidden_layers=2,
            num_attention_heads=4, max_position_embeddings=64,
            word_embed_proj_dim=32, activation_function="relu",
            do_layer_norm_before=True)
        torch.manual_seed(7)
        model = transformers.OPTForCausalLM(hf_cfg)
        cfg, params = import_hf_model(model)
        assert cfg.activation == "relu"
        tokens = np.random.default_rng(7).integers(0, 128, (2, 16), dtype=np.int32)
        _compare_logits(model, tokens, cfg, params)


class TestBloomImport:
    def test_logits_match(self):
        hf_cfg = transformers.BloomConfig(
            vocab_size=128, hidden_size=32, n_layer=2, n_head=4)
        torch.manual_seed(8)
        model = transformers.BloomForCausalLM(hf_cfg)
        cfg, params = import_hf_model(model)
        assert cfg.pos_emb == "alibi" and cfg.emb_norm
        tokens = np.random.default_rng(8).integers(0, 128, (2, 16), dtype=np.int32)
        _compare_logits(model, tokens, cfg, params)


class TestGPTNeoXImport:
    @pytest.mark.parametrize("parallel", [True, False])
    def test_logits_match(self, parallel):
        hf_cfg = transformers.GPTNeoXConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            max_position_embeddings=64, rotary_pct=0.25,
            use_parallel_residual=parallel, tie_word_embeddings=False)
        torch.manual_seed(9)
        model = transformers.GPTNeoXForCausalLM(hf_cfg)
        cfg, params = import_hf_model(model)
        assert cfg.parallel_block == parallel
        tokens = np.random.default_rng(9).integers(0, 128, (2, 16), dtype=np.int32)
        _compare_logits(model, tokens, cfg, params)


class TestDecodeParityNewArchs:
    """forward_decode must agree with forward for the new family features
    (parallel blocks, shared norms, alibi, partial rotary, head bias)."""

    @pytest.mark.parametrize("maker", ["phi", "bloom", "neox", "falcon7b"])
    def test_prefill_matches_forward(self, maker):
        if maker == "phi":
            hf_cfg = transformers.PhiConfig(
                vocab_size=128, hidden_size=32, intermediate_size=64,
                num_hidden_layers=2, num_attention_heads=4,
                max_position_embeddings=64, partial_rotary_factor=0.5)
            torch.manual_seed(10)
            model = transformers.PhiForCausalLM(hf_cfg)
        elif maker == "bloom":
            hf_cfg = transformers.BloomConfig(
                vocab_size=128, hidden_size=32, n_layer=2, n_head=4)
            torch.manual_seed(11)
            model = transformers.BloomForCausalLM(hf_cfg)
        elif maker == "neox":
            hf_cfg = transformers.GPTNeoXConfig(
                vocab_size=128, hidden_size=32, intermediate_size=64,
                num_hidden_layers=2, num_attention_heads=4,
                max_position_embeddings=64, rotary_pct=0.25,
                use_parallel_residual=True, tie_word_embeddings=False)
            torch.manual_seed(12)
            model = transformers.GPTNeoXForCausalLM(hf_cfg)
        else:
            hf_cfg = transformers.FalconConfig(
                vocab_size=128, hidden_size=32, num_hidden_layers=2,
                num_attention_heads=4, new_decoder_architecture=False,
                multi_query=True, alibi=False, parallel_attn=True, bias=False,
                max_position_embeddings=64)
            torch.manual_seed(13)
            model = transformers.FalconForCausalLM(hf_cfg)
        cfg, params = import_hf_model(model)

        tokens = np.random.default_rng(20).integers(0, 128, (2, 8),
                                                    dtype=np.int32)
        full = np.asarray(T.forward(params, jnp.asarray(tokens), cfg))

        cache = T.init_kv_cache(cfg, batch_size=2, max_len=16)
        logits, cache = T.forward_decode(
            params, jnp.asarray(tokens), cache, jnp.zeros((2,), jnp.int32), cfg)
        np.testing.assert_allclose(np.asarray(logits), full, rtol=2e-4,
                                   atol=2e-4)

        # one decode step after prefill == forward on the extended sequence
        nxt = np.random.default_rng(21).integers(0, 128, (2, 1), dtype=np.int32)
        step_logits, _ = T.forward_decode(
            params, jnp.asarray(nxt), cache, jnp.full((2,), 8, jnp.int32), cfg)
        ext = np.concatenate([tokens, nxt], axis=1)
        full_ext = np.asarray(T.forward(params, jnp.asarray(ext), cfg))
        np.testing.assert_allclose(np.asarray(step_logits[:, 0]),
                                   full_ext[:, -1], rtol=2e-4, atol=2e-4)


class TestQwen2MoeImport:
    def _model(self):
        hf_cfg = transformers.Qwen2MoeConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            moe_intermediate_size=24, shared_expert_intermediate_size=40,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            num_experts=4, num_experts_per_tok=2,
            max_position_embeddings=64, tie_word_embeddings=False)
        torch.manual_seed(30)
        return transformers.Qwen2MoeForCausalLM(hf_cfg)

    def test_logits_match_generous_capacity(self):
        """Qwen2-MoE: shared expert + sigmoid shared gate + un-normalized
        top-k softmax routing (norm_topk_prob=False default)."""
        model = self._model()
        cfg, params = import_hf_model(model)
        assert cfg.n_experts == 4 and cfg.moe_shared_size == 40
        assert cfg.moe_shared_gate and not cfg.moe_route_norm
        assert cfg.moe_ffn == 24
        import dataclasses

        cfg = dataclasses.replace(cfg, moe_capacity_factor=64.0)
        tokens = np.random.default_rng(30).integers(0, 128, (2, 16),
                                                    dtype=np.int32)
        _compare_logits(model, tokens, cfg, params, rtol=5e-4, atol=5e-4)

    def test_heterogeneous_stack_rejected(self):
        hf_cfg = transformers.Qwen2MoeConfig(
            vocab_size=64, hidden_size=16, intermediate_size=32,
            num_hidden_layers=2, num_attention_heads=2, num_experts=4,
            mlp_only_layers=[0])
        torch.manual_seed(31)
        model = transformers.Qwen2MoeForCausalLM(hf_cfg)
        with pytest.raises(NotImplementedError, match="heterogeneous"):
            import_hf_model(model)


class TestQwen3MoeImport:
    def test_logits_match_generous_capacity(self):
        """Qwen3-MoE: QK-norm attention, explicit head_dim, normalized top-k
        routing, no shared expert."""
        hf_cfg = transformers.Qwen3MoeConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            moe_intermediate_size=24, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            num_experts=4, num_experts_per_tok=2, norm_topk_prob=True,
            max_position_embeddings=64, tie_word_embeddings=False)
        torch.manual_seed(32)
        model = transformers.Qwen3MoeForCausalLM(hf_cfg)
        cfg, params = import_hf_model(model)
        assert cfg.qk_norm and cfg.head_dim == 16
        assert cfg.moe_route_norm and cfg.moe_shared_size == 0
        import dataclasses

        cfg = dataclasses.replace(cfg, moe_capacity_factor=64.0)
        tokens = np.random.default_rng(32).integers(0, 128, (2, 16),
                                                    dtype=np.int32)
        _compare_logits(model, tokens, cfg, params, rtol=5e-4, atol=5e-4)

    def test_decode_matches_forward(self):
        """QK-norm + MoE through the KV-cache decode path."""
        hf_cfg = transformers.Qwen3MoeConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            moe_intermediate_size=24, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            num_experts=4, num_experts_per_tok=2,
            max_position_embeddings=64, tie_word_embeddings=False)
        torch.manual_seed(33)
        model = transformers.Qwen3MoeForCausalLM(hf_cfg)
        cfg, params = import_hf_model(model)
        import dataclasses

        cfg = dataclasses.replace(cfg, moe_capacity_factor=64.0)
        tokens = np.random.default_rng(33).integers(0, 128, (2, 8),
                                                    dtype=np.int32)
        full = np.asarray(T.forward(params, jnp.asarray(tokens), cfg))
        cache = T.init_kv_cache(cfg, batch_size=2, max_len=16)
        logits, _ = T.forward_decode(
            params, jnp.asarray(tokens), cache, jnp.zeros((2,), jnp.int32), cfg)
        np.testing.assert_allclose(np.asarray(logits), full, rtol=2e-3,
                                   atol=2e-3)


class TestDeepseekV3Import:
    def _model(self, q_lora=16):
        hf_cfg = transformers.DeepseekV3Config(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            moe_intermediate_size=24, num_hidden_layers=2,
            num_attention_heads=2, num_key_value_heads=2,
            n_routed_experts=4, num_experts_per_tok=2, n_shared_experts=1,
            q_lora_rank=q_lora, kv_lora_rank=8, qk_nope_head_dim=8,
            qk_rope_head_dim=4, v_head_dim=8, first_k_dense_replace=0,
            n_group=2, topk_group=1, norm_topk_prob=True,
            routed_scaling_factor=2.5, max_position_embeddings=64,
            tie_word_embeddings=False)
        torch.manual_seed(40)
        return transformers.DeepseekV3ForCausalLM(hf_cfg)

    def test_logits_match_generous_capacity(self):
        """DeepSeek-V3: MLA attention (latent q/kv projections, interleaved
        rope on the decoupled key) + sigmoid grouped routing with
        e_score_correction_bias + shared experts + routed scaling."""
        model = self._model()
        cfg, params = import_hf_model(model)
        assert cfg.mla and cfg.kv_lora_rank == 8 and cfg.q_lora_rank == 16
        assert cfg.moe_score_func == "sigmoid" and cfg.moe_route_scale == 2.5
        assert cfg.moe_n_group == 2 and cfg.moe_gate_bias
        import dataclasses

        cfg = dataclasses.replace(cfg, moe_capacity_factor=64.0)
        tokens = np.random.default_rng(40).integers(0, 128, (2, 16),
                                                    dtype=np.int32)
        _compare_logits(model, tokens, cfg, params, rtol=5e-4, atol=5e-4)

    def test_nonzero_gate_bias_changes_selection_like_hf(self):
        """e_score_correction_bias must steer SELECTION but not weights —
        verified against HF with a non-zero bias."""
        model = self._model()
        # positive biases: selection stays among truly-kept experts (torch's
        # tie-breaking among 0.0-masked entries is unspecified and not worth
        # replicating — it only triggers when biased scores go negative)
        with torch.no_grad():
            for layer in model.model.layers:
                layer.mlp.gate.e_score_correction_bias.add_(
                    torch.tensor([0.3, 0.05, 0.2, 0.1]))
        cfg, params = import_hf_model(model)
        import dataclasses

        cfg = dataclasses.replace(cfg, moe_capacity_factor=64.0)
        tokens = np.random.default_rng(41).integers(0, 128, (2, 16),
                                                    dtype=np.int32)
        _compare_logits(model, tokens, cfg, params, rtol=5e-4, atol=5e-4)

    def test_decode_matches_forward(self):
        """MLA latent KV cache (c_kv + shared rope key only) through the
        decode path."""
        model = self._model()
        cfg, params = import_hf_model(model)
        import dataclasses

        cfg = dataclasses.replace(cfg, moe_capacity_factor=64.0)
        params = jax.tree.map(jnp.asarray, params)
        tokens = np.random.default_rng(42).integers(0, 128, (2, 8),
                                                    dtype=np.int32)
        full = np.asarray(T.forward(params, jnp.asarray(tokens), cfg))
        cache = T.init_kv_cache(cfg, batch_size=2, max_len=16)
        # the latent cache is the small one: kvr + dr vs N*(dn+dr+dv)
        assert cache["k"].shape[-1] == 8 and cache["v"].shape[-1] == 4
        logits, cache2 = T.forward_decode(
            params, jnp.asarray(tokens), cache, jnp.zeros((2,), jnp.int32),
            cfg)
        np.testing.assert_allclose(np.asarray(logits), full, rtol=2e-3,
                                   atol=2e-3)
        nxt = np.random.default_rng(43).integers(0, 128, (2, 1),
                                                 dtype=np.int32)
        step_logits, _ = T.forward_decode(
            params, jnp.asarray(nxt), cache2, jnp.full((2,), 8, jnp.int32),
            cfg)
        ext = np.concatenate([tokens, nxt], axis=1)
        full_ext = np.asarray(T.forward(params, jnp.asarray(ext), cfg))
        np.testing.assert_allclose(np.asarray(step_logits[:, 0]),
                                   full_ext[:, -1], rtol=2e-3, atol=2e-3)

    def test_first_k_dense_matches_hf(self):
        """A leading dense layer (``first_k_dense_replace``, Moonlight's and
        DeepSeek-V3's stack) imports as a segment of its own and matches
        the HF model: direct q projection, one group, top-2 of 4, a shared
        expert, a non-zero selection bias."""
        hf_cfg = transformers.DeepseekV3Config(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            moe_intermediate_size=24, num_hidden_layers=3,
            num_attention_heads=2, num_key_value_heads=2,
            n_routed_experts=4, num_experts_per_tok=2, n_shared_experts=1,
            q_lora_rank=None, kv_lora_rank=8, qk_nope_head_dim=8,
            qk_rope_head_dim=4, v_head_dim=8, first_k_dense_replace=1,
            n_group=1, topk_group=1, norm_topk_prob=True,
            routed_scaling_factor=2.446, max_position_embeddings=64,
            tie_word_embeddings=False)
        torch.manual_seed(44)
        model = transformers.DeepseekV3ForCausalLM(hf_cfg)
        with torch.no_grad():
            for layer in model.model.layers[1:]:
                layer.mlp.gate.e_score_correction_bias.add_(
                    torch.tensor([0.3, 0.05, 0.2, 0.1]))
        cfg, params = import_hf_model(model)
        assert cfg.first_dense_layers == 1 and cfg.moe_dispatch == "ragged"
        assert params["dense_blocks"]["w_up"].shape == (1, 32, 64)
        assert params["blocks"]["w_up"].shape == (2, 4, 32, 24)
        tokens = np.random.default_rng(44).integers(0, 128, (2, 16),
                                                    dtype=np.int32)
        _compare_logits(model, tokens, cfg, params, rtol=5e-4, atol=5e-4)


class TestDeepseekV2Import:
    def test_logits_match_generous_capacity(self):
        """DeepSeek-V2-Lite: MLA with NON-interleaved rope + softmax greedy
        routing + shared experts."""
        hf_cfg = transformers.DeepseekV2Config(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            moe_intermediate_size=24, num_hidden_layers=2,
            num_attention_heads=2, n_routed_experts=4, num_experts_per_tok=2,
            n_shared_experts=1, q_lora_rank=16, kv_lora_rank=8,
            qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
            first_k_dense_replace=0, topk_method="greedy",
            routed_scaling_factor=1.0, max_position_embeddings=64,
            tie_word_embeddings=False)
        torch.manual_seed(50)
        model = transformers.DeepseekV2ForCausalLM(hf_cfg)
        cfg, params = import_hf_model(model)
        assert cfg.mla and not cfg.rope_interleave
        assert cfg.moe_score_func == "softmax" and not cfg.moe_gate_bias
        import dataclasses

        cfg = dataclasses.replace(cfg, moe_capacity_factor=64.0)
        tokens = np.random.default_rng(50).integers(0, 128, (2, 16),
                                                    dtype=np.int32)
        _compare_logits(model, tokens, cfg, params, rtol=5e-4, atol=5e-4)

    def test_group_limited_greedy_rejected(self):
        hf_cfg = transformers.DeepseekV2Config(
            vocab_size=64, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=2, n_routed_experts=4,
            q_lora_rank=16, kv_lora_rank=8, qk_nope_head_dim=8,
            qk_rope_head_dim=4, v_head_dim=8, first_k_dense_replace=0,
            topk_method="group_limited_greedy", n_group=2, topk_group=1)
        torch.manual_seed(51)
        model = transformers.DeepseekV2ForCausalLM(hf_cfg)
        with pytest.raises(NotImplementedError, match="greedy"):
            import_hf_model(model)

    def test_yarn_rope_scaling_logits_match(self):
        """Released DeepSeek checkpoints set rope_scaling (yarn + mscale):
        scaled frequencies, cos/sin attention factor AND the mscale^2 softmax
        scale must all match HF."""
        hf_cfg = transformers.DeepseekV3Config(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            moe_intermediate_size=24, num_hidden_layers=2,
            num_attention_heads=2, n_routed_experts=4, num_experts_per_tok=2,
            n_shared_experts=1, q_lora_rank=16, kv_lora_rank=8,
            qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
            first_k_dense_replace=0, n_group=1, topk_group=1,
            max_position_embeddings=64, tie_word_embeddings=False,
            rope_scaling={"rope_type": "yarn", "factor": 40.0,
                          "beta_fast": 32, "beta_slow": 1,
                          "mscale": 1.0, "mscale_all_dim": 1.0,
                          "original_max_position_embeddings": 16})
        torch.manual_seed(52)
        model = transformers.DeepseekV3ForCausalLM(hf_cfg)
        cfg, params = import_hf_model(model)
        assert cfg.rope_scaling is not None and cfg.mla_scale_mult != 1.0
        import dataclasses

        cfg = dataclasses.replace(cfg, moe_capacity_factor=64.0)
        tokens = np.random.default_rng(52).integers(0, 128, (2, 24),
                                                    dtype=np.int32)
        _compare_logits(model, tokens, cfg, params, rtol=5e-4, atol=5e-4)


class TestRopeScaling:
    def test_llama3_scaling_logits_match(self):
        """Llama-3.x checkpoints all set rope_scaling type 'llama3' — the
        piecewise wavelength scaling must match HF (it changes logits at
        EVERY length, not just long contexts)."""
        hf_cfg = transformers.LlamaConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=256,
            tie_word_embeddings=False,
            rope_scaling={"rope_type": "llama3", "factor": 8.0,
                          "low_freq_factor": 1.0, "high_freq_factor": 4.0,
                          "original_max_position_embeddings": 32})
        torch.manual_seed(60)
        model = transformers.LlamaForCausalLM(hf_cfg)
        cfg, params = import_hf_model(model)
        assert cfg.rope_scaling is not None
        tokens = np.random.default_rng(60).integers(0, 128, (2, 48),
                                                    dtype=np.int32)
        _compare_logits(model, tokens, cfg, params, rtol=3e-4, atol=3e-4)

    def test_unknown_scaling_type_rejected(self):
        hf_cfg = transformers.LlamaConfig(
            vocab_size=64, hidden_size=32, num_hidden_layers=1,
            num_attention_heads=2, max_position_embeddings=64,
            rope_scaling={"rope_type": "longrope", "factor": 4.0,
                          "long_factor": [1.0], "short_factor": [1.0]})
        torch.manual_seed(61)
        try:
            model = transformers.LlamaForCausalLM(hf_cfg)
        except Exception:
            pytest.skip("transformers rejects this synthetic longrope config")
        with pytest.raises(NotImplementedError, match="rope_scaling type"):
            import_hf_model(model)


class TestQwen3Import:
    def test_logits_match(self):
        """Qwen3 dense: QK-norm + explicit head_dim (≠ hidden/heads)."""
        hf_cfg = transformers.Qwen3Config(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=2,
            num_key_value_heads=1, head_dim=16,
            max_position_embeddings=64, tie_word_embeddings=False)
        torch.manual_seed(70)
        model = transformers.Qwen3ForCausalLM(hf_cfg)
        cfg, params = import_hf_model(model)
        assert cfg.qk_norm and cfg.head_dim == 16 and not cfg.qkv_bias
        tokens = np.random.default_rng(70).integers(0, 128, (2, 16),
                                                    dtype=np.int32)
        _compare_logits(model, tokens, cfg, params)

    def test_generate_matches_hf(self):
        hf_cfg = transformers.Qwen3Config(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=2,
            num_key_value_heads=1, head_dim=16,
            max_position_embeddings=64, tie_word_embeddings=False)
        torch.manual_seed(71)
        model = transformers.Qwen3ForCausalLM(hf_cfg)
        cfg, params = import_hf_model(model)

        from deepspeed_tpu.inference import InferenceEngine

        eng = InferenceEngine(cfg, params=params, mesh=None)
        ours = eng.generate([[3, 1, 4, 1, 5]], max_new_tokens=6)[0]
        with torch.no_grad():
            hf = model.generate(torch.tensor([[3, 1, 4, 1, 5]]),
                                max_new_tokens=6, do_sample=False,
                                use_cache=True)[0, 5:].tolist()
        assert ours == hf


class TestExaoneImport:
    def test_logits_match_via_rename(self):
        """EXAONE-3 is the Llama recipe under its own key names
        (transformer.h.N.attn.attention.*, mlp.c_fc_0/1, ln_1/2, wte).
        transformers has no bundled Exaone class (trust_remote_code
        upstream), so synthesize the state dict by renaming a Llama one —
        the importer must produce byte-identical params to the llama path."""
        from types import SimpleNamespace

        hf_cfg = transformers.LlamaConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=128,
            tie_word_embeddings=False, rope_theta=10000.0)
        torch.manual_seed(77)
        model = transformers.LlamaForCausalLM(hf_cfg)
        cfg_ref, params_ref = import_hf_model(model)

        ren = {
            "model.embed_tokens.weight": "transformer.wte.weight",
            "model.norm.weight": "transformer.ln_f.weight",
            ".input_layernorm.weight": ".ln_1.weight",
            ".post_attention_layernorm.weight": ".ln_2.weight",
            ".self_attn.q_proj.": ".attn.attention.q_proj.",
            ".self_attn.k_proj.": ".attn.attention.k_proj.",
            ".self_attn.v_proj.": ".attn.attention.v_proj.",
            ".self_attn.o_proj.": ".attn.attention.out_proj.",
            ".mlp.gate_proj.": ".mlp.c_fc_0.",
            ".mlp.up_proj.": ".mlp.c_fc_1.",
            ".mlp.down_proj.": ".mlp.c_proj.",
            "model.layers.": "transformer.h.",
        }
        sd = {}
        for k, v in model.state_dict().items():
            nk = k
            for old, new in ren.items():
                nk = nk.replace(old, new)
            sd[nk] = v
        ex_cfg = SimpleNamespace(
            model_type="exaone", vocab_size=128, hidden_size=32,
            intermediate_size=64, num_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=128,
            tie_word_embeddings=False, rope_theta=10000.0,
            layer_norm_epsilon=hf_cfg.rms_norm_eps)
        cfg, params = import_hf_model((sd, ex_cfg))
        assert cfg.num_layers == cfg_ref.num_layers
        assert cfg.norm_eps == cfg_ref.norm_eps
        # configs that expose the LLAMA attr names directly must also work
        # (the alias spread must not produce duplicate kwargs)
        ex_cfg2 = SimpleNamespace(**{**vars(ex_cfg)})
        ex_cfg2.num_hidden_layers = 2
        ex_cfg2.rms_norm_eps = hf_cfg.rms_norm_eps
        cfg2, _ = import_hf_model((sd, ex_cfg2))
        assert cfg2.num_layers == cfg_ref.num_layers
        for (ka, a), (kb, b) in zip(
                sorted(jax.tree_util.tree_leaves_with_path(params_ref),
                       key=lambda kv: str(kv[0])),
                sorted(jax.tree_util.tree_leaves_with_path(params),
                       key=lambda kv: str(kv[0]))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=str(ka))
        tokens = np.random.default_rng(7).integers(0, 128, (2, 32),
                                                   dtype=np.int32)
        _compare_logits(model, tokens, cfg, params, rtol=3e-4, atol=3e-4)
