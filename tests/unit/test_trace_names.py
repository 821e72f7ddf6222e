"""Names the program gives the device trace: ``name=`` on every
``pallas_call`` and ``jax.named_scope`` phases in the training step and
the serving tick, read from the lowered text at toy size. A device trace
names a kernel's instruction after ``name=`` and carries each operation's
scope stack (``tf_op``), which is how the benchmark tells forward,
recompute, backward and optimizer apart."""
import ast
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as dst
from deepspeed_tpu.inference.fastgen import FastGenEngine

PALLAS = os.path.join(os.path.dirname(dst.__file__), "ops", "pallas")
KERNEL_NAMES = {
    "flash_fwd", "flash_dq", "flash_dkv", "window_flash_fwd",
    "window_flash_dq", "window_flash_dkv", "paged_attention",
    "latent_paged_attention", "kda_step", "kda_chunk", "ssd_step",
    "ssd_chunk", "index_scores",
    "sparse_choice", "block_sparse_fwd", "block_sparse_dq", "block_sparse_dkv",
    "evoformer_attention", "fused_adam", "quantize_int8_blocks",
    "dequant_reduce", "rms_norm", "layer_norm", "unwritten_rows"}


def _stacks(lowered, program):
    """The name stack (``op_name``) of every instruction of the compiled
    program, as a device trace carries it."""
    names = set(re.findall(r'op_name="([^"]+)"', lowered.compile().as_text()))
    return {n for n in names if n.startswith(f"jit({program})/")}


def _assert_both_heads_are_scoped(stacks):
    """A tick larger than the small bucket holds its head twice, behind a
    conditional (the sampled rows alone, or every row): the operations of
    either branch sit under ``lm_head`` and ``sample``."""
    for branch in ("branch_0_fun", "branch_1_fun"):
        for scope in ("lm_head", "sample"):
            assert any(s.startswith(f"jit(tick)/cond/{branch}/{scope}")
                       for s in stacks), (branch, scope)


def test_every_pallas_call_is_named():
    sites, literal = 0, set()
    for path in sorted(glob.glob(os.path.join(PALLAS, "*.py"))):
        tree = ast.parse(open(path).read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and \
                    getattr(node.func, "attr", "") == "pallas_call":
                sites += 1
                kw = {k.arg: k.value for k in node.keywords}
                assert "name" in kw, f"{path}:{node.lineno} has no name="
                if isinstance(kw["name"], ast.Constant):
                    literal.add(kw["name"].value)
            if isinstance(node, ast.Call) and \
                    getattr(node.func, "id", "") == "_run_rows":
                literal.add(node.args[0].value)   # norms.py: by the caller
            if isinstance(node, ast.Call) and \
                    getattr(node.func, "id", "") == "_call_name" and \
                    isinstance(node.args[0], ast.Constant):
                # flash_attention.py: a call under a window carries a name
                # of its own, ``window_<kernel>``
                literal.update((node.args[0].value,
                                "window_" + node.args[0].value))
            if isinstance(node, ast.Call) and \
                    getattr(node.func, "id", "") == "_walk":
                # paged_attention.py: one call site, named by each of the
                # kernel's instantiations; the dense one by its ``name=``
                # parameter, whose default is the literal (a stack of layer
                # kinds passes ``window_paged_attention`` /
                # ``shared_paged_attention`` from models/paged.py)
                literal.update(k.value.value for k in node.keywords
                               if k.arg == "name"
                               and isinstance(k.value, ast.Constant))
            if isinstance(node, ast.FunctionDef):
                literal.update(
                    d.value for a, d in zip(node.args.kwonlyargs,
                                            node.args.kw_defaults)
                    if a.arg == "name" and isinstance(d, ast.Constant))
    assert sites == 19
    assert literal == KERNEL_NAMES


def test_training_step_carries_phases_and_kernel_names():
    spec = dst.causal_lm_spec("tiny", dtype="float32", num_layers=2,
                              max_seq_len=128, remat="full",
                              attention="flash")
    engine, *_ = dst.initialize(model=spec, config={
        "train_batch_size": 8, "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 3}})
    step = engine._select_step_builder(1)
    batch = {"tokens": jnp.zeros((1, 8, 128), jnp.int32)}
    with engine.mesh:
        stacks = _stacks(step.lower(engine.state, batch), "train_step")
    assert len(stacks) > 100

    def some(*parts):
        return any(all(p in s for p in parts) for s in stacks)

    # forward, recompute and backward of a block, each under its scope
    assert some("loss_and_grads/jvp(", "attn")
    assert some("loss_and_grads/jvp(", "mlp")
    assert some("rematted_computation", "attn")
    assert some("rematted_computation", "mlp")
    assert some("transpose(jvp(", "/mlp/")
    assert some("loss_and_grads/jvp(embed)")
    assert some("loss_and_grads/jvp(lm_head_loss)")
    assert some("transpose(", "lm_head_loss")      # its custom derivative
    assert some("/optimizer/")
    # the three flash kernels by name: the forward one in the forward
    # pass and again in the recompute, the two backward ones after it
    assert some("loss_and_grads/jvp()", "/attn/", "/flash_fwd")
    assert some("rematted_computation/attn", "/flash_fwd")
    assert some("transpose(jvp())", "checkpoint/attn", "/flash_dq")
    assert some("transpose(jvp())", "checkpoint/attn", "/flash_dkv")
    # and the benchmark's reader sorts them into its four phases
    from benchmarks import gap_chain

    by_phase = {}
    for s in stacks:
        by_phase.setdefault(gap_chain.phase_of(s), []).append(s)
    assert set(by_phase) - {None} == {"fwd", "recompute", "bwd", "optimizer"}
    # outside every phase: the accumulator's zeros and the loss's mean
    assert len(by_phase.get(None, [])) <= 4
    assert gap_chain.phase_of(next(
        s for s in stacks if "rematted_computation/attn" in s)) == "recompute"
    engine.shutdown_telemetry()


def test_training_step_of_held_experts_carries_its_scopes_and_counters():
    """A stack of window and full layers over experts held as a share: the
    window layers' flash calls under names of their own, ``router`` and
    ``experts`` scopes in forward, recompute and backward, and the rows of
    each held expert in the registry with no fence of their own."""
    import dataclasses
    import json
    import os

    from benchmarks import model_config
    from deepspeed_tpu import telemetry

    with open(os.path.join(os.path.dirname(os.path.dirname(
            dst.__file__)), "benchmarks", "configs",
            "mellum2-12b-a2.5b.json")) as f:
        cfg = model_config.build(json.load(f), "train", remat="full",
                                 rehearse=True)
    cfg = dataclasses.replace(cfg, dtype="float32")
    spec = dst.causal_lm_spec(cfg, attention="flash")
    engine, *_ = dst.initialize(model=spec, config={
        "train_batch_size": 8, "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 3}})
    step = engine._select_step_builder(1)
    batch = {"tokens": jnp.zeros((1, 8, 64), jnp.int32)}
    with engine.mesh:
        stacks = _stacks(step.lower(engine.state, batch), "train_step")

    def some(*parts):
        return any(all(p in s for p in parts) for s in stacks)

    for scope in ("/router/", "/experts/"):
        assert some("loss_and_grads/jvp(", "/mlp/", scope), scope
        assert some("rematted_computation", "/mlp/", scope), scope
        assert some("transpose(", "/mlp/", scope), scope
    assert some("/attn/", "/window_flash_fwd")
    assert some("/attn/", "/flash_fwd")
    assert some("transpose(", "/window_flash_dq")
    assert some("transpose(", "/window_flash_dkv")
    # the benchmark's readers find the scopes as the serving form's
    from benchmarks import gap_chain

    assert {gap_chain.phase_of(s) for s in stacks if "/experts/" in s} \
        >= {"fwd", "recompute", "bwd"}
    # and a program of one chip's rows (no axis of the mesh divides two
    # sequences) hands the held experts' rows out beside its loss (the
    # step's ``metrics["moe_held"]``; no host callback), which the engine
    # puts into the registry: four layers, the forward pass alone, 2 x 64 x
    # 8 pairs, a quarter of them here
    from deepspeed_tpu.models import transformer as T

    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 64)).astype(np.int32)
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          engine.state["master"])

    def loss_and_meters(p, b):
        with T.collect_meters() as meters:
            return spec.loss_fn(p, b), meters

    (_, meters), _ = jax.jit(jax.value_and_grad(
        loss_and_meters, has_aux=True))(params, {"tokens": tokens})
    assert meters["moe_held"].shape == (4, cfg.n_experts + 2)
    engine._observe_meters({"moe_held": meters["moe_held"][None]})
    engine.shutdown_telemetry()
    got = {}
    for name in ("train_moe_held_expert_rows", "train_moe_held_pair_share",
                 "train_moe_load_imbalance", "train_moe_moved_row_share"):
        (_, child), = telemetry.histogram(name).labels_items()
        assert child.count == 4, name
        got[name] = child.sum / child.count
    assert got["train_moe_held_expert_rows"] == pytest.approx(
        got["train_moe_held_pair_share"] * 2 * 64 * 8 / 4)
    assert 0.15 < got["train_moe_held_pair_share"] < 0.35
    assert got["train_moe_load_imbalance"] >= 1.0
    # the movers walked ceil(n / tile) tiles of 512 sorted rows of the
    # 1,024 pairs a call: one tile while no more than half the pairs are here
    assert got["train_moe_moved_row_share"] == 0.5
    engine.shutdown_telemetry()


@pytest.fixture(scope="module")
def tiny_tick_stacks():
    """The name stacks of the tiny dense model's greedy tick of 32 rows,
    lowered and compiled once for the two tests that read them."""
    eng = FastGenEngine("tiny", n_blocks=16, block_size=16,
                        max_blocks_per_seq=8, token_budget=32,
                        temperature=0.0, seed=0, use_pallas_kernel=True,
                        hidden_size=64, num_layers=2, num_heads=4,
                        max_seq_len=128, vocab_size=512, dtype="float32")
    tn, mb = 32, eng.max_blocks_per_seq
    return _stacks(eng._build_tick(tn, mb).lower(
        eng.params, eng.pool, eng._pack_tick(
            np.zeros((tn,), np.int32), np.zeros((tn,), np.int32),
            np.zeros((tn, mb), np.int32), np.zeros((2,), np.uint32))),
        "tick")


def test_serving_tick_carries_scopes_and_the_kernel_name(tiny_tick_stacks):
    stacks = tiny_tick_stacks
    parts = {part for s in stacks for part in s.split("/")}
    assert {"embed", "attn", "mlp", "lm_head", "sample"} <= parts
    _assert_both_heads_are_scoped(stacks)
    # the paged kernel by name, inside a layer's attention scope
    assert any("/attn/paged_attention" in s for s in stacks)


def test_a_tick_of_window_and_full_layers_tells_its_kernels_and_experts_apart():
    """The ``afmoe`` tick: the window layers' and the full layers'
    attention under scopes and kernel names of their own (neither is
    ``paged_attention``, which the dense kernel's roofline reader takes
    for its own), the expert layer's three parts beside a dense layer's
    ``mlp``."""
    import types

    from deepspeed_tpu.models.hf_import import config_from_hf

    cfg = config_from_hf(types.SimpleNamespace(
        model_type="afmoe", hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, head_dim=16, num_attention_heads=6,
        num_key_value_heads=2, num_hidden_layers=5, num_dense_layers=1,
        layer_types=["sliding_attention"] * 4 + ["full_attention"],
        num_experts=4, router_experts=16, num_experts_per_tok=4,
        num_shared_experts=1, rms_norm_eps=1e-5, rope_theta=10000,
        route_norm=True, route_scale=2.448, sliding_window=16,
        tie_word_embeddings=False, vocab_size=128,
        max_position_embeddings=512, mup_enabled=True))
    eng = FastGenEngine(cfg, n_blocks=16, block_size=4, max_blocks_per_seq=8,
                        token_budget=32, state_slots=2, seed=0,
                        use_pallas_kernel=True)
    tn, mb = 32, eng.max_blocks_per_seq
    stacks = _stacks(eng._build_tick(tn, mb).lower(
        eng.params, eng.pool, eng._pack_tick(
            np.zeros((tn,), np.int32), np.zeros((tn,), np.int32),
            np.zeros((tn, mb), np.int32), np.zeros((2,), np.uint32))),
        "tick")
    parts = {part for s in stacks for part in s.split("/")}
    assert {"embed", "attn", "mlp", "swa", "global", "router", "experts",
            "shared_experts", "lm_head", "sample"} <= parts
    _assert_both_heads_are_scoped(stacks)
    assert any("/attn/swa/swa_attention" in s for s in stacks)
    assert any("/attn/global/global_attention" in s for s in stacks)
    assert not any("/paged_attention" in s for s in stacks)


def test_a_tick_with_conv_layers_sorts_the_mixers_time_apart():
    """The ``lfm2_moe`` tick: a ``conv`` layer's mixer (its norm, the
    projections, the taps, the state read and written) under a scope of
    its own, beside the attention layers' ``attn/global`` and the expert
    layers' parts: what ``conv_share_pct`` reads."""
    import types

    from deepspeed_tpu.models.hf_import import config_from_hf

    cfg = config_from_hf(types.SimpleNamespace(
        model_type="lfm2_moe", hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_attention_heads=4,
        num_key_value_heads=2, num_hidden_layers=6, num_dense_layers=2,
        layer_types=["conv", "conv", "full_attention", "conv", "conv",
                     "conv"], num_experts=8, num_experts_per_tok=4,
        norm_eps=1e-5, norm_topk_prob=True, routed_scaling_factor=1,
        use_expert_bias=True, conv_L_cache=3, conv_bias=False,
        rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
        vocab_size=128, max_position_embeddings=512))
    eng = FastGenEngine(cfg, n_blocks=16, block_size=4, max_blocks_per_seq=8,
                        token_budget=32, state_slots=2, seed=0,
                        use_pallas_kernel=True)
    tn, mb = 32, eng.max_blocks_per_seq
    stacks = _stacks(eng._build_tick(tn, mb).lower(
        eng.params, eng.pool, eng._pack_tick(
            np.zeros((tn,), np.int32), np.zeros((tn,), np.int32),
            np.zeros((tn, mb), np.int32), np.zeros((2,), np.uint32))),
        "tick")
    parts = {part for s in stacks for part in s.split("/")}
    assert {"embed", "conv", "attn", "global", "mlp", "router", "experts",
            "lm_head", "sample"} <= parts
    _assert_both_heads_are_scoped(stacks)
    assert any("/attn/global/global_attention" in s for s in stacks)
    # a conv layer's operations are not under ``attn``, nor the other way
    assert not any("/conv/" in s and "/attn/" in s for s in stacks)
    assert any(s.split("/conv/")[-1].startswith("dot_general")
               for s in stacks if "/conv/" in s)


def test_a_tick_of_single_sublayers_sorts_its_mixers_and_latent_apart():
    """The ``nemotron_h`` tick: a Mamba-2 layer under ``ssd`` with the
    one-row form's call under ``ssd_step`` and the chunked form under
    ``ssd_chunk``; an expert layer's two latent projections under
    ``latent_proj`` beside ``router`` / ``experts`` / ``shared_experts``;
    the attention layer's ``attn/global``: what ``ssd_share_pct``,
    ``ssd_chunk_roofline`` and ``latent_proj_share_pct`` read."""
    import types

    from deepspeed_tpu.models.hf_import import config_from_hf

    cfg = config_from_hf(types.SimpleNamespace(
        model_type="nemotron_h", hidden_size=64, head_dim=128,
        num_attention_heads=4, num_key_value_heads=2, num_hidden_layers=4,
        hybrid_override_pattern="ME*E", mamba_num_heads=16, mamba_head_dim=8,
        n_groups=8, ssm_state_size=128, conv_kernel=4, chunk_size=16,
        expand=2, intermediate_size=48, moe_intermediate_size=48,
        moe_latent_size=32, moe_shared_expert_intermediate_size=96,
        n_shared_experts=1, n_routed_experts=4, router_experts=16,
        num_experts_per_tok=9, routed_scaling_factor=5.0,
        mlp_hidden_act="relu2", layer_norm_epsilon=1e-5, vocab_size=128,
        max_position_embeddings=512))
    eng = FastGenEngine(cfg, n_blocks=16, block_size=4, max_blocks_per_seq=8,
                        token_budget=32, state_slots=2, seed=0,
                        use_pallas_kernel=True)
    tn, mb = 32, eng.max_blocks_per_seq
    stacks = _stacks(eng._build_tick(tn, mb).lower(
        eng.params, eng.pool, eng._pack_tick(
            np.zeros((tn,), np.int32), np.zeros((tn,), np.int32),
            np.zeros((tn, mb), np.int32), np.zeros((2,), np.uint32))),
        "tick")
    parts = {part for s in stacks for part in s.split("/")}
    assert {"embed", "ssd", "ssd_step", "ssd_chunk", "attn", "global",
            "latent_proj", "router", "experts", "shared_experts", "lm_head",
            "sample"} <= parts
    _assert_both_heads_are_scoped(stacks)
    assert any("/ssd/ssd_step/ssd_step" in s for s in stacks)
    assert any("/ssd/ssd_chunk/" in s for s in stacks)
    assert any("/attn/global/global_attention" in s for s in stacks)
    assert any(s.split("/latent_proj/")[-1].startswith("dot_general")
               for s in stacks if "/latent_proj/" in s)
    # a mixer's operations are not under another's scope, and a
    # feed-forward layer's are under neither
    assert not any("/ssd/" in s and "/attn/" in s for s in stacks)
    assert not any("/experts/" in s and ("/ssd/" in s or "/attn/" in s)
                   for s in stacks)


def test_a_looped_tick_tells_its_passes_its_norm_and_its_gate_apart(
        tiny_tick_stacks):
    """The ``ouro`` tick: every pass's layers under ``pass<t>`` (a trace
    tells pass 0's attention from pass 3's by its path), the norm between
    passes under ``loop_norm``, the exit gate under ``exit_gate`` ahead of
    ``lm_head`` in both heads of the full-budget tick; the kernel is the
    homogeneous stack's ``paged_attention``, under each pass."""
    import types

    from deepspeed_tpu.models.hf_import import config_from_hf

    cfg = config_from_hf(types.SimpleNamespace(
        model_type="ouro", hidden_size=64, num_attention_heads=4,
        num_key_value_heads=4, num_hidden_layers=2, intermediate_size=96,
        rms_norm_eps=1e-6, rope_theta=1000000, vocab_size=128,
        max_position_embeddings=512, total_ut_steps=3,
        early_exit_threshold=0.5))
    eng = FastGenEngine(cfg, n_blocks=16, block_size=16, max_blocks_per_seq=8,
                        token_budget=32, seed=0, use_pallas_kernel=True)
    tn, mb = 32, eng.max_blocks_per_seq
    stacks = _stacks(eng._build_tick(tn, mb).lower(
        eng.params, eng.pool, eng._pack_tick(
            np.zeros((tn,), np.int32), np.zeros((tn,), np.int32),
            np.zeros((tn, mb), np.int32), np.zeros((2,), np.uint32))),
        "tick")
    parts = {part for s in stacks for part in s.split("/")}
    assert {"embed", "pass0", "pass1", "pass2", "attn", "mlp", "loop_norm",
            "exit_gate", "lm_head", "sample"} <= parts
    assert "pass3" not in parts
    _assert_both_heads_are_scoped(stacks)
    for t in range(3):
        assert any(f"/pass{t}/" in s and "/attn/paged_attention" in s
                   for s in stacks), t
        assert any(f"/pass{t}/" in s and "/mlp/" in s for s in stacks), t
    for branch in ("branch_0_fun", "branch_1_fun"):
        assert any(s.startswith(f"jit(tick)/cond/{branch}/exit_gate")
                   for s in stacks), branch
    # the norm between passes is no pass's and no layer's; the gate is
    # outside the head's scope, and a pass's layers outside both
    assert any(s.startswith("jit(tick)/loop_norm") for s in stacks)
    assert not any("/loop_norm/" in s and "/pass" in s for s in stacks)
    assert not any("/exit_gate/" in s and "/lm_head/" in s for s in stacks)
    # an unlooped tick carries none of them
    names = {part for s in tiny_tick_stacks for part in s.split("/")}
    assert not names & {"pass0", "loop_norm", "exit_gate"}


def test_a_tick_of_sparse_layers_tells_its_three_parts_apart():
    """The ``KeyeVL2`` tick: a sparse layer's indexer, its choice and its
    attention over the chosen under scopes of their own inside ``attn``
    (``index_share_pct`` / ``select_share_pct`` /
    ``sparse_attention_share_pct`` and ``roofline/sparse_attention.py`` read
    them), the three Mosaic calls by name (``roofline/index_scores.py``
    classifies by it), beside the expert layers' parts."""
    import types

    from deepspeed_tpu.models.hf_import import config_from_hf

    cfg = config_from_hf(types.SimpleNamespace(
        model_type="KeyeVL2", hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, head_dim=16, num_attention_heads=4,
        num_key_value_heads=2, num_hidden_layers=2, num_experts=4,
        router_experts=8, num_experts_per_tok=2, norm_topk_prob=True,
        rms_norm_eps=1e-6, rope_theta=10000000,
        rope_scaling={"mrope_section": [2, 3, 3], "rope_type": "default"},
        sa_config={"indexer_head_dim": 8, "indexer_num_heads": 4,
                   "indexer_num_kv_heads": 1, "topk": 16},
        tie_word_embeddings=False, vocab_size=128,
        max_position_embeddings=512))
    eng = FastGenEngine(cfg, n_blocks=16, block_size=4, max_blocks_per_seq=8,
                        token_budget=32, state_slots=2, seed=0,
                        use_pallas_kernel=True)
    tn, mb = 32, eng.max_blocks_per_seq
    stacks = _stacks(eng._build_tick(tn, mb).lower(
        eng.params, eng.pool, eng._pack_tick(
            np.zeros((tn,), np.int32), np.zeros((tn,), np.int32),
            np.zeros((tn, mb), np.int32), np.zeros((2,), np.uint32))),
        "tick")
    parts = {part for s in stacks for part in s.split("/")}
    assert {"embed", "attn", "index", "select", "sparse", "router",
            "experts", "lm_head", "sample"} <= parts
    _assert_both_heads_are_scoped(stacks)
    assert any("/attn/index/index_scores" in s for s in stacks)
    assert any("/attn/sparse/sparse_attention" in s for s in stacks)
    assert not any("/paged_attention" in s or "/global_attention" in s
                   for s in stacks)
    # the choice is a Mosaic call under its scope (``select_share_pct``
    # reads the scope)
    assert any("/attn/select/sparse_choice" in s for s in stacks)
