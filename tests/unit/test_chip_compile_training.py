"""The training step's kernels (flash attention, under a window or not, the
grouped matmuls, a share's movers) compiled for the v5e at the training
cells' widths, without a chip. The serving kernels are
``test_chip_compile.py``'s; see its note on libtpu.
"""
import re

import pytest

import jax
import jax.numpy as jnp

from chip_topology import mosaic_calls as _mosaic_calls
from chip_topology import one_chip  # noqa: F401 (a fixture)


# a named block of 64, and float32 operands and heads of 256 at whole blocks
FLASH_SHAPES = {
    "mistral7b-1x4096": (1, 4096, 32, 8, 128, jnp.bfloat16, {}),
    "pythia69b-2x2048": (2, 2048, 32, 32, 128, jnp.bfloat16, {}),
    "short-1x100": (1, 100, 4, 2, 128, jnp.bfloat16, {}),
    "named-64x128": (1, 512, 4, 4, 128, jnp.bfloat16,
                     dict(block_q=64, block_kv=128)),
    "float32-1x2048": (1, 2048, 4, 4, 128, jnp.float32, {}),
    "float32-256-1x2048": (1, 2048, 4, 2, 256, jnp.float32, {}),
}


@pytest.mark.parametrize("shape", sorted(FLASH_SHAPES))
def test_flash_kernels_compile_for_v5e(one_chip, shape, monkeypatch):
    import importlib

    from benchmarks.roofline import flash_attention as need
    from benchmarks.trace_reduce import Op

    # the package exports the function under the module's own name
    F = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(F, "_use_interpret", lambda: False)
    B, S, N, K, D, dtype, blocks = FLASH_SHAPES[shape]
    block_q = min(blocks.get("block_q") or F.choose_blocks(S, S)[0],
                  F._round_pow2(S))
    S_pad = -(-S // block_q) * block_q

    def arg(heads):
        return jax.ShapeDtypeStruct((B, S, heads, D), dtype,
                                    sharding=one_chip)

    def grads(q, k, v, do):
        o, back = jax.vjp(lambda q, k, v: F.flash_attention(
            q, k, v, causal=True, **blocks), q, k, v)
        return (o,) + back(do)

    text = jax.jit(grads).lower(arg(N), arg(K), arg(K), arg(N)) \
        .compile().as_text()
    # a trace names an event by the instruction with its operands' types;
    # the compiled text keeps those under ``operand_layout_constraints``
    calls = [Op("", "custom-call", re.sub(
        r"custom-call\(.*?\), (.*operand_layout_constraints=\{(.+?\})\}, )",
        r"custom-call(\2), \1", line), 0.0, 0.0)
        for line in text.splitlines()
        if "custom_call_target=\"tpu_custom_call\"" in line]
    # the benchmark's reader tells the three apart by operand and result
    # counts and reads B*N, S, D off operand 0: what it finds here is what
    # ``flash_attention_roofline`` is reckoned from
    assert sorted(need.classify(c) for c in calls) == ["dkv", "dq", "fwd"]
    for c in calls:
        matmuls = need._MATMULS[need.classify(c)]
        assert need.ops_and_bytes(need.classify(c), c.text)[0] == \
            matmuls * B * N * S_pad * S_pad * D



# the flash kernels under a WINDOW at the training cell that has one: a
# chip's share of a step (2 x 8,192, 32 query / 4 KV heads of 128, window
# 1,024) and, so that the geometry is not the one case, a window longer than
# a block at Mistral's 1 x 4,096 x 32 / 8
WINDOW_FLASH_SHAPES = {
    "mellum2-2x8192-w1024": (2, 8192, 32, 4, 128, 1024),
    "gqa-1x4096-w1536": (1, 4096, 32, 8, 128, 1536),
}


@pytest.mark.parametrize("shape", sorted(WINDOW_FLASH_SHAPES))
def test_window_flash_kernels_compile_for_v5e(one_chip, shape, monkeypatch):
    import importlib

    from benchmarks.roofline import flash_attention as full
    from benchmarks.roofline import window_flash_attention as need

    F = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(F, "_use_interpret", lambda: False)
    B, S, N, K, D, window = WINDOW_FLASH_SHAPES[shape]

    def arg(heads):
        return jax.ShapeDtypeStruct((B, S, heads, D), jnp.bfloat16,
                                    sharding=one_chip)

    def grads(q, k, v, do):
        o, back = jax.vjp(lambda q, k, v: F.flash_attention(
            q, k, v, causal=True, window=window), q, k, v)
        return (o,) + back(do)

    text = jax.jit(grads).lower(arg(N), arg(K), arg(K), arg(N)) \
        .compile().as_text()
    calls = _mosaic_calls(text)
    # three Mosaic calls under names of their own: the window's reader finds
    # them by name, with the live area for their need
    assert sorted(need.classify(c) for c in calls) == ["dkv", "dq", "fwd"]
    assert all("window_flash_" in c.name for c in calls)
    area = S * window - window * window / 2
    for c in calls:
        kind = need.classify(c)
        assert need.ops_and_bytes(kind, c.text, window)[0] == \
            need._MATMULS[kind] * 2 * B * N * area * D
        # the full kernels' reader would take it for a causal square
        assert full.ops_and_bytes(kind, c.text)[0] > \
            3.9 * need.ops_and_bytes(kind, c.text, window)[0] * (
                1 if window == 1024 else 0.4)


# the grouped matmuls of the training cell with experts, forward and
# backward, at a layer's shapes: a row a pair of the step's 2 x 8,192 x 8 x 2,304
# against 16 held experts' [2,304, 896] and [896, 2,304], each product under
# the tiles of its own shapes
def test_training_gmm_and_tgmm_compile_for_v5e(one_chip, monkeypatch):
    from jax.experimental.pallas.ops.tpu.megablox.ops import backend

    from benchmarks.roofline import train_expert_gmm as need
    from deepspeed_tpu.moe import layer as MOE

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows = 2 * 8192 * 8
    bf = jnp.bfloat16
    traced = []
    for name in ("gmm", "tgmm"):
        def spy(*a, _name=name, _real=getattr(backend, name), **kw):
            # (product, the matrices' [K, N] as the forward has them, tiles)
            kn = a[1].shape[1:] if _name == "gmm" else \
                (a[0].shape[0], a[1].shape[1])
            traced.append((_name + "T" * kw.get("transpose_rhs", False),
                           tuple(kn), a[4]))
            return _real(*a, **kw)
        monkeypatch.setattr(backend, name, spy)

    def arg(dims, dtype=bf):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def ffn(x, experts, sizes):
        return jnp.sum(MOE.ragged_expert_ffn(
            x, sizes, experts, "swiglu").astype(jnp.float32))

    experts = {"w_up": arg((16, 2304, 896)), "w_gate": arg((16, 2304, 896)),
               "w_down": arg((16, 896, 2304))}
    # Mosaic refuses a call whose scoped VMEM is over the limit (16 MiB):
    # that this compiles says every product's is under it
    text = jax.jit(jax.grad(ffn, (0, 1))).lower(
        arg((rows, 2304)), experts, arg((16,), jnp.int32)).compile().as_text()
    calls = _mosaic_calls(text)
    kinds = [need.classify(c) for c in calls]
    # forward three, the rows' gradient three (XLA may share one of them:
    # the sum's cotangent is a constant), the matrices' three
    assert kinds.count("gmm") in (5, 6) and kinds.count("tgmm") == 3
    pairs = 2 * 8192 * 8 // 4
    for c, kind in zip(calls, kinds):
        ops, moved = need.ops_and_bytes(kind, c.text, pairs)
        assert ops == 2.0 * pairs * 2304 * 896, (kind, c.text[:200])
        assert moved >= 2 * (16 * 2304 * 896 + pairs * 896)
        # the rows' tile, read off the call: its scalar-prefetched table of
        # visits has a row tile's entry and one more a group but the first
        visits = [int(n) for n in re.findall(r"s32\[(\d+)\]", c.text)]
        assert rows // 256 + 16 - 1 in visits, c.text[:300]
    # the three products of ONE matrix run under three triples of their
    # own, none with a remainder, each what the rule gives for its shapes
    for kn in ((2304, 896), (896, 2304)):
        fwd, dgrad, wgrad = MOE.gmm_tilings(rows, *kn, 16, 2)
        assert len({fwd, dgrad, wgrad}) == 3
        got = {p: t for p, shape, t in traced if shape == kn}
        assert got == {"gmm": fwd, "gmmT": dgrad, "tgmm": wgrad}
        (K, N) = kn
        for (tm, tk, tn), (k, n) in zip((fwd, dgrad, wgrad),
                                        ((K, N), (N, K), (K, N))):
            assert rows % tm == 0 and k % tk == 0 and n % tn == 0


def test_a_shares_movers_compile_for_v5e_and_copy_no_sorted_array(
        one_chip, monkeypatch):
    """A share of an expert layer at the training cell's shape, forward
    and backward: the movers' loops and the calls that hand them their
    arrays compile, no ``[131072, .]`` array is copied around them (an
    array two loops shared was: PR 50), and the layer's temporaries are
    the step's (4.3 GB here, 4.47 in the step; the plain forms' step 4.02)."""
    from benchmarks.roofline import train_expert_gmm as need
    from deepspeed_tpu.moe import layer as MOE

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows, k, width, inter, held = 2 * 8192, 8, 2304, 896, 16
    bf = jnp.bfloat16

    def arg(dims, dtype=bf):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def share(x, w, idx, experts):
        y, _ = MOE._held_routed(x, w, idx, experts, "swiglu", 0, 64)
        return jnp.sum(y.astype(jnp.float32))

    experts = {"w_up": arg((held, width, inter)),
               "w_gate": arg((held, width, inter)),
               "w_down": arg((held, inter, width))}
    operands = (arg((rows, width)), arg((rows, k)),
                arg((rows, k), jnp.int32), experts)

    assert MOE.held_tiles(rows * k, rows) == (512, 512)
    live = jax.jit(jax.grad(share, (0, 1, 3))).lower(*operands).compile()
    text = live.as_text()
    assert " while(" in text
    assert not [s for s in re.findall(r"= (\S+) copy\(", text)
                if s.startswith(f"bf16[{rows * k},")]
    # the roofline of the grouped matmuls takes the calls it took: the
    # nine of the forward, the rows' gradient and the matrices', none new
    kinds = [need.classify(c) for c in _mosaic_calls(text)]
    assert kinds.count(None) >= 5
    assert kinds.count("gmm") in (5, 6) and kinds.count("tgmm") == 3
    # the arrays the loops fill are made when a loop can start and share
    # memory like any other (as ``lax.empty`` they did not: 17.8 GB a step)
    assert live.memory_analysis().temp_size_in_bytes < 4.6e9


