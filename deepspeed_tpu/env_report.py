"""Environment / compatibility report — the ``ds_report`` analog.

Parity: reference ``deepspeed/env_report.py`` (``op_report`` :30 + setup
report) printed by ``bin/ds_report``. Reports the JAX/XLA toolchain, device
topology, and the status of every native/Pallas op this framework ships.

CLI: ``python -m deepspeed_tpu.env_report``
"""
from __future__ import annotations

import importlib
import shutil
import sys

GREEN_OK = "[OKAY]"
RED_NO = "[NO]"
UNWIRED_TAG = "[UNWIRED]"


def _try_version(mod: str) -> str:
    try:
        m = importlib.import_module(mod)
        return getattr(m, "__version__", "unknown")
    except Exception as e:  # import-time failures vary; surface the type
        return f"{RED_NO} ({type(e).__name__})"


# Agreement bound for kernel-vs-reference, as max|kernel - ref| / max|ref|.
# The references run in float32 at "highest" matmul precision on the same
# bf16-representable inputs; the kernels accumulate in float32 and round
# their outputs to bf16 (relative step 2^-8 = 0.4%), and an MXU pass over
# a float32 intermediate (the softmax probabilities) rounds it to bf16
# too. Both stay under 1%; a wrong mask, a wrong GQA head mapping or an
# 8-bit compute path is off by several percent or more.
PROBE_TOL = 2e-2

#: kernel modules nothing in the package imports, and the op-builder row
#: whose module does not exist; listed so the report cannot read as if
#: they were in use (their removal is ROADMAP D8)
UNWIRED = (
    ("pallas.fused_adam", "deepspeed_tpu/ops/pallas/fused_adam.py"),
    ("pallas.norms", "deepspeed_tpu/ops/pallas/norms.py"),
    ("op_builder rms_norm", "names deepspeed_tpu.ops.pallas.rms_norm, "
                            "which does not exist"),
)


def rel_err(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want))
                 / max(np.max(np.abs(want)), 1e-30))


def check_rel_errors(errs: dict) -> None:
    for name, err in errs.items():
        if not err < PROBE_TOL:   # also catches NaN
            raise AssertionError(
                f"{name} disagrees with its reference: rel err {err:.4g} "
                f">= {PROBE_TOL}")


def _agree(got, want, what: str) -> None:
    check_rel_errors({what: rel_err(got, want)})


def flash_rel_errors(S: int, N: int, K: int, D: int, seed: int = 0) -> dict:
    """Flash attention forward and backward (random cotangent) against
    ``dot_product_attention``, causal, batch 1, bf16 inputs."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.transformer import dot_product_attention
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, g = (jax.random.normal(kk, (1, S, N, D), jnp.bfloat16)
            for kk in ks[:2])
    k, v = (jax.random.normal(kk, (1, S, K, D), jnp.bfloat16)
            for kk in ks[2:])

    def run(attn, *xs):
        def loss(q, k, v):
            out = attn(q, k, v, causal=True)
            return jnp.sum(out.astype(jnp.float32)
                           * g.astype(jnp.float32)), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(*xs)
        return (out,) + grads

    got = run(flash_attention, q, k, v)
    with jax.default_matmul_precision("highest"):
        want = run(dot_product_attention,
                   *(x.astype(jnp.float32) for x in (q, k, v)))
    return {f"flash_{name}": rel_err(a, b) for name, a, b in
            zip(("fwd", "bwd_dq", "bwd_dk", "bwd_dv"), got, want)}


def paged_rel_errors(rows: int, mb: int, bs: int, N: int, K: int, D: int,
                     seed: int = 0) -> dict:
    """The paged decode kernel against ``paged_attention_reference``:
    every row its own sequence with a random context length, bf16 pool."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models.paged import paged_attention_reference
    from deepspeed_tpu.ops.pallas.paged_attention import paged_attention

    ks = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    nb = rows * mb + 1
    q = jax.random.normal(ks[0], (rows, N, D), jnp.bfloat16)
    kpool = jax.random.normal(ks[1], (nb, bs, K, D), jnp.bfloat16)
    vpool = jax.random.normal(ks[2], (nb, bs, K, D), jnp.bfloat16)
    rng = np.random.default_rng(seed)
    tables = jnp.asarray(rng.permutation(np.arange(1, nb))
                         .reshape(rows, mb), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, mb * bs + 1, rows), jnp.int32)
    got = jax.jit(paged_attention)(q, kpool, vpool, tables, lengths)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(paged_attention_reference)(
            q.astype(jnp.float32), kpool.astype(jnp.float32),
            vpool.astype(jnp.float32), tables, lengths)
    return {"paged_decode": rel_err(got, want)}


def _probe_flash():
    check_rel_errors(flash_rel_errors(S=128, N=4, K=2, D=128))


def _probe_paged():
    check_rel_errors(paged_rel_errors(rows=4, mb=2, bs=16, N=4, K=2, D=128))


def _probe_gmm():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.moe.layer import grouped_dot

    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    x = jax.random.normal(ks[0], (512, 256), jnp.bfloat16)
    w = jax.random.normal(ks[1], (4, 256, 512), jnp.bfloat16)
    sizes = np.array([100, 0, 284, 128], np.int32)
    got = jax.jit(grouped_dot)(x, w, jnp.asarray(sizes))
    x32, w32 = np.asarray(x, np.float32), np.asarray(w, np.float32)
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    want = np.concatenate([x32[lo:hi] @ w32[e] for e, (lo, hi)
                           in enumerate(zip(bounds[:-1], bounds[1:]))])
    _agree(got, want, "grouped_dot")


def _probe_quantization():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.pallas.quantization import (
        dequant_reduce,
        quantize_int8_blocks,
    )
    from deepspeed_tpu.ops.quantization import dequantize_int8, quantize_int8

    block, world = 2048, 4
    x = jax.random.normal(jax.random.PRNGKey(3), (world, 16 * block),
                          jnp.float32)
    q, s = jax.jit(lambda r: quantize_int8_blocks(r, block))(x[0])
    q_ref, s_ref = quantize_int8(x[0], block)
    _agree(s, s_ref, "int8 scales")
    # a value on a rounding boundary may land one step apart
    if int(np.max(np.abs(np.asarray(q, np.int32)
                         - np.asarray(q_ref, np.int32)))) > 1:
        raise AssertionError("int8 codes differ by more than one step")
    qs = [quantize_int8(r, block) for r in x]
    qw = jnp.stack([a for a, _ in qs])
    sw = jnp.stack([b for _, b in qs])
    got = jax.jit(lambda a, b: dequant_reduce(a, b, block))(qw, sw)
    want = sum(dequantize_int8(a, b, block) for a, b in qs)
    _agree(got, want, "dequant_reduce")


def _probe_block_sparse():
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas import block_sparse as bs

    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q, k, v = (jax.random.normal(kk, (1, 2, 256, 128), jnp.bfloat16)
               for kk in ks)
    layout = bs.causal_layout(bs.dense_layout(2))
    got = jax.jit(lambda q, k, v: bs.block_sparse_attention(
        q, k, v, layout, 128))(q, k, v)
    with jax.default_matmul_precision("highest"):
        want = bs.block_sparse_attention_reference(
            *(x.astype(jnp.float32) for x in (q, k, v)), layout, 128)
    _agree(got, want, "block-sparse attention")


def _probe_evoformer():
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas.evoformer import _reference, evoformer_flash

    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    q, k, v = (jax.random.normal(kk, (2, 128, 2, 128), jnp.bfloat16)
               for kk in ks[:3])
    bias = jax.random.normal(ks[3], (1, 2, 128, 128), jnp.float32)
    got = jax.jit(evoformer_flash)(q, k, v, bias)
    with jax.default_matmul_precision("highest"):
        want = _reference(*(x.astype(jnp.float32) for x in (q, k, v)), bias)
    _agree(got, want, "evoformer attention")


def _probe_aio():
    from deepspeed_tpu.ops.aio import _build_library

    _build_library()


KERNEL_PROBES = (
    ("pallas.flash_attention (fwd+bwd)", _probe_flash),
    ("pallas.paged_attention", _probe_paged),
    ("megablox.gmm (moe grouped_dot)", _probe_gmm),
    ("pallas.quantization (int8)", _probe_quantization),
    ("pallas.block_sparse", _probe_block_sparse),
    ("pallas.evoformer", _probe_evoformer),
    ("aio (csrc build)", _probe_aio),
)


def op_report() -> list:
    """Status of each accelerated op (reference ``op_report``): every
    probe compiles the kernel for the live backend (Mosaic on a TPU, the
    Pallas interpreter elsewhere), runs it once and compares it with its
    reference. → ``[(name, status)]``; a status other than
    :data:`GREEN_OK` carries the first line of what was raised."""
    rows = []
    for name, fn in KERNEL_PROBES:
        try:
            fn()
            rows.append((name, GREEN_OK))
        except Exception as e:  # noqa: BLE001 — the report lists every op
            first = (str(e).strip().splitlines() or [""])[0]
            rows.append((name, f"{RED_NO} ({type(e).__name__}: {first})"))
    rows.extend((name, f"{UNWIRED_TAG} ({why})") for name, why in UNWIRED)
    return rows


def main() -> None:
    import jax

    import deepspeed_tpu

    print("-" * 60)
    print("deepspeed_tpu environment report")
    print("-" * 60)
    print(f"deepspeed_tpu version ... {deepspeed_tpu.__version__}")
    print(f"python .................. {sys.version.split()[0]}")
    print(f"jax ..................... {_try_version('jax')}")
    print(f"flax .................... {_try_version('flax')}")
    print(f"optax ................... {_try_version('optax')}")
    print(f"orbax.checkpoint ........ {_try_version('orbax.checkpoint')}")
    print(f"numpy ................... {_try_version('numpy')}")
    gxx = shutil.which("g++")
    print(f"g++ ..................... {gxx or RED_NO}")
    print("-" * 60)
    print(f"backend ................. {jax.default_backend()}")
    print(f"process count ........... {jax.process_count()}")
    print(f"device count ............ {jax.device_count()}")
    devs = jax.devices()
    if devs:
        print(f"device[0] ............... {devs[0].device_kind}")
    print("-" * 60)
    print("op compatibility (each kernel compiled, run, compared):")
    rows = op_report()
    for name, status in rows:
        print(f"  {name:.<36} {status}")
    print("-" * 60)
    if any(status.startswith(RED_NO) for _, status in rows):
        sys.exit(1)


if __name__ == "__main__":
    main()
