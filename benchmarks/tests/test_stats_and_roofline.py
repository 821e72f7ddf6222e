import types

import pytest

from benchmarks import stats
from benchmarks.flops import dense as flops
from benchmarks.roofline import flash_attention, hlo_shapes, paged_attention


def test_percentile():
    assert stats.percentile([], 90) is None
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile(list(range(101)), 90) == 90
    assert stats.percentile([0, 10], 95) == pytest.approx(9.5)


def test_failed_request_is_the_largest_value():
    ok = [0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9]
    assert stats.with_failures(ok, 0) == ok
    worse = stats.with_failures(ok, 3)
    assert len(worse) == 12 and worse.count(0.9) == 4
    assert stats.percentile(worse, 90) > stats.percentile(ok, 90)
    assert stats.percentile(worse, 90) == pytest.approx(0.9)
    assert stats.with_failures([], 5) == []


def test_gaps_and_spread():
    assert stats.gaps([1.0, 1.5, 1.6]) == pytest.approx([0.5, 0.1])
    assert stats.spread([100, 100, 100, 100]) == 0
    # the driver's quartiles (statistics.quantiles), wider than numpy's
    assert stats.spread([98, 99, 100, 101, 102]) == pytest.approx(0.03)
    assert stats.spread([1.0, 1.1, 1.2, 1.3, 1.4, 1.5]) == pytest.approx(
        0.35 / 1.25)
    assert stats.spread([1]) is None


def test_train_flops():
    # 12-layer Mistral-7B widths: 2.88 B parameters, 131 M of them the
    # input embedding; 4,096-token sequences
    n = 2_880_000_000
    mm = flops.matmul_params(n, 32000, 4096, tied_embeddings=False)
    assert mm == n - 32000 * 4096
    model = types.SimpleNamespace(vocab_size=32000, hidden_size=4096,
                                  num_layers=12, tie_embeddings=False)
    per_tok = flops.train_flops_per_token(model, n, 4096)
    assert per_tok == 6 * mm + 6 * 12 * 4096 * 4096
    assert flops.matmul_params(n, 32000, 4096, True) == n


FWD = ('%f = (bf16[32,4096,128]{2,1,0}, f32[32,4096,1]{2,1,0}) custom-call('
       'bf16[32,4096,128]{2,1,0} %q, bf16[8,4096,128]{2,1,0} %k, '
       'bf16[8,4096,128]{2,1,0} %v), custom_call_target="tpu_custom_call"')
DKV = ('%g = (bf16[8,4096,128]{2,1,0}, bf16[8,4096,128]{2,1,0}) custom-call('
       'bf16[32,4096,128]{2,1,0} %q, bf16[8,4096,128]{2,1,0} %k, '
       'bf16[8,4096,128]{2,1,0} %v, bf16[32,4096,128]{2,1,0} %do, '
       'f32[32,4096,1]{2,1,0} %lse, f32[32,4096,1]{2,1,0} %delta), '
       'custom_call_target="tpu_custom_call"')
PAGED = ('%p = bf16[64,32,128]{2,1,0} custom-call(s32[64,16]{1,0} %t, '
         's32[64]{0} %l, bf16[64,32,128]{2,1,0} %q, '
         'bf16[38416,32,8,128]{3,2,1,0} %kp, bf16[38416,32,8,128]{3,2,1,0} %vp)'
         ', custom_call_target="tpu_custom_call"')


class _Op:
    is_mosaic = True

    def __init__(self, text):
        self.text = text


def test_hlo_shapes():
    res, ops = hlo_shapes.split(FWD)
    assert res == [("bf16", (32, 4096, 128)), ("f32", (32, 4096, 1))]
    assert ops[1] == ("bf16", (8, 4096, 128)) and len(ops) == 3
    assert hlo_shapes.nbytes(("f32", (32, 4096, 1))) == 32 * 4096 * 4


def test_flash_roofline_by_hand():
    assert flash_attention.classify(_Op(FWD)) == "fwd"
    assert flash_attention.classify(_Op(DKV)) == "dkv"
    assert flash_attention.classify(_Op(PAGED)) is None
    fl, by = flash_attention.ops_and_bytes("fwd", FWD)
    # two causal matmuls: 2 * (2 * 32 * 4096^2 * 128 / 2)
    assert fl == 2 * 2 * 32 * 4096 * 4096 * 128 / 2
    q = 32 * 4096 * 128 * 2
    kv = 8 * 4096 * 128 * 2
    assert by == 2 * q + 2 * kv + 32 * 4096 * 4
    fl3, _ = flash_attention.ops_and_bytes("dkv", DKV)
    assert fl3 == 1.5 * fl
    # 137 GFLOP (0.70 ms at peak) against 84 MB (0.10 ms): compute-bound
    assert fl / 197e12 > 5 * by / 819e9


def test_paged_roofline_by_hand():
    assert paged_attention.classify(_Op(PAGED)) == "paged"
    # one key block + one value block of 32 positions x 8 heads x 128, bf16
    assert paged_attention.block_bytes(PAGED) == 2 * 32 * 8 * 128 * 2
    # a tick whose sequences hold 100 blocks, through 16 layers
    assert paged_attention.needed_bytes(100, 16, 131072) == 100 * 16 * 131072

    class Run:
        peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
        client = {"ticks": [(0, 1, 0, 5, 999), (1, 2, 0, 5, 100),
                            (2, 3, 9, 5, 50)],
                  "marks": {"trace_from_tick": 1, "trace_to_tick": 3}}

    calls = [_Op(PAGED)] * 32                  # 2 traced ticks x 16 layers
    seconds, bound = paged_attention.least_seconds(Run, calls)
    assert bound == "memory"
    assert seconds == pytest.approx(150 * 16 * 131072 / 819e9)
    assert paged_attention.least_seconds(Run, []) is None


def test_flash_least_seconds():
    class Run:
        peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}

    seconds, bound = flash_attention.least_seconds(Run, [_Op(FWD), _Op(DKV)])
    fl, _ = flash_attention.ops_and_bytes("fwd", FWD)
    assert bound == "compute"
    assert seconds == pytest.approx(2.5 * fl / 197e12)
