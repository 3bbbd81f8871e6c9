"""Roofline and MFU counts against hand-worked numbers for both configs."""
import pytest

from benchmarks.chip import cost, harness
from benchmarks.chip.peaks import peaks_for

BENCH = harness.benchmark()
SMOL = harness.config_file(BENCH, "smollm-360m")
QWEN = harness.load_json(harness.HERE / "configs" / "qwen3-8b-pp4-last.json")
V5E = peaks_for("TPU v5 lite")


def test_smollm_parameter_counts():
    # q 960*960 + k,v 2*960*320 + o 960*960 + mlp 3*960*2560
    assert cost.layer_matmul_params(SMOL) == 9_830_400
    # 32 layers + the tied head 49152*960
    assert cost.matmul_params(SMOL) == 314_572_800 + 47_185_920
    # + norms 32*2*960 + 960, embedding counted once (tied)
    assert cost.param_count(SMOL) == 361_821_120
    assert cost.kv_bytes_per_token(SMOL) == 2 * 32 * 5 * 64 * 2 == 40_960


def test_qwen_stage_parameter_counts():
    # q 4096*4096 + k,v 2*4096*1024 + o 4096*4096 + mlp 3*4096*12288
    assert cost.layer_matmul_params(QWEN) == 192_937_984
    assert cost.matmul_params(QWEN) == 9 * 192_937_984 + 622_329_856
    # + norms 9*2*4096 + 4096 + qk norms 9*2*128, untied embedding and head
    assert cost.param_count(QWEN) == 2_981_181_696
    assert cost.kv_bytes_per_token(QWEN) == 2 * 9 * 8 * 128 * 2 == 36_864


def test_smollm_decode_step_by_hand():
    c = cost.decode_step(SMOL, [100, 300], batch=64)
    # 2 rows through 361,758,720 matrix entries, attention 4*32*15*64 per key
    assert c.flops == 2 * 361_758_720 * 2 + 122_880 * 400
    # weights and norms once, K/V of 400 tokens, 2 embedding rows,
    # logits and two int32 histograms of 64 x 49152
    assert c.bytes == (723_517_440 + 124_800 + 40_960 * 400 + 3_840
                       + 3 * 64 * 49_152 * 4)
    t, bound = cost.least_time(c, V5E)
    assert bound == "memory"
    assert t == pytest.approx(777_778_816 / 819e9)


def test_qwen_decode_step_is_memory_bound_at_64_rows():
    c = cost.decode_step(QWEN, [1024] * 64, batch=64)
    t, bound = cost.least_time(c, V5E)
    assert bound == "memory"
    assert c.bytes == pytest.approx(
        2 * (9 * 192_937_984 + 622_329_856) + 2 * (9 * 2 * 4096 + 4096)
        + 36_864 * 64 * 1024 + 64 * 4096 * 2 + 3 * 64 * 151_936 * 4)


def test_prefill_and_token_flops_by_hand():
    # 256 tokens through the layers, causal attention over 256*257/2 keys,
    # the head once
    assert cost.prefill_flops(SMOL, 256) == (
        2 * 314_572_800 * 256 + 122_880 * 256 * 257 / 2 + 2 * 49_152 * 960)
    assert cost.decode_token_flops(QWEN, 10) == (
        2 * cost.matmul_params(QWEN) + 4 * 9 * 32 * 128 * 10)


def test_compute_bound_when_flops_dominate():
    t, bound = cost.least_time(cost.Cost(flops=197e12, bytes=1.0), V5E)
    assert bound == "compute" and t == pytest.approx(1.0)
