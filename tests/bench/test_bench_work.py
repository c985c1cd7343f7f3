"""Work functions and peaks against hand-computed values."""
import pytest

import bench_smoke  # noqa: F401  (puts the checkout on sys.path)
from bench.lib import registry, work


def _shape(name):
    return work.Shape(registry.config(name))


def test_minitron_step_reads_880_mb_of_indices_and_1_57_gb_of_head():
    s = _shape("minitron-4b")
    # per layer, 2 bits per weight: 3072 x (5120 + 3072 + 18432) / 4
    # + 9216 x 3072 / 4 = 27,525,120 bytes; 32 layers
    assert s.index_bytes() == 27_525_120 * 32 == 880_803_840
    assert s.head_bytes() == 3072 * 256000 * 2 == 1_572_864_000


def test_qwen2_72b_stage_reads_4_39_gb_of_indices():
    s = _shape("qwen2-72b-pp4")
    # 8192 x (10240 + 8192 + 59136) / 4 + 29568 x 8192 / 4 per layer
    assert s.index_bytes() == 219_414_528 * 20 == 4_388_290_560
    assert s.layer_params() == 877_658_112   # 877.7 M weights a layer


def test_eva_flops_and_bytes_by_hand():
    # M=16, K=3072, N=5120, C=2, n=8, d=8
    eva = 2 * 16 * 3072 * 256 * 2 + 16 * 5120 * 384 * 2
    assert work.vq_flops(16, 3072, 5120, 2, 8, 8) == min(
        2 * 16 * 3072 * 5120, eva) == eva
    assert work.vq_flops(1, 3072, 64, 2, 8, 8) == 2 * 3072 * 64  # dense wins
    assert work.vq_bytes(16, 3072, 5120, 2, 8, 8) == (
        3072 * 5120 // 4 + 2 * 8 * 256 * 4 + 5120 * 4
        + 2 * 16 * 3072 + 2 * 16 * 5120)


@pytest.mark.parametrize("name", ["minitron-4b", "qwen2-72b-pp4"])
@pytest.mark.parametrize("M", [1, 8, 16, 128, 1024])
def test_least_time_never_above_a_dense_bf16_roofline(name, M):
    """The EVA format never counts more bytes or FLOPs than a dense bf16
    weight would, so a share of its least time stays under 100% of any
    execution that is at least as fast as the dense roofline allows."""
    s = _shape(name)
    pk = work.peak("TPU v5 lite")
    dense = s.layers * sum(
        max((2 * K * N + 2 * M * K + 2 * M * N) / pk["hbm_bytes_per_s"],
            2 * M * K * N / pk["bf16_flop_per_s"])
        for _, K, N in s.vq_linears())
    least = work.vq_least_seconds(s, M, pk)
    assert 0 < least <= dense
    # the packed indices alone bound it from below
    assert least >= s.index_bytes() / pk["hbm_bytes_per_s"]


def test_decode_flops():
    s = _shape("minitron-4b")
    per_row = 2 * (32 * s.layer_params() + 3072 * 256000)
    attn = 4 * 32 * 24 * 128
    assert work.decode_flops(s, [100, 200]) == 2 * per_row + attn * 300


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        work.peak("cpu")
    assert work.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
