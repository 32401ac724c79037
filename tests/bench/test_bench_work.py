"""The benchmark's counts of operations and bytes, against values worked out
by hand from the published sizes."""
import json
import os

import pytest

from benchmarks.chip import work

CONFIGS = os.path.join(os.path.dirname(__file__), "..", "..", "benchmarks",
                       "chip", "configs")


def spec(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def test_deepseek_7b_parameter_counts():
    s = spec("deepseek-7b")
    # per layer: q, k, v 4096 x 3 x 32 x 128; o 32 x 128 x 4096;
    # gate, up, down 3 x 4096 x 11008
    per_layer = 50_331_648 + 16_777_216 + 135_266_304
    assert work.layer_matmul_params(s) == 30 * per_layer == 6_071_255_040
    assert work.head_params(s) == 4096 * 102400 == 419_430_400
    # with the embedding and 61 norm vectors: the bf16 bytes on the chip
    total = 6_071_255_040 + 2 * 419_430_400 + 61 * 4096
    assert 2 * total == 13_820_731_392


def test_mamba2_370m_parameter_counts():
    s = spec("mamba2-370m")
    # per layer: in_proj 1024 x (2*2048 + 2*128 + 32); out_proj 2048 x 1024
    assert work.layer_matmul_params(s) == 48 * (4_489_216 + 2_097_152)
    assert work.head_params(s) == 1024 * 50280 == 51_486_720
    # conv 48 x 5 x 2304, dt_bias/a_log/D 48 x 3 x 32, norms 48 x 3072 + 1024
    total = 316_145_664 + 51_486_720 + 552_960 + 4_608 + 147_456 + 1_024
    assert 2 * total == 736_676_864


def test_deepseek_7b_task_flops():
    # B=1, S=128, N=64: 191 tokens through the layers, 64 head evaluations;
    # attention 30 layers x 4 x 32 x 128 per attended key: prompt keys
    # 128*129/2 = 8256, decode keys 129 + ... + 191 = 10080
    want = (2 * 6_071_255_040 * 191 + 2 * 419_430_400 * 64
            + 30 * 16384 * 8256 + 30 * 16384 * 10080)
    assert work.task_flops(spec("deepseek-7b"), 1, 128, 64) == want
    assert work.task_flops(spec("deepseek-7b"), 8, 128, 64) == 8 * want


def test_mamba2_370m_task_flops():
    # B=1, S=256, N=16: 271 tokens through the layers; SSD per prompt token
    # and head (Q=256, N=128, P=64): 257 x 192 + 4 x 128 x 64 = 82112;
    # per decode token and head 5 x 64 x 128 = 40960; 48 layers x 32 heads
    want = (2 * 316_145_664 * 271 + 2 * 51_486_720 * 16
            + 48 * 32 * 82112 * 256 + 48 * 32 * 40960 * 15)
    assert work.task_flops(spec("mamba2-370m"), 1, 256, 16) == want


def test_flash_attention_work():
    flops, nbytes = work.flash_attention_work(spec("deepseek-7b"), 2, 1024)
    # 30 layers x B 2 x 4 x 32 heads x 128 x 1024*1025/2 keys
    assert flops == 30 * 2 * 4 * 32 * 128 * 524_800
    # q, k, v, o: 4 x 1024 x 32 x 128 x 2 bytes per layer and row
    assert nbytes == 30 * 2 * 4 * 1024 * 32 * 128 * 2
    # compute-bound on a v5e: flops/197e12 > bytes/819e9
    assert flops / 197e12 > nbytes / 819e9


def test_ssd_scan_work():
    flops, nbytes = work.ssd_scan_work(spec("mamba2-370m"), 2, 8192)
    assert flops == 48 * 2 * 32 * 8192 * 82112
    # per layer and row: x and y bf16 and dt f32 (8192 x 32 x 260), B and
    # C bf16 (8192 x 2 x 128 x 2), the final state f32 (32 x 64 x 128 x 4)
    per = 8192 * 32 * 260 + 8192 * 512 + 1_048_576
    assert nbytes == 48 * 2 * per


@pytest.mark.parametrize("name", ["deepseek-7b", "mamba2-370m"])
def test_counts_grow_with_the_task(name):
    s = spec(name)
    assert work.task_flops(s, 1, 256, 8) > work.task_flops(s, 1, 128, 8)
    assert work.task_flops(s, 1, 128, 16) > work.task_flops(s, 1, 128, 8)
