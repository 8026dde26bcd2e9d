"""Operation and byte counts against hand arithmetic, and the peaks
table."""

import pytest

import _path  # noqa: F401
import costs
import peaks

M = {"hidden_size": 4096, "num_attention_heads": 32,
     "num_key_value_heads": 8, "head_dim": 128, "intermediate_size": 12288,
     "num_hidden_layers": 12, "vocab_size": 151936}


def test_layer_and_kv_sizes():
    # qwen3-8b: q 4096x4096, k and v 4096x1024, o 4096x4096, ffn 3x4096x12288
    assert costs.layer_matmul_params(M) == \
        2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 12288
    assert costs.layer_matmul_params(M) == 192_937_984
    assert costs.kv_bytes_per_token(M) == 49_152      # 12 x 2 x 8 x 128 x 2


def test_paged_decode_kernel():
    f, b = costs.paged_decode_kernel(M, [1000, 3000])
    assert f == 4 * 32 * 128 * 4000
    assert b == 2 * 8 * 128 * 2 * 4000 + 2 * 2 * 32 * 128 * 2


def test_packed_prefill_kernel():
    # a fresh 2,048-token chunk and a 1,024-token chunk after 4,096 cached
    segs = [(0, 2048), (4096, 1024)]
    pairs = 2048 * 2049 // 2 + (1024 * 4096 + 1024 * 1025 // 2)
    f, b = costs.packed_prefill_kernel(M, segs)
    assert f == 4 * 32 * 128 * pairs
    assert b == 2 * 8 * 128 * 2 * (2048 + 5120) + 2 * 3072 * 32 * 128 * 2


def test_steps():
    p, V = costs.layer_matmul_params(M), 151936
    f, b = costs.decode_step(M, [100, 200])
    assert f == 2 * (2 * 12 * p + 2 * 4096 * V) + 12 * 4 * 32 * 128 * 300
    assert b == (12 * p + 4096 * V) * 2 + 12 * 2 * 8 * 128 * 2 * 300 \
        + 2 * 49_152
    f, b = costs.prefill_step(M, [(0, 512)])
    assert f == 2 * 512 * 12 * p + 2 * 4096 * V \
        + 12 * 4 * 32 * 128 * (512 * 513 // 2)


def test_ideal_seconds_takes_the_binding_bound():
    pk = peaks.peaks_for("TPU v5 lite")
    assert costs.ideal_seconds(197e12, 1.0, pk) == pytest.approx(1.0)
    assert costs.ideal_seconds(1.0, 819e9, pk) == pytest.approx(1.0)


def test_peaks_refuse_an_unknown_device():
    assert peaks.peaks_for("TPU v5 lite").bf16_flops == 197e12
    with pytest.raises(SystemExit, match="no peaks"):
        peaks.peaks_for("TPU v9 imaginary")
