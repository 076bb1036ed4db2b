"""The FLOP and byte counts against hand counts."""
import pytest

from bench import common, flops, peaks, reference


def dims(name):
    return reference.Dims.from_config(
        common.load_json(common.BENCH_DIR / "configs" / f"{name}.json"))


# per layer: wq 3072*3072 + wk, wv 2*3072*1024 + wo 3072*3072
#            + SwiGLU 3*3072*9216
MINITRON_LAYER = 9_437_184 + 6_291_456 + 9_437_184 + 84_934_656


def test_minitron_stage4_train_flops():
    d = dims("minitron-4b-stage4")
    assert MINITRON_LAYER == 110_100_480
    n = 4 * MINITRON_LAYER + 3072 * 32000     # + output head, no embedding
    assert flops.matmul_params(d) == n == 538_705_920
    attn = 6 * 24 * 128 * 4097 * 4            # causal, fwd + bwd
    assert flops.train_flops_per_token(d, 4096) == 6 * n + attn
    assert flops.train_flops_per_token(d, 4096) == pytest.approx(3.534e9,
                                                                 rel=1e-3)


def test_minitron_stage8_train_flops():
    """The 8-layer stage that an earlier benchmark ran (6.5 GFLOP a token
    by hand), from the same widths."""
    import dataclasses
    d = dataclasses.replace(dims("minitron-4b-stage4"), n_layers=8)
    n = 8 * MINITRON_LAYER + 3072 * 32000
    assert flops.matmul_params(d) == n == 979_107_840
    attn = 6 * 24 * 128 * 4097 * 8
    assert flops.train_flops_per_token(d, 4096) == 6 * n + attn
    assert flops.train_flops_per_token(d, 4096) == pytest.approx(6.479e9,
                                                                 rel=1e-3)


def test_deepseek_coder_stage8_params():
    d = dims("deepseek-coder-33b-stage8")
    per_layer = 2 * 7168 * 7168 + 2 * 7168 * 1024 + 3 * 7168 * 19200
    assert flops.matmul_params(d) == 8 * per_layer + 7168 * 32256


def test_paged_call_hand_count():
    d = dims("deepseek-coder-33b-stage8")
    f, b = flops.paged_attention_cost(d, [10, 20], kv_bytes=2)
    assert f == 4 * 56 * 128 * 30                       # 860,160
    assert b == 2 * 8 * 128 * 2 * 30 + 8 * 56 * 128 * 2  # K, V + f32 q, out
    assert (f, b) == (860_160, 237_568)


def test_peaks_table():
    pk = peaks.peak("TPU v5 lite")
    assert pk["bf16_flops"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peak("cpu")


def test_serving_forward_hand_count():
    d = dims("deepseek-coder-33b-stage8")
    layer = 2 * 7168 * 7168 + 2 * 7168 * 1024 + 3 * 7168 * 19200
    head = 7168 * 32256
    assert flops.prefill_flops(d, 10) == \
        2 * 10 * 8 * layer + 2 * 56 * 128 * 10 * 11 * 8 + 2 * head
    assert flops.decode_flops(d, 100) == \
        2 * 8 * layer + 4 * 56 * 128 * 100 * 8 + 2 * head
