"""The FLOP and byte counts against hand counts, through the family
that each configuration names."""
import pytest

from bench import common, flops, peaks


def model(name):
    """(family module, Dims) of ``bench/configs/<name>.json``."""
    cfg = common.load_json(common.BENCH_DIR / "configs" / f"{name}.json")
    fam = common.family(cfg)
    return fam, fam.Dims.from_config(cfg)


# per layer: wq 3072*3072 + wk, wv 2*3072*1024 + wo 3072*3072
#            + SwiGLU 3*3072*9216
MINITRON_LAYER = 9_437_184 + 6_291_456 + 9_437_184 + 84_934_656


def test_minitron_stage4_train_flops():
    fam, d = model("minitron-4b-stage4")
    assert MINITRON_LAYER == 110_100_480
    n = 4 * MINITRON_LAYER + 3072 * 32000     # + output head, no embedding
    assert fam.matmul_params(d) == n == 538_705_920
    attn = 6 * 24 * 128 * 4097 * 4            # causal, fwd + bwd
    assert fam.train_flops_per_token(d, 4096) == 6 * n + attn
    assert fam.train_flops_per_token(d, 4096) == pytest.approx(3.534e9,
                                                               rel=1e-3)


def test_minitron_stage8_train_flops():
    """The 8-layer stage that an earlier benchmark ran (6.5 GFLOP a token
    by hand), from the same widths."""
    import dataclasses
    fam, d = model("minitron-4b-stage4")
    d = dataclasses.replace(d, n_layers=8)
    n = 8 * MINITRON_LAYER + 3072 * 32000
    assert fam.matmul_params(d) == n == 979_107_840
    attn = 6 * 24 * 128 * 4097 * 8
    assert fam.train_flops_per_token(d, 4096) == 6 * n + attn
    assert fam.train_flops_per_token(d, 4096) == pytest.approx(6.479e9,
                                                               rel=1e-3)


def test_deepseek_coder_stage8_params():
    fam, d = model("deepseek-coder-33b-stage8")
    per_layer = 2 * 7168 * 7168 + 2 * 7168 * 1024 + 3 * 7168 * 19200
    assert fam.matmul_params(d) == 8 * per_layer + 7168 * 32256


def test_paged_call_hand_count():
    _, d = model("deepseek-coder-33b-stage8")
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
    fam, d = model("deepseek-coder-33b-stage8")
    layer = 2 * 7168 * 7168 + 2 * 7168 * 1024 + 3 * 7168 * 19200
    head = 7168 * 32256
    assert fam.prefill_flops(d, 10) == \
        2 * 10 * 8 * layer + 2 * 56 * 128 * 10 * 11 * 8 + 2 * head
    assert fam.decode_flops(d, 100) == \
        2 * 8 * layer + 4 * 56 * 128 * 100 * 8 + 2 * head
