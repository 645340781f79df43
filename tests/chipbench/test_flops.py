"""flops.py against counts worked by hand from the published shapes."""

import pytest

from chipbench_helpers import ROOT, read

from chipbench import flops

M = read(f"{ROOT}/chipbench/configs/mistral-7b.json")
I = read(f"{ROOT}/chipbench/configs/internlm2-1.8b.json")


def test_mistral_by_hand():
    # per layer: wq 4096x4096, wk and wv 4096x1024, wo 4096x4096, three
    # 4096x14336 FFN matrices, two norms
    layer = 16_777_216 + 2 * 4_194_304 + 16_777_216 + 3 * 58_720_256 + 8192
    assert layer == 218_112_000
    assert flops.num_params({**M, "num_hidden_layers": 32}) == \
        32 * layer + 2 * 32768 * 4096 + 4096 == 7_248_023_552  # the published 7.25B
    assert flops.num_params(M) == M["num_hidden_layers"] * layer + 268_439_552
    # forward, per token, seq 2048: projections 2 x 41.9M, attention
    # 2 x 2 x 4096 x 2049 / 2, FFN 2 x 176.2M, head 2 x 134.2M
    per_layer = 2 * 41_943_040 + 2 * 4096 * 2049 + 2 * 176_160_768
    assert per_layer == 452_993_024
    fwd = M["num_hidden_layers"] * per_layer + 2 * 4096 * 32768
    assert flops.forward_flops_per_token(M, 2048) == fwd
    assert flops.train_flops_per_token(M, 2048) == 3 * fwd


def test_internlm2_by_hand():
    layer = 4_194_304 + 2 * 2_097_152 + 4_194_304 + 3 * 16_777_216 + 4096
    assert layer == 62_918_656
    assert flops.num_params({**I, "num_hidden_layers": 24}) == \
        24 * layer + 2 * 92544 * 2048 + 2048 == 1_889_110_016  # the published 1.89B
    per_layer = 2 * 12_582_912 + 2 * 2048 * 2049 + 2 * 50_331_648
    fwd = I["num_hidden_layers"] * per_layer + 2 * 2048 * 92544
    assert flops.forward_flops_per_token(I, 2048) == fwd
    # the head is most of the cut model's arithmetic
    assert 2 * 2048 * 92544 / fwd > 0.55


def test_mfu_and_peaks():
    assert flops.peaks("TPU v5 lite") == {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}
    with pytest.raises(ValueError, match="no peaks known"):
        flops.peaks("TPU v9 imaginary")
    want = flops.train_flops_per_token(M, 2048) * 18_774.5 / 197e12
    assert flops.mfu(M, 2048, 18_774.5, "TPU v5 lite") == pytest.approx(want)
    assert 0.3 < want < 0.7


def test_attention_kernel_cost():
    c = flops.attention_kernel_cost(M, 4, 2048, "fwd")
    pairs = 4 * 32 * 2048 * 2049 / 2
    assert c["flops"] == 2 * 2 * pairs * 128
    assert c["bytes"] == 2 * (4 * 2048 * 32 * 128 * 2) + 2 * (4 * 2048 * 8 * 128 * 2)
    assert flops.attention_kernel_cost(M, 4, 2048, "bwd")["flops"] == 2.5 * c["flops"]
    t, bound = flops.roofline_floor_s(c, "TPU v5 lite")
    assert bound == "compute" and t == pytest.approx(c["flops"] / 197e12)
