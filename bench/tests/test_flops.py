"""The FLOP counts of both configurations against a count by hand from
their published widths, at the cell's batch 4 x 2048 tokens."""
import importlib
import os

from bench.harness.spec import BENCH, load_json

B, S = 4, 2048


def config(name: str):
    c = load_json(os.path.join(BENCH, "configs", name + ".json"))
    return c, importlib.import_module("bench.models." + c["family"])


def test_yi6b_step_flops_by_hand():
    c, model = config("yi6b")
    # per layer: q 4096*4096, k and v 4096*512 each, o 4096*4096,
    # SwiGLU 3 * 4096*11008; two layers; head 4096 * 8000 live rows
    layer = 16_777_216 + 2 * 2_097_152 + 16_777_216 + 135_266_304
    params = 2 * layer + 32_768_000
    assert params == 378_798_080
    # causal attention: QK^T and AV over 2048*2049/2 pairs, 32 heads of 128
    attn = 2 * 2 * 2 * 2_098_176 * 32 * 128
    want = 3 * B * (2 * params * S + attn)
    assert model.step_flops(c, B, S) == want
    assert abs(want - 1.9444e13) < 1e10


def test_mamba2_step_flops_by_hand():
    c, model = config("mamba2")
    # in_proj 2048 -> 2*4096 + 2*128 + 64 = 8512; out_proj 4096 -> 2048;
    # depthwise conv 4 taps over 4096 + 256 channels
    proj = 2 * 2048 * 8512 + 2 * 4096 * 2048 + 2 * 4 * 4352
    # SSD by chunks of 128: 16 chunks of 128*129/2 causal pairs, each pair
    # a C.B product (128) and a scores.x product (64 heads * 64); state in
    # and out: 2 * 2 * 64 * 64 * 128 per token
    pairs = 16 * 8256
    ssd = 2 * pairs * (128 + 4096) + 4 * S * 64 * 64 * 128
    head = 2 * 2048 * 6285
    want = 3 * B * (16 * (proj * S + ssd) + head * S)
    assert model.step_flops(c, B, S) == want
    assert abs(want - 2.2e13) < 0.05e13
