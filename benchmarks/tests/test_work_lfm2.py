"""work_lfm2.py's counts against values worked by hand, at the widths of
``configs/lfm2-8b-a1b-l14.json``: hidden 2048, 32 query heads over 8 kv
heads of 64, dense width 7168, 32 experts of 1792 with 4 a token,
vocabulary 65,536; 14 layers: 3 attention and 11 convolution mixers, 2
dense and 12 expert MLPs."""
import common
import work
import work_lfm2

CFG = common.load_json(common.HERE, "configs", "lfm2-8b-a1b-l14.json")
PEAKS = common.load_json(common.HERE, "peaks.json")["TPU v5 lite"]


def test_layers_by_kind():
    assert work_lfm2.layer_counts(CFG) == {"attn": 3, "conv": 11,
                                           "dense": 2, "moe": 12}
    assert work_lfm2.head_dim(CFG) == 64


def test_active_parameters():
    # attention mixer: q and o 2048 x 2048 each, k and v 2048 x 512 each
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512
    assert attn == 10_485_760
    # convolution mixer: 2048 -> 6144 in, 2048 -> 2048 out
    conv = 2048 * 6144 + 2048 * 2048
    assert conv == 16_777_216
    dense = 3 * 2048 * 7168
    assert dense == 44_040_192
    # 4 experts of 3 x 2048 x 1792 and the router's 2048 x 32
    moe = 4 * 3 * 2048 * 1792 + 2048 * 32
    assert moe == 44_105_728
    total = 3 * attn + 11 * conv + 2 * dense + 12 * moe
    assert total == 833_355_776
    assert work_lfm2.active_matmul_params(CFG) == total


def test_forward_flops():
    # a position: twice the active weights, and 2 x 3 x 2048 = 12,288 for
    # the taps of each of the 11 convolution layers
    per_position = 2 * 833_355_776 + 11 * 12_288
    assert per_position == 1_666_846_720
    # an attended position: q.k and p.v over 32 heads of 64 in each of
    # the 3 attention layers; the tied head: 2 x 2048 x 65,536
    per_attended, per_head = 4 * 2048 * 3, 2 * 2048 * 65_536
    assert (per_attended, per_head) == (24_576, 268_435_456)
    # a decode step of 128 lanes at cache length 450 each
    got = work_lfm2.forward_flops(CFG, 128, 128 * 450, 128)
    assert got == 128 * per_position + 57_600 * per_attended \
        + 128 * per_head == 249_131_696_128
    # a prompt of 161 prefilled: it attends 161 * 162 / 2 and heads once
    got = work_lfm2.forward_flops(CFG, 161, 161 * 162 / 2, 1)
    assert got == 161 * per_position + 13_041 * per_attended + per_head


def test_one_expert_layer_of_a_decode_step():
    # 128 lanes x 4 experts = 512 rows over 32 experts: every expert hit
    f, b = work_lfm2.expert_layer(512, CFG)
    assert f == 6 * 512 * 2048 * 1792 == 11_274_289_152
    # all 32 experts' 3 x 2048 x 1792 weights and the rows in and out
    assert b == (32 * 11_010_048 + 2 * 512 * 2048) * 2 == 708_837_376
    t, bound = work.roofline_seconds(f, b, PEAKS)
    assert bound == "hbm" and abs(t - 708_837_376 / 819e9) < 1e-12
    # fewer rows than experts: only the experts hit are read
    f, b = work_lfm2.expert_layer(8, CFG)
    assert b == (8 * 11_010_048 + 2 * 8 * 2048) * 2


def test_decode_attention_of_one_layer():
    # 128 lanes at cache length 450: 57,600 attended positions
    f, b = work_lfm2.decode_attention(57_600, CFG)
    assert f == 4 * 57_600 * 32 * 64 == 471_859_200
    # K and V of the 8 kv heads of 64, bfloat16
    assert b == 2 * 57_600 * 8 * 64 * 2 == 117_964_800
    t, bound = work.roofline_seconds(f, b, PEAKS)
    assert bound == "hbm"
