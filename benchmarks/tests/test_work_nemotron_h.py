"""work_nemotron_h.py's counts against values worked by hand, at the
widths of ``configs/nemotron-3-nano-30b-a3b-l13.json``: hidden 2688; 64
Mamba heads of 64 in 8 groups, state 128, kernel 4; 32 query heads over 2
kv heads of 128; 64 held of 128 ranked experts of 1856, 6 a token, a
shared expert of 3712; vocabulary slice 65,536; 13 blocks
``MEMEM*EMEMEM*``: 6 Mamba-2, 2 attention, 5 expert blocks."""
import common
import work
import work_nemotron_h as w

CFG = common.load_json(common.HERE, "configs",
                       "nemotron-3-nano-30b-a3b-l13.json")
PEAKS = common.load_json(common.HERE, "peaks.json")["TPU v5 lite"]


def test_blocks_by_kind_and_widths():
    assert w.layer_counts(CFG) == {"ssm": 6, "attn": 2, "moe": 5}
    assert w.d_inner(CFG) == 4096 and w.conv_dim(CFG) == 6144
    assert w.state_elements(CFG) == 64 * 64 * 128 == 524_288
    assert w.routed_here(CFG) == 3.0            # 6 x 64 / 128


def test_active_parameters():
    # Mamba-2: 2688 -> 4096 + 6144 + 64 in, 4096 -> 2688 out
    ssm = 2688 * 10304 + 4096 * 2688
    assert ssm == 38_707_200
    # attention: q and o 2688 x 4096 each, k and v 2688 x 256 each
    attn = 2 * 2688 * 4096 + 2 * 2688 * 256
    assert attn == 23_396_352
    # experts: the router's 2688 x 128, the shared expert's up and down at
    # 3712, and 3 routed experts' up and down at 1856
    moe = 2688 * 128 + 2 * 2688 * 3712 + 3 * 2 * 2688 * 1856
    assert moe == 50_233_344
    total = 6 * ssm + 2 * attn + 5 * moe
    assert total == 530_202_624
    assert w.active_matmul_params(CFG) == total


def test_forward_flops():
    # a position: twice the active weights; in each Mamba-2 block the
    # convolution's 2 x 4 x 6144 = 49,152 and 5 FLOPs a state element
    ssm = 49_152 + 5 * 524_288
    assert ssm == 2_670_592
    per_position = 2 * 530_202_624 + 6 * ssm
    assert per_position == 1_076_428_800
    head = 2 * 2688 * 65_536
    assert head == 352_321_536
    assert w.forward_flops(CFG, 1, 0, 0) == per_position
    assert w.forward_flops(CFG, 0, 0, 1) == head
    # attended positions: 4 x 4096 a position in each of 2 attention blocks
    assert w.forward_flops(CFG, 0, 1000, 0) == 4 * 4096 * 2 * 1000
    # a prompt of 100: its positions, the causal triangle, one head
    assert w.forward_flops(CFG, 100, 5050, 1) == \
        100 * per_position + 4 * 4096 * 2 * 5050 + head


def test_expert_layer_counts_the_published_bytes():
    # 768 rows on 64 held experts: 4 x 768 x 2688 x 1856 FLOPs; every
    # expert's up and down at 1856 once, and the rows in and out
    flops, nbytes = w.expert_layer(768, CFG)
    assert flops == 4 * 768 * 2688 * 1856 == 15_325_986_816
    assert nbytes == (64 * 2 * 2688 * 1856 + 2 * 768 * 2688) * 2 \
        == 1_285_423_104
    least, bound = work.roofline_seconds(flops, nbytes, PEAKS)
    assert bound == "hbm" and abs(least - 1.5696e-3) < 1e-6
    # fewer rows than experts: only the experts hit are read
    assert w.expert_layer(10, CFG)[1] == (10 * 2 * 2688 * 1856
                                          + 2 * 10 * 2688) * 2


def test_decode_attention_reads_two_kv_heads():
    flops, nbytes = w.decode_attention(1000, CFG)
    assert flops == 4 * 1000 * 32 * 128
    assert nbytes == 2 * 1000 * 2 * 128 * 2


def test_ssm_update_reads_and_writes_the_state_once():
    # 256 live lanes: 256 x 2 MB read and as much written
    flops, nbytes = w.ssm_update(256, CFG)
    assert nbytes == 2 * 256 * 524_288 * 4 == 1_073_741_824
    assert flops == 5 * 256 * 524_288
    least, bound = work.roofline_seconds(flops, nbytes, PEAKS)
    assert bound == "hbm" and abs(least - 1.3110e-3) < 1e-6
