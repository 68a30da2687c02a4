"""work.py's counts against values worked by hand."""
import common
import work

PEAKS = common.load_json(common.HERE, "peaks.json")["TPU v5 lite"]


def test_flash_b16_s1024_h12_d64():
    # per (batch, head): QK^T and PV are 2 * 1024 * 1024 * 64 FLOPs each
    # = 268,435,456 for both; causal halves it: 134,217,728.
    # 16 * 12 = 192 of them: 25,769,803,776 forward.
    f, b = work.flash_forward(16, 1024, 12, 64)
    assert f == 25_769_803_776
    # q, k, v read and o written: 4 * 16*1024*12*64 * 2 bytes
    assert b == 4 * 12_582_912 * 2 == 100_663_296
    # backward: four products for the forward's two, recompute not counted
    f2, b2 = work.flash_backward(16, 1024, 12, 64)
    assert f2 == 51_539_607_552
    assert b2 == 201_326_592
    # one layer fwd+bwd: 77.3 GFLOP -> 0.392 ms at 197 TFLOP/s; 302 MB ->
    # 0.369 ms at 819 GB/s: compute bound, just
    t, bound = work.roofline_seconds(f + f2, b + b2, PEAKS)
    assert bound == "compute" and abs(t - 77_309_411_328 / 197e12) < 1e-12


def test_one_paged_decode_row():
    # kv_len 450 at hidden 4096: q.K^T and p.V, 450 * 4096 MACs each
    f, b = work.paged_decode_row(450, 4096)
    assert f == 4 * 450 * 4096 == 7_372_800
    assert b == 2 * 450 * 4096 * 2 == 7_372_800    # K and V, bf16
    t, bound = work.roofline_seconds(f, b, PEAKS)
    assert bound == "hbm" and abs(t - 7_372_800 / 819e9) < 1e-15


def test_gpt2_small_train_step():
    cfg = common.load_json(common.HERE, "configs", "gpt2-small.json")
    # blocks: 12 * (4*768^2 + 2*768*3072) = 84,934,656 weights
    assert work.block_matmul_params(cfg) * 12 == 84_934_656
    fwd_blocks = 2 * 84_934_656 * 16384
    fwd_attn = 4 * 768 * 12 * (16 * 1024 * 1025 // 2)
    fwd_head = 2 * 768 * 50304 * 16 * 1023
    assert work.train_step_flops(cfg, 16, 1024) == \
        3.0 * (fwd_blocks + fwd_attn + fwd_head)


def test_unknown_device_is_an_error():
    peaks = common.load_json(common.HERE, "peaks.json")
    assert "TPU v4" not in peaks and "cpu" not in peaks
    assert peaks["TPU v5 lite"]["flops_per_s"]["bfloat16"] == 197e12
    assert peaks["TPU v5e"]["hbm_bytes_per_s"] == 819e9
