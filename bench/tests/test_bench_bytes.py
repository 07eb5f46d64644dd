"""Bytes per kernel call against shapes worked by hand, and the peak
table keyed by device kind."""

import pytest

import harness
import kernels_bytes as kb


def test_rank_gather_bytes_from_call_shapes():
    # DNA fused row: sigma 7 checkpoint words + 64 symbols * 4 bits / 32 =
    # 8 packed words = 15 words; + 3 prefetched scalars + 1 answer = 19
    # words = 76 bytes per query; 128 queries answered as (1, 1, 128)
    outs = [("s32", (1, 1, 128))]
    args = [("s32", (128,))] * 3 + [("s32", (1048577, 1, 15))]
    assert kb.kernel_call(outs, args) == ("rank_gather", 128 * 76)
    # unpacked proteins row: 64 int32 symbols -> 68 words per query
    args = [("s32", (1024,))] * 3 + [("s32", (262145, 1, 64))]
    assert kb.kernel_call([("s32", (8, 1, 128))], args) == (
        "rank_gather", 1024 * 272)


def test_radix_pass_bytes_from_call_shapes():
    # 2^24 + 8192 keys as (131136, 128) rows, blocks of 1024 keys: the
    # histogram pass reads the keys and the shift and writes 16392 rows
    # of 256 bins (the shapes of a 2^24-token proteins build)
    keys = ("s32", (131136, 128))
    hist = kb.kernel_call([("s32", (16392, 256))], [("s32", (1,)), keys])
    assert hist == ("radix_hist", 4 * (1 + 131136 * 128 + 16392 * 256))
    # the position pass also reads the bases and writes one int per key
    pos = kb.kernel_call([keys], [("s32", (1,)), keys,
                                  ("s32", (16392, 256))])
    assert pos == ("radix_pos", 4 * (1 + 2 * 131136 * 128 + 16392 * 256))


def test_other_calls_are_not_kernels_of_the_index():
    assert kb.kernel_call([("s32", (64,))], [("s32", (64,))]) is None
    assert kb.kernel_call([("s32", (8, 256)), ("s32", (8,))],
                          [("s32", (1,)), ("s32", (64, 128))]) is None


def test_peaks_by_device_kind():
    p = harness.peaks_for("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes"] == 16e9
    with pytest.raises(harness.HarnessError):
        harness.peaks_for("TPU v9 imaginary")
