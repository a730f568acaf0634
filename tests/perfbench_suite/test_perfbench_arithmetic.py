"""Percentiles, gaps and rates on hand-made event lists; FLOP and byte
counts against hand-worked totals; the table of peaks."""

import pytest

from perfbench.harness import counts, peaks, stats


def test_percentile_is_linear_between_order_statistics():
    v = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.percentile(v, 0) == 10.0
    assert stats.percentile(v, 50) == 30.0
    assert stats.percentile(v, 90) == pytest.approx(46.0)
    assert stats.percentile(v, 100) == 50.0
    assert stats.percentile([7.0], 90) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_token_gaps_pool_every_request_and_skip_first_tokens():
    times = [[1.0, 1.5, 2.5], [2.0, 2.25], [9.0]]
    # window [1.2, 3]: a's gaps end at 1.5 and 2.5, b's at 2.25
    assert sorted(stats.token_gaps(times, 1.2, 3.0)) == [0.25, 0.5, 1.0]
    # a gap belongs to the window its later token arrives in
    assert stats.token_gaps(times, 2.4, 3.0) == [1.0]
    assert stats.token_gaps([[9.0]], 0.0, 10.0) == []


def test_rate_between_first_tokens_credits_what_completed_in_the_span():
    firsts = [(1.0, 100), (3.0, 200), (5.0, 300), (9.0, 400)]
    tokens = [1.0, 2.0, 3.0, 3.5, 4.0, 5.0, 5.5, 9.0]
    # window [0, 6]: span 1.0 -> 5.0; prompts 200 + 300; generated
    # tokens in (1, 5]: 2.0 3.0 3.5 4.0 5.0
    rate, ta, tb = stats.rate_between_first_tokens(firsts, tokens, 0.0, 6.0)
    assert (ta, tb) == (1.0, 5.0)
    assert rate == pytest.approx((500 + 5) / 4.0)


def test_one_completion_in_the_window_is_no_rate():
    assert stats.rate_between_first_tokens(
        [(1.0, 100), (9.0, 5)], [1.0, 2.0], 0.0, 6.0) is None
    assert stats.rate_between_first_tokens([], [], 0.0, 6.0) is None
    assert stats.rate_between_completions([(2.0, 500)], 0.0, 6.0) is None


def test_rate_between_completions():
    done = [(1.0, 700), (2.0, 800), (4.0, 900), (7.0, 50)]
    assert stats.rate_between_completions(done, 0.0, 5.0) == \
        pytest.approx((800 + 900) / 3.0)


def test_histogram_mean_is_a_difference_over_the_window():
    before = {"sum": 1.0, "count": 10, "buckets": {}}
    after = {"sum": 1.6, "count": 14, "buckets": {}}
    assert stats.histogram_mean_delta(before, after) == pytest.approx(0.15)
    assert stats.histogram_mean_delta(None, after) == pytest.approx(1.6 / 14)
    assert stats.histogram_mean_delta(after, after) is None


GPT2_XL = {"n_embd": 1600, "n_layer": 48, "vocab_size": 50257,
           "n_positions": 1024, "n_inner": 6400, "n_head": 25}


def test_gpt2_xl_counts_against_hand_worked_totals():
    c = counts.gpt2_param_counts(GPT2_XL)
    # a layer: 4 x 1600^2 attention + 2 x 1600 x 6400 MLP = 30,720,000
    # 48 layers 1,474,560,000; tied head 50257 x 1600 = 80,411,200
    assert c["matmul"] == 1_474_560_000 + 80_411_200
    # norms: 48 x 2 x (scale + bias) x 1600 + final 2 x 1600
    assert c["norms"] == 48 * 4 * 1600 + 3200
    assert c["total"] == c["matmul"] + 1024 * 1600 + c["norms"] \
        == 1_556_920_000
    fwd = counts.gpt2_forward_flops_per_token(GPT2_XL, 1024)
    # 2 x 1,554,971,200 + 48 layers x 4 x 512.5 x 1600
    assert fwd == 2 * 1_554_971_200 + 48 * 4 * 512.5 * 1600
    assert counts.gpt2_train_flops_per_token(GPT2_XL, 1024) == 3 * fwd
    # decode: weights and norms in bf16, K and V of 5000 live tokens at
    # 2 x 48 x 1600 x 2 bytes a token
    assert counts.gpt2_decode_bytes(GPT2_XL, 5000) == \
        (1_554_971_200 + 310_400) * 2 + 5000 * 307_200


def test_resnet50_counts_against_hand_worked_totals():
    layers = dict((n, (oh, ow, k, ci, co)) for n, oh, ow, k, ci, co in
                  counts.resnet50_layers())
    assert len(layers) == 1 + 16 * 3 + 4 + 1           # 54 with the fc
    assert layers["conv_init"] == (112, 112, 7, 3, 64)
    assert layers["s0b0.proj"] == (56, 56, 1, 64, 256)
    # v1.5: the stride sits in the 3x3, so conv1 of a down-sampling block
    # still runs at the larger size
    assert layers["s1b0.conv1"] == (56, 56, 1, 256, 128)
    assert layers["s1b0.conv2"] == (28, 28, 3, 128, 128)
    assert layers["s3b2.conv3"] == (7, 7, 1, 512, 2048)
    # multiply-accumulates by stage, by hand:
    stem = 112 * 112 * 49 * 3 * 64
    s0 = 56 * 56 * (64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256) \
        + 2 * 56 * 56 * (256 * 64 + 9 * 64 * 64 + 64 * 256)
    s1 = 56 * 56 * 256 * 128 + 28 * 28 * (9 * 128 * 128 + 128 * 512
                                          + 256 * 512) \
        + 3 * 28 * 28 * (512 * 128 + 9 * 128 * 128 + 128 * 512)
    s2 = 28 * 28 * 512 * 256 + 14 * 14 * (9 * 256 * 256 + 256 * 1024
                                          + 512 * 1024) \
        + 5 * 14 * 14 * (1024 * 256 + 9 * 256 * 256 + 256 * 1024)
    s3 = 14 * 14 * 1024 * 512 + 7 * 7 * (9 * 512 * 512 + 512 * 2048
                                         + 1024 * 2048) \
        + 2 * 7 * 7 * (2048 * 512 + 9 * 512 * 512 + 512 * 2048)
    macs = stem + s0 + s1 + s2 + s3 + 2048 * 1000
    assert counts.resnet50_forward_flops_per_image() == 2 * macs
    assert 4.0e9 < macs < 4.2e9                  # the well-known 4.1 G
    assert counts.resnet50_train_flops_per_image() == 6 * macs


def test_peaks_of_the_v5e_and_an_unknown_device():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks on record"):
        peaks.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
