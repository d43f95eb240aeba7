"""``benchmark/lib/solar_flops.py`` against counts made by hand: the
gated delta rule in chunked form, a function of the shapes and a
nominal chunk.  (``test_flops.py``'s cases for this family, in a file
of their own: a PR edits no file the benchmark already has.)"""

import pytest

from benchmark.lib import flops, peaks, solar_flops


# one chunk of one head, by hand: pairs = C (C + 1) / 2; scores A and B
# 2 x 2 x pairs x d; the triangular system on [K | V] 2 x (C^2 / 2) x
# 2d; three products with the [d, d] state 3 x 2 x C x d^2; B U over
# the causal half 2 x pairs x d
@pytest.mark.parametrize('d,chunk,want', [
    (128, 64, 1064960 + 1048576 + 6291456 + 532480),    # 8,937,472
    (64, 64, 532480 + 524288 + 1572864 + 266240),       # 2,895,872
    (128, 32, 270336 + 262144 + 3145728 + 135168),      # 3,813,376
])
def test_one_chunk_of_the_delta_rule_by_hand(d, chunk, want):
    assert solar_flops.kda_chunk_forward_flops(d, chunk) == want


@pytest.mark.parametrize('batch,seq_len,heads', [(1, 4096, 8),
                                                 (2, 4096, 8),
                                                 (1, 32768, 8),
                                                 (1, 4096, 64)])
def test_the_recurrence_s_cost_grows_with_tokens_and_heads_alone(
        batch, seq_len, heads):
    """FLOPs and bytes are linear in batch x tokens x heads: the state
    is carried, nothing is quadratic in the sequence."""
    one = solar_flops.kda_train_cost(1, 4096, 8, 128)
    got = solar_flops.kda_train_cost(batch, seq_len, heads, 128)
    scale = batch * seq_len * heads / (4096 * 8)
    assert got == (one[0] * scale, one[1] * scale)


def test_the_recurrence_is_bytes_bound_on_a_v5e_by_the_hand_count():
    """13.7 GFLOP against 210 MB a layer at the cell's shape: 0.07 ms
    of matmuls under 0.26 ms of HBM traffic."""
    cost = solar_flops.kda_train_cost(1, 4096, 8, 128)
    assert round(cost[0] / 1e9, 1) == 13.7
    assert round(cost[1] / 1e6) == 210
    least, side = flops.roofline_seconds(
        *cost, *peaks.chip_peak('TPU v5 lite'))
    assert side == 'memory' and round(least * 1e3, 2) == 0.26


def test_a_token_that_is_no_whole_chunk_counts_a_whole_one():
    assert solar_flops.kda_train_cost(1, 65, 1, 128)[0] == \
        solar_flops.kda_train_cost(1, 128, 1, 128)[0]
