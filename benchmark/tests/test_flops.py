"""The FLOP and byte arithmetic against counts made by hand."""

import json
import os

import pytest

from benchmark.lib import flops, peaks

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name):
    return json.load(open(os.path.join(BENCH, 'configs', name + '.json')))


def _traffic(name):
    return json.load(open(os.path.join(BENCH, 'workloads', name + '.json')))


# BERT-base forward per token, by hand: per layer 2*768*2304 (QKV) +
# 2*768*768 (output) + 2*2*768*3072 (feed-forward) = 14,155,776, plus
# 4*s*768 for scores and context; head 2*768*30522 = 46,881,792
@pytest.mark.parametrize('seq_len,forward', [
    (128, 12 * (14155776 + 393216) + 46881792),      # 221,469,696
    (2048, 12 * (14155776 + 6291456) + 46881792),    # 292,248,576
])
def test_bert_base_forward_flops_per_token(seq_len, forward):
    assert flops.transformer_encoder_forward_flops_per_token(
        12, 768, 3072, seq_len, 30522) == forward


@pytest.mark.parametrize('traffic,gflop', [('s128_b192', 0.664),
                                           ('s2048_b12', 0.877)])
def test_bert_family_trains_at_three_times_forward(traffic, gflop):
    from benchmark.families import bert
    per_token = bert.flops_per_item(_config('bert-base'), _traffic(traffic))
    assert per_token == pytest.approx(gflop * 1e9, rel=1e-3)
    assert per_token % 3 == 0


def test_resnet50_forward_flops_per_image():
    # multiply-adds by hand, stride on the 3x3 (He et al. Table 1
    # widths): stem 118,013,952; stages 667,942,912 + 1,027,604,480 +
    # 1,464,336,384 + 809,238,528 (first block of each stage with its
    # projection shortcut: 231,211,008 / 372,506,624 x3; every other
    # block 218,365,952); classifier 2,048,000
    macs = (118013952 + 667942912 + 1027604480 + 1464336384 + 809238528 +
            2048000)
    assert macs == 4089184256
    assert flops.resnet_forward_flops_per_image([3, 4, 6, 3], 224, 1000) \
        == 2 * macs
    from benchmark.families import resnet
    assert resnet.flops_per_item(_config('resnet50'),
                                 _traffic('img224_b384')) == 6 * macs


def test_flash_attention_cost_and_roofline_side():
    # b12, 12 heads of 64, s2048: one s x s x d matmul over the batch is
    # 2*12*12*2048^2*64 = 77,309,411,328 FLOPs; 2 forward + 5 backward;
    # one [b, s, h, d] bf16 tensor is 37,748,736 bytes; 4 + 8 of them
    cost = flops.flash_attention_train_cost(12, 12, 2048, 64)
    assert cost == (7 * 77309411328, 12 * 37748736)
    seconds, side = flops.roofline_seconds(*cost,
                                           *peaks.chip_peak('TPU v5e'))
    assert side == 'compute'
    assert seconds == pytest.approx(7 * 77309411328 / 197e12)
    # a bandwidth-bound call: one pass over 2 GiB with a FLOP per byte
    assert flops.roofline_seconds(2 ** 31, 2 ** 31, 197e12, 819e9) == \
        (2 ** 31 / 819e9, 'memory')


def test_a_device_without_published_peaks_is_an_error():
    assert peaks.chip_peak('TPU v5 lite') == (197e12, 819e9)
    with pytest.raises(KeyError, match='no published peaks'):
        peaks.chip_peak('cpu')
