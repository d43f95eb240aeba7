"""The full-attention layers' flash calls' share of their roofline
where query heads share K/V heads: as ``window_flash_roofline``, for
the calls WITHOUT a window (the Mosaic custom calls named after the
``fused_multihead_attention`` scope itself), with the FLOPs of the
causal half of the square at the full layers' own query-head count and
the bytes of 8 K/V heads read once (``benchmark/lib/laguna_flops.py``).
``causal_flash_roofline`` reckons one head count and as many K/V heads
as query heads, and is not declared for this family.  Nothing where the
trace names no such call or the configuration has no full layers."""

LAYER = 'kernels'
UNIT = '%'
MOVES = 'throughput'

FULL = r'fused_multihead_attention'
KIND = 'full_attention'


def read(trace, run):
    from benchmark.layer_metrics import window_flash_roofline as banded
    return banded.share(trace, run, FULL, KIND,
                        'gqa_causal_flash_roofline')
