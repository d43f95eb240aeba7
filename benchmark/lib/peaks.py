"""Published peaks of one chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(197 TFLOP/s bf16, 819 GB/s HBM bandwidth, 16 GB HBM per chip); the
v4 / v5p / v6e rows are from the same documentation's pages for those
chips.  Copied from ``bench.py`` ``CHIP_PEAKS`` (PR 21), which stays
where it is until ROADMAP D1 removes ``bench.py``.

A kind that has no row is an error: utilization against an assumed
peak would be a made-up number.  A later PR adds a row, with its
source, and edits nothing else.
"""

# device_kind -> (bf16 TFLOP/s, HBM GB/s)
CHIP_PEAKS = {
    'TPU v5 lite': (197.0, 819.0),
    'TPU v5e': (197.0, 819.0),
    'TPU v4': (275.0, 1228.0),
    'TPU v5p': (459.0, 2765.0),
    'TPU v6 lite': (918.0, 1640.0),
    'TPU v6e': (918.0, 1640.0),
}


def chip_peak(device_kind):
    """(peak bf16 FLOP/s, peak HBM bytes/s) of one chip of this kind."""
    if device_kind not in CHIP_PEAKS:
        raise KeyError(
            'no published peaks for device_kind %r: add a row to '
            'benchmark/lib/peaks.py with its source' % (device_kind,))
    tflops, gbps = CHIP_PEAKS[device_kind]
    return tflops * 1e12, gbps * 1e9
