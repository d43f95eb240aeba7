"""The gated delta rule's share of its roofline, chip 0: the least
time the chip could take for one step's forward + backward recurrences
of the delta-rule layers, by a hand count from their SHAPES and a
nominal chunk of 64 tokens (``benchmark/lib/solar_flops.py``
``kda_train_cost``: the FLOPs of the chunked form; the bytes of q, k,
v, the float32 log decays, beta, o and the [128, 128] state at each
chunk's boundary, read and written once each way), over the time the
trace gives the ``kda_attention`` op and its gradient (``kda_ms``'s).
The count never looks at what implements the op: XLA fusions and a
``lax.scan`` today, a kernel tomorrow, on one yardstick.  Nothing where
no such instruction ran or the configuration has no such layer."""

LAYER = 'op lowerings'
UNIT = '%'
MOVES = 'throughput'


def read(trace, run):
    from benchmark.layer_metrics import kda_ms
    from benchmark.lib import flops, peaks, solar_flops
    ms = kda_ms.read(trace, run)
    if not ms:
        return None
    cell = run['cell']
    sizes = cell.family.sizes(cell.config, cell.traffic)
    linear = sizes.get('linear_attn_config')
    layers = sum(kind == solar_flops.KDA
                 for kind in sizes.get('layer_types', ()))
    if not linear or not layers:
        return None
    one = solar_flops.kda_train_cost(
        cell.traffic['batch_per_chip'], cell.traffic['seq_len'],
        linear['num_heads'], linear['head_dim'])
    least_s, bound_by = flops.roofline_seconds(
        layers * one[0], layers * one[1],
        *peaks.chip_peak(run['device_kind']))
    run.setdefault('notes', {})['kda_roofline'] = (
        'the %d delta-rule layers\' recurrences take %.3f ms a step and '
        'are %s-bound by the hand count (%.2f GFLOP, %.1f MB a step at '
        'a nominal chunk of %d)'
        % (layers, ms, bound_by, layers * one[0] / 1e9,
           layers * one[1] / 1e6, solar_flops.NOMINAL_CHUNK))
    return 100.0 * least_s / (ms / 1e3)
