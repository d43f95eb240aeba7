"""The grouped expert matmuls' share of their roofline: the least time
the chip could take for one step's gate, up and down matmuls over the
S*k routed rows, forward and backward (FLOPs and bytes from shapes,
``benchmark/lib/decoder_flops.py``), over the device time the trace
gives the ``moe_experts`` op and its gradient
(``benchmark/lib/scope_time.py``; that time holds the SiLU product and
the layout copies beside the matmuls, so the share is of the whole
op, not of the bare ``ragged_dot`` calls).  Nothing where the program
holds no such op."""

LAYER = 'op lowerings'
UNIT = '%'
MOVES = 'throughput'


def belongs(op_type):
    return op_type == 'moe_experts'


def read(trace, run):
    from benchmark.lib import decoder_flops, flops, peaks, scope_time
    traced_ms = scope_time.per_step_ms(trace, run, belongs)
    if not traced_ms:
        return None
    cell = run['cell']
    s = cell.family.sizes(cell.config, cell.traffic)
    rows = cell.traffic['batch_per_chip'] * cell.traffic['seq_len'] * \
        s['num_experts_per_tok']
    layer_flops, layer_bytes = decoder_flops.grouped_gated_mlp_train_cost(
        rows, s['hidden_size'], s['intermediate_size'], s['num_experts'])
    layers = s['num_hidden_layers']
    least_s, bound_by = flops.roofline_seconds(
        layers * layer_flops, layers * layer_bytes,
        *peaks.chip_peak(run['device_kind']))
    run.setdefault('notes', {})['moe_expert_roofline'] = \
        'the grouped expert matmuls are %s-bound at these shapes' \
        % bound_by
    return 100.0 * least_s / (traced_ms / 1e3)
