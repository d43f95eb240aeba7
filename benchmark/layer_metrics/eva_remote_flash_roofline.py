"""The remote stream's flash calls' share of their roofline: as
``eva_local_flash_roofline``, for the Mosaic custom calls named after
the ``remote`` scope, with the FLOPs of the VISIBLE (query, summary)
pairs only and the summaries k~, v~ read once a call
(``benchmark/lib/evabyte_flops.py`` ``remote_train_cost``).  At two
windows a query tile sees 128 keys or none: the calls are bytes-bound
(q, o, do and dq at full length against a sixteenth of that in keys)
and the share reads low; it is the number a later issue starts from.
Nothing where the trace names no such call."""

LAYER = 'kernels'
UNIT = '%'
MOVES = 'throughput'

REMOTE = r'^remote'


def read(trace, run):
    from benchmark.layer_metrics import eva_local_flash_roofline as local
    from benchmark.lib import evabyte_flops
    return local.share(trace, run, REMOTE,
                       evabyte_flops.remote_train_cost,
                       'eva_remote_flash_roofline')
