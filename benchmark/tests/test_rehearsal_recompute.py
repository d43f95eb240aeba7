"""CPU rehearsal of the two readers of the program's pass table
(``recompute_ms``, ``hbm_recomputed_gb``) at the tiny Nemotron-H preset,
whose two Mamba-2 layers are recompute groups.  Nothing printed here is
a measurement."""

import json
import os
import re

from benchmark.tests.test_rehearsal import CONTRACT_KEYS, _last_line
from benchmark.tests.test_rehearsal_nemotron_h import ROOT, harness  # noqa: F401,E501

NEW = ('recompute_ms', 'hbm_recomputed_gb')
_NUMBER = r'(-?\d+\.\d+)'


def test_traced_run_splits_the_step_by_pass(harness, capsys):  # noqa: F811
    """Both metrics print; the note's four columns sum to the scope
    table's non-collective total; the second forward takes time, and
    less of it than the first forward (one layer of the preset's is no
    group)."""
    run, root = harness
    listed = {m['name']: m for m in json.load(open(os.path.join(
        ROOT, 'BENCHMARK.json')))['per_layer']}
    manifest_path = os.path.join(root, 'BENCHMARK.json')
    manifest = json.load(open(manifest_path))
    for name in NEW:        # as the repo's manifest lists them
        manifest['per_layer'].append(
            dict(listed[name], workloads=['tiny_nemotron']))
    json.dump(manifest, open(manifest_path, 'w'))
    assert run.main(['--workload', 'tiny_nemotron', '--seed', '0',
                     '--seconds', '1', '--trace', '1']) == 0
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert set(line) == CONTRACT_KEYS | {'breakdown'}
    assert line['correct'] is True
    got = line['metrics']
    assert set(NEW) <= set(got)
    assert got['recompute_ms']['value'] > 0
    assert got['recompute_ms']['unit'] == 'ms/step'
    assert got['hbm_recomputed_gb']['value'] >= 0
    note = re.search(
        r'recompute_ms: .*forward %s \+ recomputed %s \+ backward %s \+ '
        r'no pass \(optimizer, unscoped\) %s = %s non-collective'
        % ((_NUMBER,) * 5), out)
    forward, recomputed, backward, no_pass, total = map(float,
                                                        note.groups())
    assert abs(forward + recomputed + backward + no_pass - total) < 0.005
    assert recomputed == round(got['recompute_ms']['value'], 3)
    assert 0 < recomputed < forward
    table_total = float(re.search(
        r'unscoped_ms: .*share of %s ms non-collective' % _NUMBER,
        out).group(1))
    assert abs(total - table_total) < 0.0015
    # a row a fluid op type, the scan's among them, sorted by the
    # recomputed column
    rows = re.findall(r'^  (\w+) +%s +%s +%s$' % ((_NUMBER,) * 3), out,
                      re.M)
    assert 'ssd_scan' in [r[0] for r in rows]
    seconds = [float(r[2]) for r in rows]
    assert seconds == sorted(seconds, reverse=True) and seconds[0] > 0
    # the residual class holds what the groups kept
    assert re.search(r'hbm_residual_gb: .*by class: .*residual', out)
