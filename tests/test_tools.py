"""Coverage audits stay green (the CI-gate analog of reference
tools/check_op_desc.py + diff_api.py + check_api_approvals.sh)."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(tool, *args):
    env = dict(os.environ)
    env.setdefault('JAX_PLATFORMS', 'cpu')
    p = subprocess.run([sys.executable, os.path.join(REPO, 'tools',
                                                     tool)] + list(args),
                       capture_output=True, text=True, env=env,
                       cwd=REPO, timeout=300)
    return p


def test_op_coverage_complete():
    p = _run('check_op_coverage.py')
    assert p.returncode == 0, p.stdout + p.stderr
    assert 'coverage: complete' in p.stdout


def test_api_coverage_complete():
    import pytest
    ref = os.environ.get('PADDLE_REFERENCE', '/root/reference')
    if not os.path.isdir(os.path.join(ref, 'python/paddle/fluid')):
        pytest.skip('no reference tree at %s to audit against (set '
                    'PADDLE_REFERENCE)' % ref)
    p = _run('check_api_coverage.py')
    assert p.returncode == 0, p.stdout + p.stderr
    assert '(100.0%)' in p.stdout


def test_every_op_is_test_referenced():
    p = _run('check_test_coverage.py')
    assert p.returncode == 0, p.stdout + p.stderr
    assert 'every registered op is referenced' in p.stdout


def test_timeline_export(tmp_path):
    """fluid.profiler capture -> tools/timeline.py -> chrome-trace JSON
    (the reference's tools/timeline.py flow)."""
    import gzip
    import json

    prof = tmp_path / 'profile'
    # synthesize the jax-profiler layout the tool consumes
    d = prof / 'plugins' / 'profile' / 'run1'
    d.mkdir(parents=True)
    trace = {'traceEvents': [
        {'ph': 'M', 'pid': 1, 'name': 'process_name',
         'args': {'name': '/device:TPU:0'}},
        {'ph': 'X', 'pid': 1, 'tid': 0, 'ts': 0, 'dur': 5,
         'name': 'fusion.1'}]}
    with gzip.open(str(d / 'vm.trace.json.gz'), 'wt') as f:
        json.dump(trace, f)
    out = tmp_path / 'timeline.json'
    p = _run('timeline.py', '--profile_path', str(prof),
             '--timeline_path', str(out))
    assert p.returncode == 0, p.stdout + p.stderr
    got = json.load(open(str(out)))
    assert got['traceEvents'][1]['name'] == 'fusion.1'
