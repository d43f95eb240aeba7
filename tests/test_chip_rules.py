"""The rules that keep a run honest about its device (PR 21), on the
CPU: a place, a peak, a mesh or a cache that is not there is an error
or a fixed, stated location — never a quiet default."""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def test_xla_place_out_of_range_raises():
    n = len(jax.local_devices())
    assert fluid.XLAPlace(n - 1).jax_device() == jax.local_devices()[-1]
    with pytest.raises(ValueError, match=r'XLAPlace\(%d\).*%d local' % (n, n)):
        fluid.XLAPlace(n).jax_device()
    with pytest.raises(ValueError):
        fluid.XLAPlace(-1).jax_device()


@pytest.fixture
def jax_cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update('jax_compilation_cache_dir', was)


def test_place_jax_cache_unset_is_the_checkout(monkeypatch,
                                               jax_cache_config):
    monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR', raising=False)
    d = compile_cache.place_jax_cache()
    assert d == os.path.join(REPO, '.jax_cache')
    assert jax.config.jax_compilation_cache_dir == d
    assert compile_cache.place_jax_cache() == d     # no pid, no clock


def test_place_jax_cache_set_is_left_alone(monkeypatch, tmp_path,
                                           jax_cache_config):
    outside = str(tmp_path / 'from_outside')
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', outside)
    jax.config.update('jax_compilation_cache_dir', 'what-jax-read')
    assert compile_cache.place_jax_cache() == outside
    assert jax.config.jax_compilation_cache_dir == 'what-jax-read'
    # and the segment store's flag no longer moves it either
    compile_cache.reset_plane()
    fluid.set_flags({'FLAGS_compile_cache_dir': str(tmp_path / 'seg')})
    try:
        assert compile_cache.plane().cache_dir() == str(tmp_path / 'seg')
        assert os.path.isdir(str(tmp_path / 'seg' / 'segments'))
        assert jax.config.jax_compilation_cache_dir == 'what-jax-read'
    finally:
        fluid.set_flags({'FLAGS_compile_cache_dir': ''})
        compile_cache.reset_plane()


def test_dryrun_multichip_too_few_devices_raises_and_spawns_nothing(
        monkeypatch):
    import __graft_entry__ as graft

    def no_children(*a, **k):
        raise AssertionError('dryrun_multichip started a process')

    monkeypatch.setattr(subprocess, 'run', no_children)
    monkeypatch.setattr(subprocess, 'Popen', no_children)
    n = len(jax.devices()) + 1
    with pytest.raises(RuntimeError,
                       match='xla_force_host_platform_device_count=%d' % n):
        graft.dryrun_multichip(n)


def test_numpy_feeds_compile_once():
    """A host-fed program compiles at its first step and never again:
    feeds are staged uncommitted, like the state startup leaves, so
    step 2 binds the same argument kinds as step 1 (a committed feed
    used to make jit re-specialise the whole segment at step 2,
    unseen by executor/segment_cache_miss)."""
    compiles = []

    def on_duration(event, duration, **_):
        if event == '/jax/core/compile/backend_compile_duration':
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data('x', shape=[8], dtype='float32')
            loss = fluid.layers.mean(fluid.layers.fc(x, 4))
            fluid.optimizer.SGD(0.1).minimize(loss)
        xs = np.ones((4, 8), 'float32')
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.XLAPlace(0))
            exe.run(startup)
            per_step = []
            for _ in range(3):
                before = len(compiles)
                exe.run(main, feed={'x': xs}, fetch_list=[loss])
                per_step.append(len(compiles) - before)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert per_step[0] >= 1 and per_step[1:] == [0, 0], per_step


def test_chip_smoke_refuses_without_a_chip():
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, 'chip_smoke.py')],
        env=dict(os.environ, JAX_PLATFORMS='cpu'), capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert 'needs a TPU chip' in p.stderr
    assert '"ok"' not in p.stdout
