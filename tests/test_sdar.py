"""SDAR's block-diffusion training through fluid against its plain
reference (``paddle_tpu/models/reference/sdar.py``): the block-relation
mask of the attention op against a dense masked softmax, through the
dense arm and through the Pallas interpreter, with the tiles its loops
walk; ``layers.block_diffusion_attention`` against ONE dense [2L, 2L]
softmax; the zoo program's loss and every parameter's gradient through
its recompute groups; that no corrupted logit leaks a token it must
not see; the eight expert shares adding up to the uncut layer; the
mutations the tolerance has to refuse; the corruption as data.  CPU,
tiny sizes; the published widths are checked on the chip
(``chip_smoke.py --phase sdar``, PERF.md)."""

import copy
import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers, monitor
from paddle_tpu.models import sdar
from paddle_tpu.models.reference import sdar as reference
from paddle_tpu.ops.pallas import flash_attention as fa

SEQ = 32

# the tiny model, holding experts 2 .. 5 of its 8
HELD = copy.copy(sdar.TINY)
HELD.experts_held = (2, 4)


def _scalar(x):
    return float(np.asarray(x).ravel()[0])


# --- the mask ---------------------------------------------------------


def _relation_mask(t, tk, block, kind):
    """[t, tk] booleans, written out from the definition."""
    r, c = np.arange(t)[:, None], np.arange(tk)[None, :]
    if kind == 'causal':
        return c // block <= r // block
    return c // block < r // block


def _dense_masked(q, k, v, mask):
    """(o, lse with 0 where a row sees no key): one dense softmax."""
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    s = jnp.einsum('bthd,bshd->bhts', q, k, precision='highest') * \
        q.shape[-1] ** -0.5
    s = jnp.where(mask[None, None], s, -jnp.inf)
    some = mask.any(1)[None, None, :]
    lse = jnp.where(some, jax.nn.logsumexp(
        jnp.where(some[..., None], s, 0.0), -1), 0.0)
    p = jnp.where(some[..., None], jnp.exp(s - lse[..., None]), 0.0)
    return jnp.einsum('bhts,bshd->bthd', p, v, precision='highest'), lse


def _compare_with_dense(tk, block, kind, min_seq):
    rng = np.random.RandomState(hash((tk, block, kind)) % 2 ** 31)
    t = tk
    q = jnp.asarray(rng.randn(2, t, 4, 16), jnp.float32)
    k, v = (jnp.asarray(rng.randn(2, tk, 2, 16), jnp.float32)
            for _ in range(2))
    w = jnp.asarray(rng.randn(2, t, 4, 16), jnp.float32)
    mask = _relation_mask(t, tk, block, kind)

    def ours(q, k, v):
        o, lse = fa.flash_attention(q, k, v, block_mask=(block, kind),
                                    with_lse=True, min_seq=min_seq)
        return o, lse

    def value(f, q, k, v):
        o, lse = f(q, k, v)
        lse = jnp.where(jnp.isfinite(lse), lse, 0.0)
        return jnp.sum(o * w) + 0.1 * jnp.sum(jnp.square(lse))

    o, lse = ours(q, k, v)
    want_o, want_lse = _dense_masked(q, k, v, mask)
    blind = ~mask.any(1)
    assert blind.sum() == (0 if kind == 'causal' else block)
    # a row that sees no key: out 0 and lse -inf, as under the coarse mask
    assert np.all(np.asarray(o)[:, blind] == 0)
    assert np.all(np.isneginf(np.asarray(lse)[:, :, blind]))
    assert np.all(np.isfinite(np.asarray(lse)[:, :, ~blind]))
    assert np.abs(np.asarray(o) - want_o).max() <= 2e-5
    assert np.abs(np.where(blind, 0, np.asarray(lse)) -
                  want_lse).max() <= 2e-5
    got = jax.grad(functools.partial(value, ours), (0, 1, 2))(q, k, v)
    want = jax.grad(functools.partial(
        value, lambda *a: _dense_masked(*a, mask)), (0, 1, 2))(q, k, v)
    for g, wg, name in zip(got, want, 'qkv'):
        assert np.abs(np.asarray(g) - wg).max() <= \
            1e-4 * np.abs(np.asarray(wg)).max(), name


@pytest.mark.parametrize('block', [4, 32])
@pytest.mark.parametrize('kind', fa.BLOCK_RELATIONS)
def test_block_mask_dense_arm_matches_a_masked_softmax(kind, block):
    """Values, log-sum-exps and all three gradients of the dense arm,
    grouped heads (4 query heads over 2), with the rows that see no
    key."""
    _compare_with_dense(96, block, kind, min_seq=10 ** 9)


@pytest.mark.parametrize('backward', ['one_pass', 'two_pass'])
@pytest.mark.parametrize('block', [4, 32])
@pytest.mark.parametrize('kind', fa.BLOCK_RELATIONS)
def test_block_mask_kernels_match_a_masked_softmax(
        kind, block, backward, pallas_interpret, monkeypatch):
    """The same through the four kernel bodies under the interpreter,
    at tiles small enough that the loops skip tiles."""
    monkeypatch.setattr(fa, 'DEFAULT_BLOCK_Q', 64)
    monkeypatch.setattr(fa, 'DEFAULT_BLOCK_K', 32)
    monkeypatch.setattr(fa, 'FUSED_BLOCK_Q', 64)
    monkeypatch.setattr(fa, 'FUSED_BLOCK_K', 32)
    monkeypatch.setattr(fa, 'FUSED_BWD', backward == 'one_pass')
    before = monitor.counter_value(
        'pallas/flash_attention/backward_' + backward) or 0
    masked = monitor.counter_value('pallas/flash_attention/mask_block') or 0
    _compare_with_dense(96, block, kind, min_seq=0)
    assert monitor.counter_value(
        'pallas/flash_attention/backward_' + backward) > before
    assert monitor.counter_value(
        'pallas/flash_attention/mask_block') > masked


@pytest.mark.parametrize('blocks', [(64, 32), (32, 64), (128, 96)])
@pytest.mark.parametrize('block', [4, 32, 48])
@pytest.mark.parametrize('kind', fa.BLOCK_RELATIONS)
def test_the_loops_walk_the_tiles_that_hold_a_visible_pair(kind, block,
                                                            blocks):
    """_key_blocks (forward, dq) and _query_blocks (dkv, one pass)
    visit exactly the [block_q, block_k] tiles in which some
    query sees some key, and ``sdar/tiles_visited`` counts those."""
    bq, bk = blocks
    t = tk = 192 if bq < 128 else 384
    relation = fa.block_relation(block, kind)
    mask = _relation_mask(t, tk, block, kind)
    want = mask.reshape(t // bq, bq, tk // bk, bk).any((1, 3))
    rows = np.zeros_like(want)
    for i in range(t // bq):
        lo, hi = fa._key_blocks(i * bq, bq, bk, tk // bk, False, 0, None,
                                relation)
        rows[i, int(lo):int(hi)] = True
    assert np.array_equal(rows, want)
    cols = np.zeros_like(want)
    for j in range(tk // bk):
        lo, hi = fa._query_blocks(j * bk, bk, bq, t // bq, False, 0, None,
                                  relation)
        cols[int(lo):int(hi), j] = True
    assert np.array_equal(cols, want)
    from paddle_tpu.ops import registry
    registry.begin_trace()
    fa._count_tiles(3, t, tk, relation, blocks, passes=2)
    assert monitor.gauge_value('sdar/tiles_visited') == 6 * want.sum()


def test_block_mask_refuses_what_it_cannot_mean():
    q = jnp.zeros((1, 64, 2, 8))
    for bad in [dict(block_mask=(4, 'causal'), causal=True),
                dict(block_mask=(4, 'upwards')),
                dict(block_mask=(0, 'strict')),
                dict(block_mask=(4, 'stacked'))]:
        with pytest.raises(ValueError):
            fa.flash_attention(q, q, q, **bad)
    with pytest.raises(ValueError):
        fa.flash_attention(q, q[:, :32], q[:, :32],
                           block_mask=(4, 'strict'))


# --- a handful of keys -------------------------------------------------


def _small(n, t, h, g, d, dv, dtype, seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(n, t, h, d), dtype),
            jnp.asarray(rng.randn(n, t, g, d), dtype),
            jnp.asarray(rng.randn(n, t, g, dv), dtype),
            jnp.asarray(rng.randn(n, t, h, dv), jnp.float32))


def _taken(name):
    return monitor.counter_value('pallas/flash_attention/' + name) or 0


@pytest.mark.parametrize('dtype', [jnp.float32, jnp.bfloat16],
                         ids=['f32', 'bf16'])
@pytest.mark.parametrize('t,h,g,d,dv', [
    (1, 8, 1, 128, 128), (4, 32, 4, 128, 128), (16, 16, 2, 128, 128),
    (4, 16, 2, 128, 256), (4, 16, 2, 256, 128), (8, 3, 3, 128, 128)])
def test_small_keys_kernels_match_the_dense_chain(t, h, g, d, dv, dtype,
                                                  pallas_interpret):
    """The own-block call's arm (ops/pallas/small_keys.py, the bodies
    under the interpreter) against the dense chain at the cell's
    grouping (H query heads over H / 8 K/V heads): values, log-sum-exps
    and the gradients of q, k, v through BOTH outputs; 1, 4 and 16 keys,
    values wider and narrower than the keys once each, and three heads
    ungrouped once."""
    n = 2 * 128 // t
    q, k, v, w = _small(n, t, h, g, d, dv, dtype, seed=t)

    def value(f, q, k, v):
        o, lse = f(q, k, v)
        return jnp.sum(o.astype(jnp.float32) * w) + \
            0.1 * jnp.sum(jnp.square(lse))

    def ours(q, k, v):
        return fa.flash_attention(q, k, v, with_lse=True)

    def dense(q, k, v):
        return fa._dense_path(q, k, v, False, None, with_lse=True)

    before = _taken('dispatch_small_keys')
    o, lse = ours(q, k, v)
    assert _taken('dispatch_small_keys') == before + 1
    assert fa._common._LAST['flash_attention'] == {
        'path': 'fused', 'reason': 'forced_interpret', 'interpret': True,
        'arm': 'small_keys'}
    want_o, want_lse = dense(q, k, v)
    assert o.dtype == dtype and lse.dtype == jnp.float32
    assert lse.shape == (n, h, t)
    # bfloat16: one last place of an output of a few units
    tol = 2e-5 if dtype == jnp.float32 else 3.2e-2
    assert np.abs(np.asarray(o, np.float32) -
                  np.asarray(want_o, np.float32)).max() <= tol
    assert np.abs(np.asarray(lse) - want_lse).max() <= 2e-5
    got = jax.grad(functools.partial(value, ours), (0, 1, 2))(q, k, v)
    want = jax.grad(functools.partial(value, dense), (0, 1, 2))(q, k, v)
    for a, b, name in zip(got, want, 'qkv'):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() <= \
            (1e-4 if dtype == jnp.float32 else 2e-2) * np.abs(b).max(), name


def test_small_keys_without_the_log_sum_exp(pallas_interpret):
    """``with_lse`` off: the same output, and the gradient with no
    cotangent on the statistics."""
    q, k, v, w = _small(64, 4, 8, 1, 128, 128, jnp.float32)
    o = fa.flash_attention(q, k, v)
    want = fa._dense_path(q, k, v, False, None)
    assert np.abs(np.asarray(o) - want).max() <= 2e-5
    got = jax.grad(lambda *a: jnp.sum(fa.flash_attention(*a) * w),
                   (0, 1, 2))(q, k, v)
    dense = jax.grad(lambda *a: jnp.sum(
        fa._dense_path(*a, False, None) * w), (0, 1, 2))(q, k, v)
    for a, b, name in zip(got, dense, 'qkv'):
        assert np.abs(np.asarray(a) - b).max() <= \
            1e-4 * np.abs(np.asarray(b)).max(), name


@pytest.mark.parametrize('case', [
    'keys_17', 'causal', 'key_bias', 'dropout', 'block_mask'])
def test_what_the_small_keys_arm_leaves_alone(case, pallas_interpret):
    """The shape and the masks decide: 16 unmasked keys take the arm;
    one key more, a causal mask, a key bias, a dropout rate and a block
    mask each go the way they went."""
    t = 17 if case == 'keys_17' else 16
    q, k, v, _ = _small(8, t, 8, 1, 128, 128, jnp.float32)
    more = {'keys_17': {}, 'causal': dict(causal=True),
            'key_bias': dict(key_bias=jnp.zeros((8, t))),
            'dropout': dict(dropout_rate=0.1,
                            dropout_seed=jnp.uint32(7)),
            'block_mask': dict(block_mask=(4, 'causal'))}[case]
    before = _taken('dispatch_small_keys')
    fa.flash_attention(q, k, v, **more)
    assert _taken('dispatch_small_keys') == before
    assert 'arm' not in fa._common._LAST['flash_attention']
    assert fa._common._LAST['flash_attention']['reason'] == 'below_floor'
    fa.flash_attention(q[:, :16], k[:, :16], v[:, :16])
    assert _taken('dispatch_small_keys') == before + 1


@pytest.mark.parametrize('case', [
    'off_tpu', 'narrow_heads', 'ragged_batch', 'three_keys', 'float16',
    'auto_partitioned'])
def test_small_keys_gates_leave_the_call_where_it_went(case, request):
    """Off a TPU, heads off the 128 lanes, a batch that is no whole
    grid step, a length that does not divide the lanes, float16 and
    the GSPMD runner's trace: the dispatch as it stood, which answers
    the dense chain below ``FLASH_MIN_SEQ``."""
    if case != 'off_tpu':
        request.getfixturevalue('pallas_interpret')
    n, t, h, g, d, dtype = {
        'narrow_heads': (32, 4, 8, 1, 64, jnp.float32),
        'ragged_batch': (33, 4, 8, 1, 128, jnp.float32),
        'three_keys': (128, 3, 8, 1, 128, jnp.float32),
        'float16': (32, 4, 8, 1, 128, jnp.float16),
    }.get(case, (32, 4, 8, 1, 128, jnp.float32))
    q, k, v, _ = _small(n, t, h, g, d, d, dtype)
    before = (_taken('dispatch_small_keys'),
              _taken('fallback/below_floor'))
    o, lse = fa.flash_attention(
        q, k, v, with_lse=True,
        auto_partitioned=case == 'auto_partitioned')
    assert (_taken('dispatch_small_keys'),
            _taken('fallback/below_floor')) == (before[0], before[1] + 1)
    assert fa._common._LAST['flash_attention'] == {
        'path': 'dense', 'reason': 'below_floor', 'interpret': False}
    want_o, want_lse = fa._dense_path(q, k, v, False, None, with_lse=True)
    assert np.array_equal(np.asarray(o), np.asarray(want_o))
    assert np.array_equal(np.asarray(lse), np.asarray(want_lse))


def test_block_diffusion_attention_runs_its_own_blocks_in_the_arm(
        pallas_interpret, monkeypatch):
    """The layer at the cell's grouping in small (8 query heads a K/V
    head, heads of 128): the own-block call takes the small-keys arm
    (one a layer's lowering, beside the two block-mask calls' kernels)
    and the layer is still ONE softmax under the reference's mask,
    values and all three gradients."""
    monkeypatch.setattr(fa, 'FLASH_MIN_SEQ', 128)
    length, block, heads, kv_heads, d = 128, 4, 8, 1, 128
    rng = np.random.RandomState(3)
    feed = {n: rng.randn(1, r, h, d).astype('float32')
            for n, r, h in (('q', 2 * length, heads),
                            ('w', 2 * length, heads),
                            ('k', 2 * length, kv_heads),
                            ('v', 2 * length, kv_heads))}
    with fluid.scope_guard(fluid.Scope()):
        main, startup, out, grads = _layer_program(
            length, block, heads, kv_heads, d, 2 * length)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        before = _taken('dispatch_small_keys'), _taken('dispatch_fused')
        got = exe.run(main, feed=feed, fetch_list=[out] + grads)
    # the arm's call beside the two block-mask calls' kernels
    assert _taken('dispatch_small_keys') - before[0] == 1
    assert _taken('dispatch_fused') - before[1] == 3
    mask = reference.visible(length, block)

    def dense(q, k, v):
        return jnp.sum(_dense_masked(q, k, v, mask)[0] * feed['w'])

    want = _dense_masked(feed['q'], feed['k'], feed['v'], mask)[0]
    assert np.abs(got[0] - want).max() <= 2e-5
    want_grads = jax.grad(dense, (0, 1, 2))(feed['q'], feed['k'],
                                            feed['v'])
    for g, wg, name in zip(got[1:], want_grads, 'qkv'):
        assert np.abs(g - wg).max() <= 1e-4 * np.abs(wg).max(), name


# --- the layer --------------------------------------------------------


def _layer_program(length, block, heads, kv_heads, d, q_rows):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        q = layers.data('q', shape=[q_rows, heads, d], dtype='float32')
        k = layers.data('k', shape=[2 * length, kv_heads, d],
                        dtype='float32')
        v = layers.data('v', shape=[2 * length, kv_heads, d],
                        dtype='float32')
        w = layers.data('w', shape=[q_rows, heads, d], dtype='float32')
        for x in (q, k, v):
            x.stop_gradient = False
        out = layers.block_diffusion_attention(q, k, v, block)
        loss = layers.reduce_sum(layers.elementwise_mul(out, w))
        grads = fluid.backward.gradients(loss, [q, k, v])
    return main, startup, out, grads


@pytest.mark.parametrize('arm', ['dense', 'kernels'])
@pytest.mark.parametrize('rows', ['both_copies', 'corrupted_only'])
@pytest.mark.parametrize('block', [4, 16])
def test_block_diffusion_attention_is_one_dense_softmax(block, rows, arm,
                                                        request):
    """The three calls and the merge against ONE softmax under the
    [2L, 2L] mask of the reference, values and all three gradients;
    with the corrupted rows alone as queries (a last layer) it is the
    top half of the same.  Through the kernels (interpreter) the two
    block-mask calls run at 64 queries."""
    length, heads, kv_heads, d = 64, 4, 2, 8
    if arm == 'kernels':
        request.getfixturevalue('pallas_interpret')
        monkey = request.getfixturevalue('monkeypatch')
        monkey.setattr(fa, 'FLASH_MIN_SEQ', 64)
    q_rows = 2 * length if rows == 'both_copies' else length
    rng = np.random.RandomState(block)
    feed = {n: rng.randn(2, r, h, d).astype('float32')
            for n, r, h in (('q', q_rows, heads), ('w', q_rows, heads),
                            ('k', 2 * length, kv_heads),
                            ('v', 2 * length, kv_heads))}
    fused = monitor.counter_value(
        'pallas/flash_attention/dispatch_fused') or 0
    with fluid.scope_guard(fluid.Scope()):
        main, startup, out, grads = _layer_program(
            length, block, heads, kv_heads, d, q_rows)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        got = exe.run(main, feed=feed, fetch_list=[out] + grads)
    if arm == 'kernels':    # the two block-mask calls, not the folded one
        assert monitor.counter_value(
            'pallas/flash_attention/dispatch_fused') - fused >= \
            (2 if rows == 'both_copies' else 1)
    mask = reference.visible(length, block)[:q_rows]

    def dense(q, k, v):
        return jnp.sum(_dense_masked(q, k, v, mask)[0] * feed['w'])

    want = _dense_masked(feed['q'], feed['k'], feed['v'], mask)[0]
    assert np.abs(got[0] - want).max() <= 2e-5
    want_grads = jax.grad(dense, (0, 1, 2))(feed['q'], feed['k'],
                                            feed['v'])
    for g, wg, name in zip(got[1:], want_grads, 'qkv'):
        assert np.abs(g - wg).max() <= 1e-4 * np.abs(wg).max(), name


def test_block_diffusion_attention_counts_its_visible_pairs():
    """``sdar/visible_pairs``: the pairs the two block-mask calls let
    through, a head: L (L + B) / 2 + L (L - B) / 2 = L^2 a sequence."""
    with fluid.scope_guard(fluid.Scope()):
        main, startup, out, _ = _layer_program(64, 4, 4, 2, 8, 128)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        feed = {n: np.zeros((3,) + tuple(main.global_block().var(n).shape[1:]),
                            'float32') for n in 'qkvw'}
        exe.run(main, feed=feed, fetch_list=[out])
    assert monitor.gauge_value('sdar/visible_pairs') == 3 * 64 * 64


def test_block_diffusion_attention_refuses_ragged_blocks():
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        q = layers.data('q', shape=[60, 2, 8], dtype='float32')
        with pytest.raises(ValueError):
            layers.block_diffusion_attention(q, q, q, 4)
        with pytest.raises(ValueError):
            layers.block_diffusion_attention(q, q, q, 7)


# --- the program ------------------------------------------------------


def _seeded_weights(shapes, cfg, seed):
    """Weights large enough that every part of the model moves the
    loss: unit-variance matmuls, gains around 1, a router whose top-k
    margins are wide."""
    rng = np.random.RandomState(seed)
    out = []
    for s in shapes:
        if len(s) == 1:
            w = 1 + 0.1 * rng.randn(*s)
        elif s == (cfg.hidden, cfg.experts):
            w = 4.0 * rng.randn(*s) / np.sqrt(s[0])
        elif s[0] == cfg.vocab_size:
            w = rng.randn(*s)
        else:
            w = rng.randn(*s) / np.sqrt(s[-2])
        out.append(w.astype('float32'))
    return out


def _sizes(cfg):
    return dict(layers=cfg.layers, head_dim=cfg.head_dim, top_k=cfg.top_k,
                block=cfg.block_length, eps=cfg.rms_eps,
                theta=cfg.rope_theta, renormalize=cfg.renormalize,
                first=(cfg.experts_held or (0,))[0])


def _run(cfg, seed, feed=None, fetch_logits=False):
    """The train program (SGD at lr 0, so the fetched gradients are the
    whole step) on seeded weights -> (loss, {param: grad}, params in
    creation order, weights, feed, logits or None)."""
    with fluid.scope_guard(fluid.Scope()):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            _, logits, loss = sdar.build_pretrain(cfg, SEQ)
            params = [p.name for p in main.all_parameters()]
            shapes = [tuple(p.shape) for p in main.all_parameters()]
            test = main.clone(for_test=True)
            pairs = fluid.optimizer.SGD(0.0).minimize(loss)[1]
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        weights = _seeded_weights(shapes, cfg, seed)
        scope = fluid.global_scope()
        for name, w in zip(params, weights):
            scope.set_var(name, jnp.asarray(w))
        if feed is None:
            feed = sdar.synthetic_batch(cfg, 2, SEQ, seed)
        if fetch_logits:
            out = exe.run(test, feed=feed, fetch_list=[loss, logits])
            return _scalar(out[0]), None, params, weights, feed, out[1]
        out = exe.run(main, feed=feed,
                      fetch_list=[loss] + [g.name for _, g in pairs])
    grads = {p.name: np.asarray(g, np.float32)
             for (p, _), g in zip(pairs, out[1:])}
    return _scalar(out[0]), grads, params, weights, feed, None


def _reference(cfg, weights, feed, grads=False, **changed):
    f = reference.loss_and_grads if grads else reference.loss
    return jax.jit(functools.partial(f, **dict(_sizes(cfg), **changed)))(
        weights, {k: jnp.asarray(v) for k, v in feed.items()})


# float32 program against the float32 reference, both at full matmul
# precision, the same corruption fed to both: what is left is the order
# of float32 sums (three softmaxes merged by log-sum-exp against one,
# sorted grouped matmuls against a loop over experts)
LOSS_RTOL, GRAD_RTOL = 2e-6, 2e-4


@pytest.mark.parametrize('cfg', [HELD, sdar.TINY],
                         ids=['experts_2_to_5', 'all_experts'])
def test_tiny_f32_loss_and_every_gradient_match_the_reference(cfg):
    """Two sequences through three layers, the first two of them
    recompute groups, the last computing only keys and values of its
    clean rows."""
    groups = monitor.counter_value('executor/recompute_groups') or 0
    loss, grads, params, weights, feed, _ = _run(cfg, 3)
    assert (monitor.counter_value('executor/recompute_groups') or
            0) - groups >= 2
    want, want_grads = _reference(cfg, weights, feed, grads=True)
    assert abs(loss - float(want)) <= LOSS_RTOL * abs(float(want))
    assert set(grads) == set(params)
    assert len(params) == 3 + reference.PER_LAYER * cfg.layers
    for name, g in zip(params, want_grads):
        g = np.asarray(g)
        assert np.abs(g).max() > 0, name
        assert np.abs(grads[name] - g).max() <= \
            GRAD_RTOL * np.abs(g).max(), name


def _causal_mask(length, block):
    """A token-causal mask in place of the block relation."""
    i = np.arange(2 * length)
    copy, pos = i >= length, i % length
    same = copy[:, None] == copy[None, :]
    mask = reference.visible(length, block)
    return np.where(same & copy[:, None],
                    pos[None, :] <= pos[:, None], mask)


def _own_block_clean(length, block):
    """The corrupted copy seeing its own block's CLEAN keys too."""
    i = np.arange(2 * length)
    copy, blk = i >= length, (i % length) // block
    leak = ~copy[:, None] & copy[None, :] & (blk[None, :] == blk[:, None])
    return reference.visible(length, block) | leak


def _positions_not_repeated(feed):
    return dict(feed, pos_ids=np.tile(
        np.arange(2 * SEQ, dtype='int32'), (feed['ids'].shape[0], 1)))


def _no_one_over_t(feed):
    return dict(feed, weights=(feed['weights'] > 0).astype('float32'))


MUTATIONS = {
    'a_causal_mask': dict(mask=_causal_mask(SEQ, 4)),
    'own_block_s_clean_keys_seen': dict(mask=_own_block_clean(SEQ, 4)),
    'another_block_length': dict(block=8),
    'gates_not_renormalised': dict(renormalize=False),
    'a_wrong_held_range': dict(first=3),
    'another_rotary_base': dict(theta=1e4),
    'two_experts_a_token': dict(top_k=2),
    'positions_not_repeated': _positions_not_repeated,
    'no_one_over_t': _no_one_over_t,
    'shifted_labels': 'shifted',
}


@pytest.mark.parametrize('mutation', sorted(MUTATIONS))
def test_a_mutated_reference_fails_the_tolerance(mutation):
    """Each way of getting the objective or the layer wrong moves the
    reference's loss away from the program's by hundreds of
    tolerances."""
    loss, _, _, weights, feed, _ = _run(HELD, 5)
    how = MUTATIONS[mutation]
    if how == 'shifted':    # position i predicting token i + 1
        fed = {k: jnp.asarray(v) for k, v in feed.items()}
        logits = reference.forward(weights, fed, **_sizes(HELD))
        want = float(reference.weighted_cross_entropy(
            logits, jnp.roll(fed['ids'], -1, axis=1), fed['weights']))
    elif callable(how):
        want = float(_reference(HELD, weights, how(feed)))
    else:
        want = float(_reference(HELD, weights, feed, **how))
    right = float(_reference(HELD, weights, feed))
    assert abs(loss - right) <= LOSS_RTOL * right
    assert abs(loss - want) > 300 * LOSS_RTOL * right, (loss, want)


def test_no_logit_leaks_a_token_it_must_not_see():
    """Changing CLEAN token i moves no corrupted logit of the blocks up
    to i's own (the corrupted copy sees earlier blocks' clean tokens
    only) and does move later ones; changing CORRUPTED token i moves
    its own block's logits and no other's."""
    cfg, block = HELD, HELD.block_length
    _, _, _, _, feed, base = _run(cfg, 11, fetch_logits=True)
    i = 13
    blk = np.arange(SEQ) // block
    clean = dict(feed, ids=feed['ids'].copy())
    clean['ids'][0, i] = (clean['ids'][0, i] + 1) % cfg.mask_id
    moved = np.abs(_run(cfg, 11, clean, True)[5] - base).max(-1)
    assert np.all(moved[0, blk <= blk[i]] == 0)
    assert np.all(moved[0, blk > blk[i]] > 1e-6)
    assert np.all(moved[1] == 0)
    noisy = dict(feed, noisy_ids=feed['noisy_ids'].copy())
    noisy['noisy_ids'][0, i] = (noisy['noisy_ids'][0, i] + 1) % cfg.mask_id
    moved = np.abs(_run(cfg, 11, noisy, True)[5] - base).max(-1)
    assert np.all(moved[0, blk != blk[i]] == 0)
    assert np.all(moved[0, blk == blk[i]] > 1e-6)
    assert np.all(moved[1] == 0)


# --- the shares -------------------------------------------------------


def test_the_8_expert_shares_add_up_to_the_uncut_layer():
    """The deployment's 8 chips a layer: the routed experts in 8 shares
    (16 of 128 a share, top-8 of the softmax over all 128, gates
    renormalised).  The parts of the routed sum the shares give
    through ``layers.moe(experts_held=...)`` add up to what the uncut
    reference gives for the whole layer; one share alone is far from
    it."""
    rng = np.random.RandomState(2)
    b, t, d, hidden, shares, experts, top_k = 2, 12, 16, 8, 8, 128, 8
    per = experts // shares
    x = rng.randn(b, t, d).astype('float32')
    wr = (4 * rng.randn(d, experts) / np.sqrt(d)).astype('float32')
    gate, up = (rng.randn(experts, d, hidden).astype('float32') /
                np.sqrt(d) for _ in range(2))
    down = rng.randn(experts, hidden, d).astype('float32') / \
        np.sqrt(hidden)
    with jax.default_matmul_precision('highest'):
        whole = np.asarray(reference.routed(
            jnp.asarray(x.reshape(b * t, d)), wr, gate, up, down, top_k,
            0))
    parts = []
    for i in range(shares):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.scope_guard(fluid.Scope()):
            with fluid.program_guard(main, startup), \
                    fluid.unique_name.guard():
                xv = layers.data('x', shape=[t, d], dtype='float32')
                out, _ = layers.moe(
                    xv, num_experts=experts, hidden_size=hidden,
                    capacity_factor=None, top_k=top_k, renormalize=True,
                    experts_held=(i * per, per), aux_weight=0.0)
                names = [p.name for p in main.all_parameters()]
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            held = slice(i * per, (i + 1) * per)
            for name, w in zip(names, [wr, gate[held], up[held],
                                       down[held]]):
                fluid.global_scope().set_var(name, jnp.asarray(w))
            parts.append(np.asarray(exe.run(
                main, feed={'x': x}, fetch_list=[out])[0]).reshape(
                    b * t, d))
    assert np.abs(sum(parts) - whole).max() <= 2e-5 * np.abs(whole).max()
    assert np.abs(parts[0] - whole).max() > 0.3 * np.abs(whole).max()


def test_the_cell_s_cut_builds_the_published_parameter_count():
    """The published widths, as the cell cuts them (layers 0 to 5,
    experts 0 to 15 of 128, 18992 vocabulary rows), BUILT and counted,
    nothing run: 645,623,296 parameters, by layer as the issue reckons
    them."""
    cfg = copy.copy(sdar.BASE)
    cfg.layers, cfg.experts_held, cfg.vocab_size = 6, (0, 16), 18992
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        sdar.build_pretrain(cfg, 64)
        every = main.all_parameters()
    assert all(p.trainable for p in every)
    count = sum(int(np.prod(p.shape)) for p in every)
    outside = 2 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 128 + 2 * 2048 + \
        2048 * 128
    assert outside == 19140864
    layer = outside + 16 * 3 * 2048 * 768
    assert layer == 94638336
    assert count == 6 * layer + 2 * 18992 * 2048 + 2048 == 645623296


def test_the_startup_values_are_the_assumed_ones():
    """What the startup program leaves: the embedding's data rows
    Normal(0, embed_std) and its LAST row, MASK, Normal(0, init_std)
    like every matrix; the per-head gains of q and k qk_gain, every
    other gain 1 (creation order: embedding; per layer g1, Wq, gq, Wk,
    gk, Wv, Wo, g2, router, gate, up, down; final gain; head)."""
    cfg = copy.copy(sdar.TINY)
    cfg.vocab_size, cfg.embed_std, cfg.qk_gain = 400, 0.7, 2.5
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 11
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        sdar.build_pretrain(cfg, 16)
        names = [p.name for p in main.all_parameters()]
    with fluid.scope_guard(fluid.Scope()):
        fluid.Executor(fluid.XLAPlace(0)).run(startup)
        scope = fluid.global_scope()
        values = [np.asarray(fluid.core.as_array(scope.find_var(n)))
                  for n in names]
    table = values[0]
    assert table.shape == (400, cfg.hidden)
    assert abs(table[:-1].std() - 0.7) < 0.02
    assert abs(table[-1].std() - cfg.init_std) < 0.3 * cfg.init_std
    per = reference.PER_LAYER
    for layer in range(cfg.layers):
        g1, wq, gq, wk, gk, wv, wo, g2 = values[1 + per * layer:][:8]
        assert np.all(gq == 2.5) and np.all(gk == 2.5)
        assert gq.shape == gk.shape == (cfg.head_dim,)
        assert np.all(g1 == 1) and np.all(g2 == 1)
        for w in (wq, wk, wv, wo):
            assert abs(w.std() - cfg.init_std) < 0.1 * cfg.init_std
    assert np.all(values[-2] == 1)


# --- the corruption ---------------------------------------------------


def test_the_corruption_is_a_function_of_ids_and_seed():
    """Deterministic in the seed; MASK where the weight is positive and
    the token where it is 0; one t a block; positions repeated; a
    masked share near E[t] = (1 + t_min) / 2 and a mean weight near
    1."""
    cfg = copy.copy(sdar.TINY)
    cfg.vocab_size = 1000
    ids = np.random.RandomState(0).randint(0, cfg.mask_id, (8, 4096))
    a, b = sdar.corrupt(ids, 2147483777, cfg), sdar.corrupt(
        ids, 2147483777, cfg)
    other = sdar.corrupt(ids, 2147483778, cfg)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a['noisy_ids'], other['noisy_ids'])
    masked = a['weights'] > 0
    assert np.all(a['noisy_ids'][masked] == cfg.mask_id)
    assert np.array_equal(a['noisy_ids'][~masked], ids[~masked])
    assert np.array_equal(a['ids'], ids) and ids.max() < cfg.mask_id
    assert np.array_equal(a['pos_ids'][:, :4096], a['pos_ids'][:, 4096:])
    assert np.array_equal(a['pos_ids'][3, :4096], np.arange(4096))
    # one t a block: the masked tokens of a block carry one weight 1 / t
    w = a['weights'].reshape(8, -1, cfg.block_length)
    top = w.max(-1, keepdims=True)
    assert np.all((w == 0) | (w == top))
    assert 1.0 <= w[w > 0].min() and w.max() <= 1.0 / cfg.t_min
    assert abs(masked.mean() - (1 + cfg.t_min) / 2) < 0.02
    assert abs(a['weights'].mean() - 1.0) < 0.05
    assert {v.dtype.name for v in a.values()} == {'int32', 'float32'}
    with pytest.raises(ValueError):
        sdar.corrupt(ids[:, :4095], 1, cfg)
