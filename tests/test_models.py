"""Model-zoo integration tests (reference book-tests style: train a few
steps on synthetic data, assert the loss decreases)."""

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import models


def _train(build_fn, batch_fn, opt, steps=15):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 17
    with fluid.program_guard(main, startup):
        feeds, _, loss = build_fn()
        opt.minimize(loss)
    rng = np.random.RandomState(0)
    losses = []
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        for _ in range(steps):
            l, = exe.run(main, feed=batch_fn(rng), fetch_list=[loss])
            losses.append(float(l))
    return losses


def test_bert_tiny_trains():
    cfg = models.bert.TINY
    losses = _train(
        lambda: models.bert.build_pretrain(cfg, seq_len=32),
        lambda rng: models.bert.synthetic_batch(cfg, 8, 32, rng),
        fluid.optimizer.Adam(1e-3))
    assert losses[-1] < losses[0], losses


@pytest.mark.parametrize('attn_dropout,per_layer', [(0.0, 2), (0.1, 3)])
def test_bert_train_step_draws_dropout_from_the_counter_hash(
        attn_dropout, per_layer):
    """The lowered train step of a two-layer BERT holds no threefry
    generator: every `dropout` op (two a layer and the embedding's,
    one more a layer on the attention probabilities of a short
    sequence) draws from ops/keep_hash.py, one
    `dropout/counter_draws` a lowering."""
    import jax
    from paddle_tpu.fluid import monitor
    layers, seq = 2, 32
    cfg = models.bert.BertConfig(
        vocab_size=128, hidden=32, layers=layers, heads=2,
        intermediate=64, max_pos=seq, dropout=0.1,
        attn_dropout=attn_dropout)
    ops = per_layer * layers + 1
    batch = models.bert.synthetic_batch(cfg, 4, seq,
                                        np.random.RandomState(0))
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        _, _, loss = models.bert.build_pretrain(cfg, seq)
        fluid.optimizer.Adam(1e-3).minimize(loss)
    assert sum(op.type == 'dropout'
               for op in main.global_block().ops) == ops
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        step = exe.compile(main, feed_names=sorted(batch),
                           fetch_names=[loss.name])
        scope = fluid.global_scope()
        state = {n: fluid.core.as_array(scope.find_var(n))
                 for n in step.state_names}
        data = {n: batch[n] if n in batch
                else fluid.core.as_array(scope.find_var(n))
                for n in step.input_names}
        before = monitor.counter_value('dropout/counter_draws')
        text = jax.jit(step.fn).lower(
            np.int32(0), state, data).as_text(debug_info=True)
        drawn = monitor.counter_value('dropout/counter_draws') - before
        # the one-chip runner's whole-program gradient lowers an op
        # once; a runner that lowers the grad op's replay apart lowers
        # it twice (XLA merges the two draws)
        assert drawn and drawn % ops == 0, (drawn, ops)
        assert monitor.gauge_value('dropout/elements') == \
            drawn // ops * (
                (2 * layers + 1) * 4 * seq * cfg.hidden +
                (per_layer - 2) * layers * 4 * cfg.heads * seq * seq)
    assert 'threefry' not in text and '_bernoulli' not in text
    assert 'dropout' in text        # the scopes are there to look in


def test_transformer_tiny_trains():
    # fixed batch (memorization): with fresh random token batches every
    # step the loss signal is below the dropout noise floor at 15 steps
    cfg = models.transformer.TINY
    cache = {}

    def batch_fn(rng):
        if 'b' not in cache:
            cache['b'] = models.transformer.synthetic_batch(
                cfg, 8, 16, 16, rng)
        return cache['b']

    losses = _train(
        lambda: models.transformer.build(cfg, src_len=16, tgt_len=16),
        batch_fn, fluid.optimizer.Adam(1e-3))
    assert losses[-1] < losses[0], losses


def test_wide_deep_trains():
    cfg = models.wide_deep.TINY
    losses = _train(
        lambda: models.wide_deep.build(cfg),
        lambda rng: models.wide_deep.synthetic_batch(cfg, 32, rng),
        fluid.optimizer.Adam(5e-3), steps=25)
    assert losses[-1] < losses[0], losses


def test_word2vec_trains():
    fixed = {}

    def batch(rng):
        # memorize one fixed batch: reliable loss decrease in few steps
        if not fixed:
            fixed.update(models.word2vec.synthetic_batch(200, 32, rng))
        return fixed

    losses = _train(lambda: models.word2vec.build(vocab_size=200),
                    batch, fluid.optimizer.Adam(5e-3), steps=25)
    assert losses[-1] < losses[0], losses


def test_resnet18_cifar_trains():
    def build():
        feeds_logits = models.resnet.build(image_shape=(3, 32, 32),
                                           class_dim=10, depth=18)
        feeds, logits, loss, acc = feeds_logits
        return feeds, logits, loss

    def batch(rng):
        x = rng.randn(8, 3, 32, 32).astype('float32')
        y = rng.randint(0, 10, (8, 1)).astype('int64')
        return {'image': x, 'label': y}

    losses = _train(build, batch, fluid.optimizer.Momentum(0.01, 0.9),
                    steps=10)
    # random labels: just require a finite, stable optimization
    assert np.isfinite(losses).all()
    assert losses[-1] < 15.0, losses


def test_resnet50_builds():
    """Full ResNet-50 graph builds with correct shapes (compile check is
    bench/graft territory)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        feeds, logits, loss, acc = models.resnet.build()
    assert tuple(logits.shape) == (-1, 1000)
    n_params = len(main.all_parameters())
    # 53 convs + 53 BN(scale+bias) + fc(w+b) and BN means/vars are
    # parameters too in this design
    assert n_params > 150, n_params


def test_gpt_lm_learns_pattern_and_generates():
    """Decoder-only causal LM (models/gpt.py): trains on a deterministic
    +3 (mod V) token sequence, loss collapses, and greedy decoding
    continues the pattern — exercising causal attention masks through
    training AND the host-driven generation loop."""
    from paddle_tpu.models import gpt

    cfg = gpt.GptConfig(vocab_size=23, hidden=32, layers=2, heads=4,
                        max_pos=16, dropout=0.0)
    seq = 16
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 17
    with fluid.program_guard(main, startup):
        feeds, logits, loss = gpt.build_lm(cfg, seq)
        infer_prog = main.clone(for_test=True)
        fluid.optimizer.Adam(3e-3).minimize(loss)

    rng = np.random.RandomState(0)

    def batch(n=32):
        starts = rng.randint(0, cfg.vocab_size, (n, 1))
        ids = (starts + 3 * np.arange(seq)) % cfg.vocab_size
        return gpt.lm_batch(ids)

    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.XLAPlace(0))
        exe.run(startup)
        losses = []
        for _ in range(120):
            l, = exe.run(main, feed=batch(), fetch_list=[loss])
            losses.append(float(np.asarray(l).ravel()[0]))
        assert np.isfinite(losses).all()
        assert losses[-1] < 0.1, (losses[0], losses[-1])

        toks = gpt.greedy_generate(exe, infer_prog, logits, [5, 8, 11],
                                   steps=6, cfg=cfg)
    want = [(5 + 3 * i) % cfg.vocab_size for i in range(9)]
    assert toks == want, (toks, want)


def test_gpt_flash_path_matches_naive():
    """The causal flash dispatch (seq >= flash_min_len) produces the
    same logits as the naive masked chain — model-level wiring check
    for fused_multihead_attention(causal=True)."""
    from paddle_tpu.models import gpt

    def logits_with(use_flash):
        cfg = gpt.GptConfig(vocab_size=31, hidden=32, layers=1,
                            heads=4, max_pos=32, dropout=0.0,
                            use_flash=use_flash)
        cfg.flash_min_len = 16   # force the flash path at seq 32
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 23
        with fluid.program_guard(main, startup):
            feeds, logits, loss = gpt.build_lm(cfg, 32, is_test=True)
        rng = np.random.RandomState(1)
        ids = rng.randint(0, 31, (2, 32))
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.XLAPlace(0))
            exe.run(startup)
            out, = exe.run(main, feed=gpt.lm_batch(ids),
                           fetch_list=[logits])
        return np.asarray(out)

    naive = logits_with(False)
    flash = logits_with(True)
    np.testing.assert_allclose(flash, naive, rtol=2e-3, atol=2e-3)
