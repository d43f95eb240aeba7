"""Pure-JAX platform-ceiling train steps for the non-ResNet BASELINE
configs (round-4 VERDICT item 2): what a hand-tuned JAX user would
write with no framework in the loop, same batch/precision/optimizer as
the matching bench.py entry.  The gap bench-vs-ceiling isolates
framework overhead from platform limits, like
tools/jax_resnet_ceiling.py does for config 1.

  python tools/jax_ceilings.py bert  [--batch 32] [--seq 128]
  python tools/jax_ceilings.py bert  --batch 4 --seq 2048   # flash
  python tools/jax_ceilings.py widedeep [--batch 2048]
  python tools/jax_ceilings.py nmt   [--batch 32]

AMP semantics mirror the bench programs: bf16 activations with f32
MASTER weights (params cast to bf16 at use), f32 Adam/Adagrad, dynamic
loss scaling (scale the loss, all-finite check over grads, skip-or-
apply + scale update) for bert/nmt.  Sync style: np.asarray value
fetch (bench.py's convention).
"""
import argparse
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

BF16 = jnp.bfloat16


# ---------------------------------------------------------------- common

def dense(x, w, b=None):
    y = x @ w.astype(x.dtype)
    if b is not None:
        y = y + b.astype(x.dtype)
    return y


def layer_norm(x, g, b, eps=1e-5):
    xf = x.astype(jnp.float32)
    m = jnp.mean(xf, -1, keepdims=True)
    v = jnp.mean(jnp.square(xf - m), -1, keepdims=True)
    y = (xf - m) * jax.lax.rsqrt(v + eps) * g + b
    return y.astype(x.dtype)


def dropout(x, rate, key):
    if not rate:
        return x
    keep = jax.random.bernoulli(key, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0).astype(x.dtype)


def adam_init(params):
    zeros = lambda p: jax.tree.map(jnp.zeros_like, p)
    return {'m': zeros(params), 'v': zeros(params),
            't': jnp.zeros((), jnp.float32)}


def adam_apply(params, grads, st, lr=1e-4, b1=0.9, b2=0.999, eps=1e-8):
    t = st['t'] + 1.0
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, st['m'], grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g,
                     st['v'], grads)
    corr = jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
    new = jax.tree.map(
        lambda p, mm, vv: p - lr * corr * mm / (jnp.sqrt(vv) + eps),
        params, m, v)
    return new, {'m': m, 'v': v, 't': t}


def scaled_step(loss_fn, params, opt_state, scale, *args):
    """Dynamic-loss-scaling step (the AMP decorate semantics): scale
    the loss, unscale grads, all-finite check gates the update, scale
    doubles every 1000 good steps / halves on overflow."""
    def scaled_loss(p):
        return loss_fn(p, *args).astype(jnp.float32) * scale['s']
    loss, grads = jax.value_and_grad(scaled_loss)(params)
    grads = jax.tree.map(lambda g: g.astype(jnp.float32) / scale['s'],
                         grads)
    finite = jnp.all(jnp.stack([jnp.all(jnp.isfinite(g))
                                for g in jax.tree.leaves(grads)]))
    new_params, new_opt = adam_apply(params, grads, opt_state)
    params = jax.tree.map(lambda a, b: jnp.where(finite, a, b),
                          new_params, params)
    opt_state = jax.tree.map(lambda a, b: jnp.where(finite, a, b),
                             new_opt, opt_state)
    good = jnp.where(finite, scale['good'] + 1, 0)
    s = jnp.where(finite,
                  jnp.where(good >= 1000, scale['s'] * 2.0, scale['s']),
                  scale['s'] * 0.5)
    good = jnp.where(good >= 1000, 0, good)
    return loss / scale['s'], params, opt_state, {'s': s, 'good': good}


def _sync(state):
    """Close the async-dispatch window by fetching the SMALLEST state
    leaf (a scalar: adam t / scale / step counter).  Fetching a big
    leaf would time the device-to-host copy — syncing on the
    [30522,768] embedding ships 94 MB per sync."""
    leaves = jax.tree.leaves(state)
    np.asarray(min(leaves, key=lambda a: getattr(a, 'size', 1 << 60)))


def timeit(step, state, steps, feed):
    # device-resident feeds AND initial state, like bench._timed_steps:
    # shipping numpy per call forces synchronous host-to-device
    # transfers and a recompile on the numpy->Array transition
    feed = tuple(jax.device_put(np.asarray(f)) for f in feed)
    state = jax.tree.map(jax.device_put, state)
    state = step(state, *feed)  # warm/compile
    _sync(state)
    t0 = time.perf_counter()
    for _ in range(steps):
        state = step(state, *feed)
    _sync(state)
    return (time.perf_counter() - t0) / steps


# ---------------------------------------------------------------- bert

def run_bert(batch, seq, steps, ablate=(), n_layers=12):
    V, H, L, NH, FF, TV = 30522, 768, n_layers, 12, 3072, 2
    D = H // NH
    drop = 0.0 if 'dropout' in ablate else 0.1
    attn_drop = (0.1 if seq < 512 else 0.0) if 'dropout' not in ablate \
        else 0.0
    use_flash = seq >= 512
    rng = np.random.RandomState(0)

    def w(*shape):
        return (rng.randn(*shape) * 0.02).astype(np.float32)

    params = {'emb': w(V, H), 'pos': w(seq, H), 'sent': w(TV, H),
              'ln0_g': np.ones(H, np.float32),
              'ln0_b': np.zeros(H, np.float32),
              'mlm_w': w(H, V), 'mlm_b': np.zeros(V, np.float32),
              'nsp_w': w(H, 2), 'nsp_b': np.zeros(2, np.float32)}
    for i in range(L):
        params.update({
            'l%d_qkv' % i: w(H, 3 * H),
            'l%d_qkv_b' % i: np.zeros(3 * H, np.float32),
            'l%d_o' % i: w(H, H), 'l%d_o_b' % i: np.zeros(H, np.float32),
            'l%d_ln1_g' % i: np.ones(H, np.float32),
            'l%d_ln1_b' % i: np.zeros(H, np.float32),
            'l%d_f1' % i: w(H, FF), 'l%d_f1_b' % i: np.zeros(FF,
                                                            np.float32),
            'l%d_f2' % i: w(FF, H), 'l%d_f2_b' % i: np.zeros(H,
                                                             np.float32),
            'l%d_ln2_g' % i: np.ones(H, np.float32),
            'l%d_ln2_b' % i: np.zeros(H, np.float32)})

    ids = rng.randint(0, V, (batch, seq)).astype('int32')
    sent = np.zeros((batch, seq), 'int32')
    mlm = np.where(rng.rand(batch, seq) < 0.15,
                   rng.randint(0, V, (batch, seq)), -1).astype('int32')
    nsp = rng.randint(0, 2, (batch,)).astype('int32')

    if use_flash:
        import os
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        from paddle_tpu.ops.pallas.flash_attention import flash_attention

    def attention(x, p, i, key, key_bias):
        qkv = dense(x, p['l%d_qkv' % i], p['l%d_qkv_b' % i])
        q, k, v = jnp.split(qkv, 3, -1)
        q, k, v = [a.reshape(batch, seq, NH, D) for a in (q, k, v)]
        if use_flash:
            # the framework's bench passes the input mask as the flash
            # key bias; ride it as a runtime arg so the ceiling pays
            # the same per-block bias add + dbias backward
            ctx = flash_attention(q, k, v, min_seq=0,
                                  key_bias=key_bias)
        else:
            s = jnp.einsum('bthd,bshd->bhts', q, k,
                           preferred_element_type=jnp.float32) / D ** 0.5
            # the framework's naive chain adds the input-mask bias too
            s = s + key_bias[:, None, None, :]
            pr = jax.nn.softmax(s, -1).astype(x.dtype)
            pr = dropout(pr, attn_drop, key)
            ctx = jnp.einsum('bhts,bshd->bthd', pr, v)
        return dense(ctx.reshape(batch, seq, H), p['l%d_o' % i],
                     p['l%d_o_b' % i])

    def loss_fn(p, ids, sent_ids, mlm_label, nsp_label, key_bias,
                step_key):
        x = (p['emb'][ids] + p['pos'][None, :, :] +
             p['sent'][sent_ids]).astype(BF16)
        x = layer_norm(x, p['ln0_g'], p['ln0_b'])
        keys = jax.random.split(step_key, 3 * L)
        for i in range(L):
            a = dropout(attention(x, p, i, keys[3 * i], key_bias),
                        drop, keys[3 * i + 1])
            x = layer_norm(x + a, p['l%d_ln1_g' % i], p['l%d_ln1_b' % i])
            f = dense(x, p['l%d_f1' % i], p['l%d_f1_b' % i])
            f = jax.nn.gelu(f, approximate=False)
            f = dense(f, p['l%d_f2' % i], p['l%d_f2_b' % i])
            f = dropout(f, drop, keys[3 * i + 2])
            x = layer_norm(x + f, p['l%d_ln2_g' % i], p['l%d_ln2_b' % i])
        if 'head' in ablate:
            mlm_loss = jnp.mean(jnp.square(x.astype(jnp.float32)))
        else:
            logits = dense(x, p['mlm_w'],
                           p['mlm_b']).astype(jnp.float32)
            lp = jax.nn.log_softmax(logits, -1)
            tgt = jnp.maximum(mlm_label, 0)
            nll = -jnp.take_along_axis(lp, tgt[..., None], -1)[..., 0]
            maskd = (mlm_label >= 0).astype(jnp.float32)
            mlm_loss = jnp.sum(nll * maskd) / \
                jnp.maximum(jnp.sum(maskd), 1)
        cls = x[:, 0, :]
        nl = dense(cls, p['nsp_w'], p['nsp_b']).astype(jnp.float32)
        nlp = jax.nn.log_softmax(nl, -1)
        nsp_loss = -jnp.mean(
            jnp.take_along_axis(nlp, nsp_label[:, None], -1))
        return mlm_loss + nsp_loss

    opt = adam_init(params)
    scale = {'s': jnp.float32(32768.0), 'good': jnp.zeros((), jnp.int32)}

    @jax.jit
    def step(state, ids, sent_ids, mlm_label, nsp_label, key_bias):
        params, opt, scale, it = state
        key = jax.random.fold_in(jax.random.PRNGKey(0), it)
        if 'scaling' in ablate:
            loss, grads = jax.value_and_grad(loss_fn)(
                params, ids, sent_ids, mlm_label, nsp_label, key_bias,
                key)
            params, opt = adam_apply(params, grads, opt)
        else:
            loss, params, opt, scale = scaled_step(
                loss_fn, params, opt, scale, ids, sent_ids, mlm_label,
                nsp_label, key_bias, key)
        return (params, opt, scale, it + 1)

    state = (params, opt, scale, jnp.zeros((), jnp.int32))
    key_bias = np.zeros((batch, seq), np.float32)  # used on flash path
    dt = timeit(step, state, steps, (ids, sent, mlm, nsp, key_bias))
    print('bert ceiling b%d s%d%s: %.2f ms/step (%.1f seq/s)'
          % (batch, seq,
             (' -' + ','.join(sorted(ablate))) if ablate else '',
             dt * 1e3, batch / dt))


# ------------------------------------------------------------ wide&deep

def run_widedeep(batch, steps):
    VOC, EMB, NS, ND = 1000, 16, 26, 13
    HID = (400, 400, 400)
    rng = np.random.RandomState(0)
    params = {'demb': (rng.randn(VOC, EMB) * 0.02).astype(np.float32),
              'wemb': (rng.randn(VOC, 1) * 0.02).astype(np.float32),
              'wd': (rng.randn(ND, 1) * 0.05).astype(np.float32)}
    last = ND + NS * EMB
    for i, h in enumerate(HID):
        params['h%d' % i] = (rng.randn(last, h) *
                             (2.0 / last) ** 0.5).astype(np.float32)
        params['h%d_b' % i] = np.zeros(h, np.float32)
        last = h
    params['out'] = (rng.randn(last, 1) * 0.05).astype(np.float32)
    params['out_b'] = np.zeros(1, np.float32)

    dense_x = rng.rand(batch, ND).astype('float32')
    sparse_x = rng.randint(0, VOC, (batch, NS)).astype('int32')
    label = rng.randint(0, 2, (batch, 1)).astype('float32')

    def loss_fn(p, dense_x, sparse_x, label):
        emb = p['demb'][sparse_x].reshape(batch, NS * EMB)
        x = jnp.concatenate([dense_x, emb], 1)
        for i in range(len(HID)):
            x = jax.nn.relu(x @ p['h%d' % i] + p['h%d_b' % i])
        deep = x @ p['out'] + p['out_b']
        wide = jnp.sum(p['wemb'][sparse_x], 1) + dense_x @ p['wd']
        logit = deep + wide
        return jnp.mean(
            jnp.maximum(logit, 0) - logit * label +
            jnp.log1p(jnp.exp(-jnp.abs(logit))))

    acc = jax.tree.map(jnp.zeros_like, params)

    @jax.jit
    def step(state, dense_x, sparse_x, label):
        p, acc = state
        g = jax.grad(loss_fn)(p, dense_x, sparse_x, label)
        acc = jax.tree.map(lambda a, gg: a + gg * gg, acc, g)
        p = jax.tree.map(
            lambda pp, gg, aa: pp - 0.01 * gg / (jnp.sqrt(aa) + 1e-6),
            p, g, acc)
        return (p, acc)

    dt = timeit(step, (params, acc), steps, (dense_x, sparse_x, label))
    print('wide&deep ceiling b%d: %.2f ms/step (%.0f ex/s)'
          % (batch, dt * 1e3, batch / dt))


# ------------------------------------------------------------------ nmt

def run_nmt(batch, steps, src_len=64, tgt_len=64):
    # FAITHFUL to models/transformer.py + bench_transformer: fc biases
    # everywhere, dropout on embeddings / attention probs / ffn mid
    # (18+ sites), additive pad bias on encoder scores, post-LN, label
    # smoothing, AMP + dynamic loss scaling, Adam
    V, H, NH, FF, L = 10000, 512, 8, 2048, 6
    D = H // NH
    drop = 0.1
    eps_ls = 0.1
    rng = np.random.RandomState(0)

    def w(*shape):
        return (rng.randn(*shape) * 0.02).astype(np.float32)

    def b(n):
        return np.zeros(n, np.float32)

    params = {'semb': w(V, H), 'temb': w(V, H), 'proj': w(H, V),
              'proj_b': b(V)}
    for side, n in (('e', L), ('d', L)):
        for i in range(n):
            pre = '%s%d_' % (side, i)
            params.update({pre + 'qkv': w(H, 3 * H),
                           pre + 'qkv_b': b(3 * H),
                           pre + 'o': w(H, H), pre + 'o_b': b(H),
                           pre + 'ln1g': np.ones(H, np.float32),
                           pre + 'ln1b': np.zeros(H, np.float32),
                           pre + 'f1': w(H, FF), pre + 'f1_b': b(FF),
                           pre + 'f2': w(FF, H), pre + 'f2_b': b(H),
                           pre + 'ln2g': np.ones(H, np.float32),
                           pre + 'ln2b': np.zeros(H, np.float32)})
            if side == 'd':
                params.update({pre + 'xq': w(H, H), pre + 'xq_b': b(H),
                               pre + 'xk': w(H, H), pre + 'xk_b': b(H),
                               pre + 'xv': w(H, H), pre + 'xv_b': b(H),
                               pre + 'xo': w(H, H), pre + 'xo_b': b(H),
                               pre + 'ln3g': np.ones(H, np.float32),
                               pre + 'ln3b': np.zeros(H, np.float32)})

    src = rng.randint(0, V, (batch, src_len)).astype('int32')
    tgt = rng.randint(0, V, (batch, tgt_len)).astype('int32')
    lab = rng.randint(0, V, (batch, tgt_len)).astype('int32')

    def posenc(t):
        pos = np.arange(t)[:, None]
        i = np.arange(H)[None, :]
        ang = pos / np.power(10000, (2 * (i // 2)) / H)
        pe = np.where(i % 2 == 0, np.sin(ang), np.cos(ang))
        return jnp.asarray(pe, BF16)


    def mha(q_in, kv_in, wqkv, wo, causal, key, xattn=None,
            bias=None):
        if xattn is None:
            qkv = dense(q_in, wqkv[0], wqkv[1])
            q, k, v = jnp.split(qkv, 3, -1)
        else:
            (wq, bq_), (wk, bk_), (wv, bv_) = xattn
            q = dense(q_in, wq, bq_)
            k = dense(kv_in, wk, bk_)
            v = dense(kv_in, wv, bv_)
        b, tq = q.shape[:2]
        tk = k.shape[1]
        q = q.reshape(b, tq, NH, D)
        k = k.reshape(b, tk, NH, D)
        v = v.reshape(b, tk, NH, D)
        s = jnp.einsum('bthd,bshd->bhts', q, k,
                       preferred_element_type=jnp.float32) / D ** 0.5
        if bias is not None:
            s = s + bias
        if causal:
            mask = jnp.tril(jnp.ones((tq, tk), bool))
            s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, -1).astype(q_in.dtype)
        p = dropout(p, drop, key)
        ctx = jnp.einsum('bhts,bshd->bthd', p, v).reshape(b, tq, H)
        return dense(ctx, wo[0], wo[1])

    def loss_fn(p, src, tgt, lab, pad_bias, key):
        keys = jax.random.split(key, 8 * L + 2)
        kc = iter(range(8 * L))
        x = (p['semb'][src].astype(BF16) * (H ** 0.5) +
             posenc(src_len)[None])
        x = dropout(x, drop, keys[-1])
        for i in range(L):
            pre = 'e%d_' % i
            a = mha(x, x, (p[pre + 'qkv'], p[pre + 'qkv_b']),
                    (p[pre + 'o'], p[pre + 'o_b']), False,
                    keys[next(kc)], bias=pad_bias)
            x = layer_norm(x + a, p[pre + 'ln1g'], p[pre + 'ln1b'])
            f = jax.nn.relu(dense(x, p[pre + 'f1'], p[pre + 'f1_b']))
            f = dropout(f, drop, keys[next(kc)])
            f = dense(f, p[pre + 'f2'], p[pre + 'f2_b'])
            x = layer_norm(x + f, p[pre + 'ln2g'], p[pre + 'ln2b'])
        mem = x
        y = (p['temb'][tgt].astype(BF16) * (H ** 0.5) +
             posenc(tgt_len)[None])
        y = dropout(y, drop, keys[-2])
        for i in range(L):
            pre = 'd%d_' % i
            a = mha(y, y, (p[pre + 'qkv'], p[pre + 'qkv_b']),
                    (p[pre + 'o'], p[pre + 'o_b']), True,
                    keys[next(kc)])
            y = layer_norm(y + a, p[pre + 'ln1g'], p[pre + 'ln1b'])
            xa = mha(y, mem, None,
                     (p[pre + 'xo'], p[pre + 'xo_b']), False,
                     keys[next(kc)],
                     xattn=((p[pre + 'xq'], p[pre + 'xq_b']),
                            (p[pre + 'xk'], p[pre + 'xk_b']),
                            (p[pre + 'xv'], p[pre + 'xv_b'])),
                     bias=pad_bias)
            y = layer_norm(y + xa, p[pre + 'ln3g'], p[pre + 'ln3b'])
            f = jax.nn.relu(dense(y, p[pre + 'f1'], p[pre + 'f1_b']))
            f = dropout(f, drop, keys[next(kc)])
            f = dense(f, p[pre + 'f2'], p[pre + 'f2_b'])
            y = layer_norm(y + f, p[pre + 'ln2g'], p[pre + 'ln2b'])
        logits = dense(y, p['proj'], p['proj_b']).astype(jnp.float32)
        lp = jax.nn.log_softmax(logits, -1)
        smooth = (1 - eps_ls)
        nll = -jnp.take_along_axis(lp, lab[..., None], -1)[..., 0]
        uniform = -jnp.mean(lp, -1)
        return jnp.mean(smooth * nll + eps_ls * uniform)

    opt = adam_init(params)
    scale = {'s': jnp.float32(32768.0), 'good': jnp.zeros((), jnp.int32)}

    @jax.jit
    def step(state, src, tgt, lab, pad_bias):
        params, opt, scale, it = state
        key = jax.random.fold_in(jax.random.PRNGKey(0), it)
        loss, params, opt, scale = scaled_step(
            loss_fn, params, opt, scale, src, tgt, lab, pad_bias, key)
        return (params, opt, scale, it + 1)

    state = (params, opt, scale, jnp.zeros((), jnp.int32))
    # pad bias rides as a RUNTIME argument: a captured zeros constant
    # would be algebraically deleted by XLA and the ceiling would not
    # pay the add+broadcast the framework model pays
    pad_bias_np = np.zeros((batch, 1, 1, src_len), np.float32)
    dt = timeit(step, state, steps, (src, tgt, lab, pad_bias_np))
    print('nmt ceiling b%d %d/%d: %.2f ms/step (%.0f tok/s)'
          % (batch, src_len, tgt_len, dt * 1e3,
             batch * tgt_len / dt))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('which', choices=['bert', 'widedeep', 'nmt'])
    ap.add_argument('--batch', type=int, default=None)
    ap.add_argument('--seq', type=int, default=128)
    ap.add_argument('--steps', type=int, default=20)
    ap.add_argument('--ablate', default='',
                    help='comma list: dropout,head,scaling')
    ap.add_argument('--layers', type=int, default=12)
    args = ap.parse_args()
    if args.which == 'bert':
        run_bert(args.batch or 32, args.seq, args.steps,
                 ablate=tuple(a for a in args.ablate.split(',') if a),
                 n_layers=args.layers)
    elif args.which == 'widedeep':
        run_widedeep(args.batch or 2048, args.steps)
    else:
        run_nmt(args.batch or 32, args.steps)


if __name__ == '__main__':
    main()
