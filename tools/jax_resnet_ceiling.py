"""Pure-JAX ResNet-50 bf16 train step: the PLATFORM CEILING for the
bench config (b128, NHWC, momentum) — what a hand-tuned JAX user would
write with no framework in the loop.  Run `python
tools/jax_resnet_ceiling.py [batch]` on the same chip as bench.py and
compare: the gap between the two is the framework's overhead.

Measured 2026-07 on the attached v5e-class chip: 2543 img/s b128
(50.3 ms/step) vs bench.py's 2506 img/s — the fluid-compatible path is
within 1.5% of hand-written JAX (pre-round reading, record removed in
PR 21; not measured on current code).

Synchronization style: a value fetch (np.asarray) closes the timed
window, bench.py's convention.
"""
import sys, time, json
import numpy as np
import jax
import jax.numpy as jnp

def conv(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), 'SAME',
        dimension_numbers=('NHWC', 'HWIO', 'NHWC'))

def bn(x, p, name):
    g, b = p[name + '_g'], p[name + '_b']
    xf = x.astype(jnp.float32)
    cnt = x.shape[0] * x.shape[1] * x.shape[2]
    s1 = jnp.sum(xf, (0, 1, 2))
    s2 = jnp.sum(xf * xf, (0, 1, 2))
    m = s1 / cnt
    v = jnp.maximum(s2 / cnt - m * m, 0.)
    y = (xf - m) * jax.lax.rsqrt(v + 1e-5) * g + b
    return y.astype(x.dtype)

def block(x, p, pre, cin, cmid, stride):
    h = jax.nn.relu(bn(conv(x, p[pre + 'c1'], 1), p, pre + 'b1'))
    h = jax.nn.relu(bn(conv(h, p[pre + 'c2'], stride), p, pre + 'b2'))
    h = bn(conv(h, p[pre + 'c3'], 1), p, pre + 'b3')
    if stride != 1 or cin != cmid * 4:
        x = bn(conv(x, p[pre + 'cs'], stride), p, pre + 'bs')
    return jax.nn.relu(x + h)

CFG = [(3, 64, 1), (4, 128, 2), (6, 256, 2), (3, 512, 2)]

def init_params(rng):
    p = {}
    def cw(name, kh, kw, ci, co):
        p[name] = (rng.randn(kh, kw, ci, co) *
                   (2.0 / (kh * kw * ci)) ** 0.5).astype(np.float32)
    def bnp(name, c):
        p[name + '_g'] = np.ones(c, np.float32)
        p[name + '_b'] = np.zeros(c, np.float32)
    cw('stem', 7, 7, 3, 64); bnp('stem_bn', 64)
    cin = 64
    for gi, (n, cmid, stride) in enumerate(CFG):
        for bi in range(n):
            pre = 'g%db%d' % (gi, bi)
            st = stride if bi == 0 else 1
            cw(pre + 'c1', 1, 1, cin, cmid); bnp(pre + 'b1', cmid)
            cw(pre + 'c2', 3, 3, cmid, cmid); bnp(pre + 'b2', cmid)
            cw(pre + 'c3', 1, 1, cmid, cmid * 4); bnp(pre + 'b3', cmid * 4)
            if st != 1 or cin != cmid * 4:
                cw(pre + 'cs', 1, 1, cin, cmid * 4); bnp(pre + 'bs', cmid * 4)
            cin = cmid * 4
    p['fc_w'] = (rng.randn(2048, 1000) * 0.01).astype(np.float32)
    p['fc_b'] = np.zeros(1000, np.float32)
    return p

def forward(p, x):
    x = x.astype(jnp.bfloat16)
    pb = {k: (v.astype(jnp.bfloat16) if v.ndim == 4 else v)
          for k, v in p.items()}
    h = jax.nn.relu(bn(conv(x, pb['stem'], 2), pb, 'stem_bn'))
    h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), 'SAME')
    cin = 64
    for gi, (n, cmid, stride) in enumerate(CFG):
        for bi in range(n):
            st = stride if bi == 0 else 1
            h = block(h, pb, 'g%db%d' % (gi, bi), cin, cmid, st)
            cin = cmid * 4
    h = jnp.mean(h.astype(jnp.float32), (1, 2))
    return h @ p['fc_w'] + p['fc_b']

def loss_fn(p, x, y):
    logits = forward(p, x)
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(logp, y, 1))

@jax.jit
def step(p, mom, x, y):
    l, g = jax.value_and_grad(loss_fn)(p, x, y)
    mom = jax.tree.map(lambda m, gg: 0.9 * m + gg, mom, g)
    p = jax.tree.map(lambda w, m: w - 0.1 * m, p, mom)
    return l, p, mom

def main():
    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 128
    rng = np.random.RandomState(0)
    p = init_params(rng)
    mom = jax.tree.map(np.zeros_like, p)
    x = jax.device_put(rng.rand(batch, 224, 224, 3).astype('float32'))
    y = jax.device_put(rng.randint(0, 1000, (batch, 1)))
    l, p2, mom2 = step(p, mom, x, y)
    print('warm loss', float(np.asarray(l)))
    for _ in range(4):
        l, p2, mom2 = step(p2, mom2, x, y)
    np.asarray(l)
    steps = 30
    t0 = time.time()
    for _ in range(steps):
        l, p2, mom2 = step(p2, mom2, x, y)
    lv = float(np.asarray(l))  # value fetch = real synchronization
    dt = time.time() - t0
    print('final loss', lv)
    print(json.dumps({'pure_jax_img_per_sec': round(batch * steps / dt, 1),
                      'ms_per_step': round(dt / steps * 1000, 2)}))

main()
