"""Manifold-constrained hyper-connections ("mHC", DeepSeek-AI,
arXiv:2512.24880, over Hyper-Connections, arXiv:2409.19606): the
residual of a decoder is a STREAM ``X`` of ``n`` rows of ``C`` features
a token, and every operator ``F`` (attention; MLP or experts) reads one
mix of the rows and writes back into all of them through a per-token
``n x n`` matrix that is brought to the doubly stochastic matrices by
``iters`` Sinkhorn normalisations.  With parameters of the operator's
own, ``phi`` [n C, n^2 + 2 n], three scalars ``alpha`` and a bias ``b``
[n^2 + 2 n]:

    r       = vec(X) / sqrt(mean(vec(X)^2) + epsilon)            (no gain)
    phi     = Phi / sqrt(n C)                  (the parameter, see STORED)
    [p~ | q~ | R~] = alpha_pre (r phi_pre) + b_pre |
                     alpha_post (r phi_post) + b_post |
                     alpha_res mat(r phi_res) + b_res            (row-major)
    H_pre   = sigmoid(p~)  [n]          H_post = 2 sigmoid(q~)  [n]
    M_0     = exp(clamp(R~, clamp_min, clamp_max))
    iters times:  M <- M / (rowsum(M) + hc_eps);  M <- M / (colsum(M) + hc_eps)
    H_res   = M                                                  [n, n]
    u       = H_pre X   [C]      y = F(RMSNorm(u))      X' = H_res X + H_post^T y

``hyper_connection_pre`` is everything up to ``u`` (it hands H_post and
H_res on), ``hyper_connection_post`` the last equation; the operator
and its norm lie between them as ops of the program.

STORED.  The parameter ``Phi`` holds phi at UNIT size, sqrt(n C) phi:
the op divides by sqrt(n C), the square root of the projection's
fan-in (the same equations; r Phi / sqrt(n C) is vec(X) Phi over the
LENGTH of vec(X)).  An optimizer whose step does not know the fan-in
(Adam's is the learning rate an element, whatever the gradient's size)
then moves a logit by at most lr sqrt(n C) a step where phi stored as
it is used would move it by lr n C: at n C = 14336 and 4e-4 that was
5.7 of logit a step, and after 23 steps the logits stood at the clamp
and 20 normalisations no longer converged (``mhc/stochastic_err`` 1e-2;
my chip runs, PR 54).

DTYPES.  r, the projection, the three maps and the Sinkhorn loop are
float32 whatever the stream's type is; the read-out and the write-back
multiply and add in float32 and round once: ``U`` to X's type, ``XOut``
to Y's (the operator's output is the program's type: under bf16 AMP the
stream is bfloat16 from the first write-back on).  Neither op is cast
by ``mixed_precision.decorate``.

THE PROJECTION ``r phi`` is computed as ``(X phi) / rms``: [tokens,
n C] x [n C, n^2 + 2 n].  Of float32 operands it runs at
``Precision.HIGHEST`` (six bfloat16 passes of the MXU over 24 columns
of its 128; 37 of a 331 ms step at the published widths: my chip run,
PR 54).  A BFLOAT16 stream is exact in bfloat16, so there phi alone is
split into three bfloat16 terms whose sum is phi to float32's last
place, laid side by side ([n C, 3 x 24]), and ONE pass with a float32
accumulator gives the three partial products: the float32 result at a
sixth of the passes.  Its gradient is autodiff's: the cotangent meets
bfloat16 operands as every matmul's does under AMP.

LAYOUT.  The maps lie TOKENS-LAST ([B, n, T], [B, n, n, T]): a 4 x 4
matrix a token in the two minor dimensions would pad every (8, 128)
tile of the chip sixteen-fold and more.  The stream is addressed as
[B T, n C] with the rows as column ranges.

GRADIENT.  jax.vjp of these lowerings: exact through all ``iters``
normalisations, trip by trip (no fixed-point shortcut).  THE LOOP runs
one of two ways, chosen once a call from what M_0 shows (``project``,
``common.dispatch``; counters ``pallas/sinkhorn/dispatch_*``):

- on a TPU, for a float32 M_0 over whole 128-token rows, INSIDE ONE
  KERNEL CALL a side (``ops/pallas/sinkhorn.py``): the forward call runs
  all the trips on a tile of 1024 tokens held in registers and keeps M_0
  alone; the backward call runs them again with every half-trip's input
  in a VMEM scratch and walks them in reverse (``y = m / (s + hc_eps)``,
  ``dm = (dy - sum(dy * y)) / (s + hc_eps)`` over the summed axis);
- otherwise (off a TPU, the tokens no multiple of 128, float64, under
  the GSPMD runner, a scratch over the kernels' VMEM budget) as ONE
  ``lax.scan`` under a ``jax.checkpoint``: a forward pass keeps M_0
  alone, and the gradient runs the scan again with every trip's M kept
  in HBM.  On the chip a trip of it is seven small fusions, 4.7 us:
  960 trips a step of the Xing4 cell were 4.5 ms where the kernels'
  36 calls are 0.2 (my chip runs, PR 55; PERF.md section 6).

It is bound by BYTES beside operators bound by the MXU: a fused
implementation moves (3 n + 2) C elements of the stream's type a token
forward (X read once for the maps and the read-out, u written; X and y
read, X' written).  What XLA makes of it is what
``benchmark/layer_metrics/mhc_roofline.py`` reads.
"""

import jax
import jax.numpy as jnp

from .registry import register

_HIGHEST = jax.lax.Precision.HIGHEST


def sinkhorn(m, iters, hc_eps):
    """m [n, n, S] > 0 (rows, columns, tokens) -> the same after
    ``iters`` x (rows, then columns) normalisations, the DENSE form
    (``project`` runs the ``pallas.sinkhorn`` kernels instead where M_0
    fits them): ONE loop of a fixed trip count in the program
    (``lax.scan``; reverse mode keeps every trip's M and differentiates
    each exactly), not ``iters`` copies of its body: unrolled, 12
    operators x (forward, recomputed forward, backward) x 40 reductions
    made each step program 0.44 GB of code and two minutes of compiling
    (my chip runs, PR 54)."""
    def normalise(m, _):
        m = m / (jnp.sum(m, 1, keepdims=True) + hc_eps)
        return m / (jnp.sum(m, 0, keepdims=True) + hc_eps), None

    return jax.lax.scan(normalise, m, None, length=iters)[0]


def _project(x2, phi):
    """x2 [S, n C] (any float type) x phi [n C, m] float32 -> [S, m]
    float32, to float32's precision: see the module's docstring."""
    if x2.dtype != jnp.bfloat16:
        return jnp.dot(x2.astype(jnp.float32), phi, precision=_HIGHEST)
    terms, rest = [], phi
    for _ in range(3):
        terms.append(rest.astype(jnp.bfloat16))
        rest = rest - terms[-1].astype(jnp.float32)
    parts = jnp.dot(x2, jnp.concatenate(terms, -1),
                    preferred_element_type=jnp.float32)
    m = phi.shape[-1]
    return parts[:, :m] + parts[:, m:2 * m] + parts[:, 2 * m:]


def project(m0, iters, hc_eps, auto_partitioned=False):
    """M_0 [n, n, S] > 0 -> H_res, by the ``sinkhorn`` kernels or by
    the scan: ONE ``common.dispatch`` decision a call, from what the
    operand shows (``pallas.sinkhorn.checks``), which the forward and
    the backward both follow.  Either way a forward pass keeps M_0
    alone: the kernel's ``custom_vjp`` by its rule, the scan under a
    ``jax.checkpoint``."""
    from .pallas import common, sinkhorn as kernel
    fused, interpret = common.dispatch(
        'sinkhorn', True, checks=kernel.checks(m0.shape, m0.dtype, iters),
        auto_partitioned=auto_partitioned)
    if fused:
        return kernel.sinkhorn(m0, iters, hc_eps, interpret)
    return jax.checkpoint(lambda m: sinkhorn(m, iters, hc_eps))(m0)


def maps(x2, phi, alpha, bias, n, epsilon, iters, hc_eps, clamp,
         auto_partitioned=False):
    """x2 [S, n C] -> (H_pre [n, S], H_post [n, S], H_res [n, n, S]),
    float32."""
    # the squares summed ROW BY ROW: a reduction over the whole [S, n C]
    # takes the write-back's concatenate into its fusion as n pads to
    # full width and their maximums (0.34 ms a call where X's bytes need
    # 0.16; my chip runs, PR 55), the rows' slices undo it
    squares = sum(jnp.sum(jnp.square(row), -1, keepdims=True)
                  for row in _rows(x2, n))
    inv = jax.lax.rsqrt(squares / x2.shape[-1] + epsilon)
    proj = (_project(x2, phi.astype(jnp.float32)) *
            (inv * x2.shape[-1] ** -0.5)).T                 # [m, S]
    bias = bias.astype(jnp.float32)[:, None]
    alpha = alpha.astype(jnp.float32)
    pre = alpha[0] * proj[:n] + bias[:n]
    post = alpha[1] * proj[n:2 * n] + bias[n:2 * n]
    res = alpha[2] * proj[2 * n:] + bias[2 * n:]
    with jax.named_scope('sinkhorn'):
        m0 = jnp.exp(jnp.clip(res, clamp[0], clamp[1])).reshape(n, n, -1)
        h_res = project(m0, iters, hc_eps, auto_partitioned)
    return jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post), h_res


def _rows(x2, n):
    """[S, n C] -> the n rows [S, C], float32."""
    c = x2.shape[-1] // n
    return [x2[:, j * c:(j + 1) * c].astype(jnp.float32)
            for j in range(n)]


@register('hyper_connection_pre')
def hyper_connection_pre(ctx, ins, attrs):
    """X [B, T, n, C], Phi [n C, n^2 + 2 n], Alpha [3], Bias
    [n^2 + 2 n] -> U [B, T, C] (X's dtype), HPost [B, n, T], HRes
    [B, n, n, T] (HRes[b, i, j, t] weighs row j into row i), Err [1]:
    the largest |rowsum(H_res) - 1| or |colsum(H_res) - 1| over the
    tokens (no gradient).  The module's docstring has the equations."""
    from ..fluid import monitor
    monitor.add('mhc/calls', 1)
    x = ins['X'][0]
    b, t, n, c = x.shape
    monitor.set_gauge('mhc/streams', n)
    monitor.set_gauge('mhc/sinkhorn_iters', int(attrs['sinkhorn_iters']))
    x2 = x.reshape(b * t, n * c)
    with jax.named_scope('maps'):
        h_pre, h_post, h_res = maps(
            x2, ins['Phi'][0], ins['Alpha'][0], ins['Bias'][0], n,
            attrs.get('epsilon', 1e-6), int(attrs['sinkhorn_iters']),
            attrs.get('hc_eps', 1e-6),
            (attrs.get('clamp_min', -30.0), attrs.get('clamp_max', 30.0)),
            getattr(ctx, 'auto_partitioned', False))
        err = jax.lax.stop_gradient(jnp.maximum(
            jnp.max(jnp.abs(jnp.sum(h_res, 1) - 1.0)),
            jnp.max(jnp.abs(jnp.sum(h_res, 0) - 1.0))))
    with jax.named_scope('read_out'):
        u = sum(h_pre[j][:, None] * row
                for j, row in enumerate(_rows(x2, n)))

    def tokens_last(h):     # [..., B T] -> [B, ..., T]
        return jnp.moveaxis(h.reshape(h.shape[:-1] + (b, t)), -2, 0)

    return {'U': [u.astype(x.dtype).reshape(b, t, c)],
            'HPost': [tokens_last(h_post)], 'HRes': [tokens_last(h_res)],
            'Err': [err.reshape(1)]}


@register('hyper_connection_post')
def hyper_connection_post(ctx, ins, attrs):
    """X [B, T, n, C], Y [B, T, C], HPost [B, n, T], HRes [B, n, n, T]
    -> XOut [B, T, n, C] = H_res X + H_post^T y, in Y's dtype."""
    x, y = ins['X'][0], ins['Y'][0]
    b, t, n, c = x.shape

    def tokens_first(h):    # [B, ..., T] -> [..., B T, 1]
        h = jnp.moveaxis(h.astype(jnp.float32), 0, -2)
        return h.reshape(h.shape[:-2] + (b * t, 1))

    h_post, h_res = tokens_first(ins['HPost'][0]), \
        tokens_first(ins['HRes'][0])
    with jax.named_scope('write_back'):
        rows = _rows(x.reshape(b * t, n * c), n)
        yf = y.reshape(b * t, c).astype(jnp.float32)
        out = jnp.concatenate(
            [(sum(h_res[i, j] * rows[j] for j in range(n)) +
              h_post[i] * yf).astype(y.dtype) for i in range(n)], -1)
    return {'XOut': [out.reshape(b, t, n, c)]}
