"""Optimizer update op lowerings.

Reference: paddle/fluid/operators/optimizers/ (~5.2k LoC C++/CUDA, dense +
SelectedRows sparse paths).  Here updates are pure functions whose outputs
alias the parameter/accumulator vars in the program (ParamOut <- Param);
the executor's functional environment gives in-place semantics, and XLA
input-output donation reuses the buffers — the TPU analog of the
reference's in-place mutation.
"""

import numpy as np
import jax
import jax.numpy as jnp

from .registry import register


def _lr(ins):
    return ins['LearningRate'][0].reshape(())


@register('sgd')
def sgd(ctx, ins, attrs):
    p = ins['Param'][0]
    g = ins['Grad'][0]
    return {'ParamOut': [p - _lr(ins) * g.astype(p.dtype)]}


@register('momentum')
def momentum(ctx, ins, attrs):
    p = ins['Param'][0]
    g = ins['Grad'][0]
    v = ins['Velocity'][0]
    mu = attrs.get('mu', 0.9)
    lr = _lr(ins)
    v_out = mu * v + g
    if attrs.get('use_nesterov', False):
        p_out = p - (g + mu * v_out) * lr
    else:
        p_out = p - lr * v_out
    return {'ParamOut': [p_out], 'VelocityOut': [v_out]}


@register('lars_momentum')
def lars_momentum(ctx, ins, attrs):
    """LARS (reference operators/optimizers/lars_momentum_op.cc)."""
    p = ins['Param'][0]
    g = ins['Grad'][0]
    v = ins['Velocity'][0]
    mu = attrs.get('mu', 0.9)
    coeff = attrs.get('lars_coeff', 0.001)
    decay = attrs.get('lars_weight_decay', 0.0005)
    eps = attrs.get('epsilon', 0.0)
    lr = _lr(ins)
    pn = jnp.sqrt(jnp.sum(p.astype(jnp.float32) ** 2))
    gn = jnp.sqrt(jnp.sum(g.astype(jnp.float32) ** 2))
    local_lr = jnp.where(pn > 0,
                         lr * coeff * pn / (gn + decay * pn + eps), lr)
    v_out = mu * v + local_lr * (g + decay * p)
    return {'ParamOut': [p - v_out], 'VelocityOut': [v_out]}


@register('adam')
def adam(ctx, ins, attrs):
    p = ins['Param'][0]
    g = ins['Grad'][0].astype(jnp.float32)
    m1 = ins['Moment1'][0]
    m2 = ins['Moment2'][0]
    b1p = ins['Beta1Pow'][0].reshape(())
    b2p = ins['Beta2Pow'][0].reshape(())
    b1 = attrs.get('beta1', 0.9)
    b2 = attrs.get('beta2', 0.999)
    eps = attrs.get('epsilon', 1e-8)
    lr = _lr(ins)
    m1_out = b1 * m1 + (1 - b1) * g
    m2_out = b2 * m2 + (1 - b2) * g * g
    lr_t = lr * jnp.sqrt(1 - b2p * b2) / (1 - b1p * b1)
    p_out = p - (lr_t * m1_out / (jnp.sqrt(m2_out) + eps)).astype(p.dtype)
    return {'ParamOut': [p_out], 'Moment1Out': [m1_out],
            'Moment2Out': [m2_out],
            'Beta1PowOut': [(b1p * b1).reshape(ins['Beta1Pow'][0].shape)],
            'Beta2PowOut': [(b2p * b2).reshape(ins['Beta2Pow'][0].shape)]}


@register('adamw')
def adamw(ctx, ins, attrs):
    coeff = attrs.get('coeff', 0.01)
    out = adam(ctx, ins, attrs)
    p = ins['Param'][0]
    lr = _lr(ins)
    out['ParamOut'] = [out['ParamOut'][0] - lr * coeff * p]
    return out


@register('adagrad')
def adagrad(ctx, ins, attrs):
    p = ins['Param'][0]
    g = ins['Grad'][0]
    mom = ins['Moment'][0]
    eps = attrs.get('epsilon', 1e-6)
    m_out = mom + g * g
    p_out = p - _lr(ins) * g / (jnp.sqrt(m_out) + eps)
    return {'ParamOut': [p_out], 'MomentOut': [m_out]}


@register('adamax')
def adamax(ctx, ins, attrs):
    p = ins['Param'][0]
    g = ins['Grad'][0]
    m = ins['Moment'][0]
    inf_norm = ins['InfNorm'][0]
    b1p = ins['Beta1Pow'][0].reshape(())
    b1 = attrs.get('beta1', 0.9)
    b2 = attrs.get('beta2', 0.999)
    eps = attrs.get('epsilon', 1e-8)
    m_out = b1 * m + (1 - b1) * g
    n_out = jnp.maximum(b2 * inf_norm, jnp.abs(g) + eps)
    lr_t = _lr(ins) / (1 - b1p)
    return {'ParamOut': [p - lr_t * m_out / n_out],
            'MomentOut': [m_out], 'InfNormOut': [n_out]}


@register('adadelta')
def adadelta(ctx, ins, attrs):
    p = ins['Param'][0]
    g = ins['Grad'][0]
    avg_sq_g = ins['AvgSquaredGrad'][0]
    avg_sq_u = ins['AvgSquaredUpdate'][0]
    rho = attrs.get('rho', 0.95)
    eps = attrs.get('epsilon', 1e-6)
    g2 = rho * avg_sq_g + (1 - rho) * g * g
    upd = -jnp.sqrt((avg_sq_u + eps) / (g2 + eps)) * g
    u2 = rho * avg_sq_u + (1 - rho) * upd * upd
    return {'ParamOut': [p + upd], 'AvgSquaredGradOut': [g2],
            'AvgSquaredUpdateOut': [u2]}


@register('rmsprop')
def rmsprop(ctx, ins, attrs):
    p = ins['Param'][0]
    g = ins['Grad'][0]
    ms = ins['MeanSquare'][0]
    mom = ins['Moment'][0]
    rho = attrs.get('decay', 0.95)
    eps = attrs.get('epsilon', 1e-6)
    mu = attrs.get('momentum', 0.0)
    lr = _lr(ins)
    ms_out = rho * ms + (1 - rho) * g * g
    if attrs.get('centered', False):
        mg = ins['MeanGrad'][0]
        mg_out = rho * mg + (1 - rho) * g
        mom_out = mu * mom + lr * g / jnp.sqrt(ms_out - mg_out * mg_out
                                               + eps)
        return {'ParamOut': [p - mom_out], 'MomentOut': [mom_out],
                'MeanSquareOut': [ms_out], 'MeanGradOut': [mg_out]}
    mom_out = mu * mom + lr * g / jnp.sqrt(ms_out + eps)
    return {'ParamOut': [p - mom_out], 'MomentOut': [mom_out],
            'MeanSquareOut': [ms_out]}


@register('ftrl')
def ftrl(ctx, ins, attrs):
    p = ins['Param'][0]
    g = ins['Grad'][0]
    sq = ins['SquaredAccumulator'][0]
    lin = ins['LinearAccumulator'][0]
    l1 = attrs.get('l1', 0.0)
    l2 = attrs.get('l2', 0.0)
    lr_power = attrs.get('lr_power', -0.5)
    lr = _lr(ins)
    new_sq = sq + g * g
    if lr_power == -0.5:
        sigma = (jnp.sqrt(new_sq) - jnp.sqrt(sq)) / lr
    else:
        sigma = (new_sq ** -lr_power - sq ** -lr_power) / lr
    lin_out = lin + g - sigma * p
    if lr_power == -0.5:
        denom = jnp.sqrt(new_sq) / lr + 2 * l2
    else:
        denom = new_sq ** -lr_power / lr + 2 * l2
    pre = jnp.clip(lin_out, -l1, l1) - lin_out
    p_out = pre / denom
    return {'ParamOut': [p_out], 'SquaredAccumOut': [new_sq],
            'LinearAccumOut': [lin_out]}


@register('lamb')
def lamb(ctx, ins, attrs):
    """LAMB (reference operators/optimizers/lamb_op.cc)."""
    p = ins['Param'][0]
    g = ins['Grad'][0].astype(jnp.float32)
    m1 = ins['Moment1'][0]
    m2 = ins['Moment2'][0]
    b1p = ins['Beta1Pow'][0].reshape(())
    b2p = ins['Beta2Pow'][0].reshape(())
    b1 = attrs.get('beta1', 0.9)
    b2 = attrs.get('beta2', 0.999)
    eps = attrs.get('epsilon', 1e-6)
    wd = attrs.get('weight_decay', 0.01)
    lr = _lr(ins)
    m1_out = b1 * m1 + (1 - b1) * g
    m2_out = b2 * m2 + (1 - b2) * g * g
    mhat = m1_out / (1 - b1p * b1)
    vhat = m2_out / (1 - b2p * b2)
    r = mhat / (jnp.sqrt(vhat) + eps) + wd * p.astype(jnp.float32)
    pn = jnp.sqrt(jnp.sum(p.astype(jnp.float32) ** 2))
    rn = jnp.sqrt(jnp.sum(r ** 2))
    trust = jnp.where((pn > 0) & (rn > 0), pn / rn, 1.0)
    p_out = p - (lr * trust * r).astype(p.dtype)
    return {'ParamOut': [p_out], 'Moment1Out': [m1_out],
            'Moment2Out': [m2_out],
            'Beta1PowOut': [(b1p * b1).reshape(ins['Beta1Pow'][0].shape)],
            'Beta2PowOut': [(b2p * b2).reshape(ins['Beta2Pow'][0].shape)]}


@register('dpsgd')
def dpsgd(ctx, ins, attrs):
    p = ins['Param'][0]
    g = ins['Grad'][0]
    clip = attrs.get('clip', 10.0)
    sigma = attrs.get('sigma', 1.0)
    gn = jnp.sqrt(jnp.sum(g * g))
    g = g / jnp.maximum(1.0, gn / clip)
    noise = sigma * clip * jax.random.normal(ctx.rng(), g.shape, g.dtype)
    return {'ParamOut': [p - _lr(ins) * (g + noise)]}


@register('proximal_gd')
def proximal_gd(ctx, ins, attrs):
    p = ins['Param'][0]
    g = ins['Grad'][0]
    l1 = attrs.get('l1', 0.0)
    l2 = attrs.get('l2', 0.0)
    lr = _lr(ins)
    prox = p - lr * g
    if l1 > 0:
        p_out = (jnp.sign(prox) *
                 jnp.maximum(jnp.abs(prox) - lr * l1, 0.0) /
                 (1.0 + lr * l2))
    else:
        p_out = prox / (1.0 + lr * l2)
    return {'ParamOut': [p_out]}


@register('dgc')
def dgc(ctx, ins, attrs):
    """Deep Gradient Compression sparsification with momentum correction
    and local error feedback (reference operators/dgc_op.h:39,168).
    u = m*u + g; v = v + u; keep top-k |v| as the communicated grad,
    retain the rest locally.  On ICI the bandwidth win is moot, but the
    semantics (and convergence behavior) are preserved for parity."""
    g = ins['Grad'][0]
    u = ins['U'][0]
    v = ins['V'][0]
    m = attrs.get('m', 0.9)
    ratio = attrs.get('sparsity_ratio', 0.999)
    n = int(np.prod(g.shape))
    k = max(1, int(n * (1.0 - ratio)))
    u_new = m * u + g
    v_new = v + u_new
    flat = jnp.abs(v_new.reshape(-1))
    thr = jax.lax.top_k(flat, k)[0][-1]
    mask = (jnp.abs(v_new) >= thr).astype(g.dtype)
    encoded = v_new * mask
    return {'EncodeGrad': [encoded],
            'UOut': [u_new * (1 - mask)],
            'VOut': [v_new * (1 - mask)],
            'GradOut': [encoded]}
