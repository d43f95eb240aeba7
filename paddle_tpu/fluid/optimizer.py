"""Optimizers: append update ops to the program.

Reference: python/paddle/fluid/optimizer.py — Optimizer.minimize(:690) =
append_backward + apply_gradients(:575); per-optimizer _append_optimize_op
(:293).  The update ops lower to pure XLA functions whose outputs alias the
parameter vars (ops/optimizer_ops.py), giving donated-buffer in-place
updates on TPU.
"""

import numpy as np

from . import core
from . import framework
from . import unique_name
from .backward import append_backward
from .framework import Variable, default_main_program, \
    default_startup_program
from .initializer import Constant
from .layer_helper import LayerHelper


class Optimizer(object):
    def __init__(self, learning_rate, regularization=None, name=None,
                 grad_clip=None):
        self._learning_rate = learning_rate
        self.regularization = regularization
        self._grad_clip = grad_clip
        self._name = name
        self._accumulators = {}  # acc_name -> {param_name: var}
        self._learning_rate_map = {}
        self.helper = None
        self.type = getattr(self, 'type', 'optimizer')

    # -- learning rate ----------------------------------------------------
    def _create_global_learning_rate(self):
        program = default_main_program()
        lr = self._learning_rate_map.get(program)
        if lr is not None:
            return
        if isinstance(self._learning_rate, Variable):
            self._learning_rate_map[program] = self._learning_rate
            return
        name = unique_name.generate('learning_rate')
        lr_var = program.global_block().create_var(
            name=name, shape=(1,), dtype='float32', persistable=True)
        lr_var.stop_gradient = True
        sb = default_startup_program().global_block()
        sb.create_var(name=name, shape=(1,), dtype='float32',
                      persistable=True)
        sb.append_op('fill_constant', outputs={'Out': name},
                     attrs={'shape': [1], 'dtype': 'float32',
                            'value': float(self._learning_rate)})
        self._learning_rate_map[program] = lr_var

    def _global_learning_rate(self, program=None):
        program = program or default_main_program()
        return self._learning_rate_map.get(program)

    def _create_param_lr(self, param_and_grad):
        param = param_and_grad[0]
        base_lr = self._global_learning_rate()
        param_lr = getattr(param, 'optimize_attr',
                           {'learning_rate': 1.0}).get('learning_rate', 1.0)
        if param_lr == 1.0:
            return base_lr
        from .layers import ops as _ops
        return _ops.scale(base_lr, scale=float(param_lr))

    # -- accumulators ------------------------------------------------------
    def _add_accumulator(self, name, param, fill_value=0.0, shape=None,
                         dtype=None):
        if name in self._accumulators and \
                param.name in self._accumulators[name]:
            return self._accumulators[name][param.name]
        shape = list(shape if shape is not None else param.shape)
        dtype = dtype or param.dtype
        var_name = unique_name.generate(param.name + '_' + name)
        block = default_main_program().global_block()
        var = block.create_var(name=var_name, shape=tuple(shape),
                               dtype=dtype, persistable=True)
        var.stop_gradient = True
        sb = default_startup_program().global_block()
        sb.create_var(name=var_name, shape=tuple(shape), dtype=dtype,
                      persistable=True)
        sb.append_op('fill_constant', outputs={'Out': var_name},
                     attrs={'shape': shape, 'dtype': dtype,
                            'value': float(fill_value)})
        self._accumulators.setdefault(name, {})[param.name] = var
        return var

    def _get_accumulator(self, name, param):
        return self._accumulators[name][param.name]

    # -- pipeline ----------------------------------------------------------
    def _create_accumulators(self, block, parameters):
        pass

    def _append_optimize_op(self, block, param_and_grad):
        raise NotImplementedError

    def _finish_update(self, block, params_grads):
        pass

    def backward(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None, callbacks=None):
        return append_backward(loss, parameter_list, no_grad_set, callbacks)

    def apply_gradients(self, params_grads):
        """Reference: optimizer.py:575."""
        with default_main_program()._role_guard('optimize'):
            return self._apply_gradients_impl(params_grads)

    def _apply_gradients_impl(self, params_grads):
        from .clip import append_gradient_clip_ops
        from .regularizer import append_regularization_ops
        params_grads = sorted(params_grads, key=lambda x: x[0].name)
        if self._grad_clip is not None:
            params_grads = self._grad_clip(params_grads)
        else:
            params_grads = append_gradient_clip_ops(params_grads)
        params_grads = append_regularization_ops(params_grads,
                                                 self.regularization)
        block = default_main_program().global_block()
        self._create_global_learning_rate()
        self._create_accumulators(block, [p for p, g in params_grads])
        optimize_ops = []
        for pg in params_grads:
            if pg[1] is None:
                continue
            optimize_ops.append(self._append_optimize_op(block, pg))
        self._finish_update(block, params_grads)
        return optimize_ops

    def apply_optimize(self, loss, startup_program, params_grads):
        return self.apply_gradients(params_grads)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None, grad_clip=None):
        """Reference: optimizer.py:690."""
        if grad_clip is not None:
            self._grad_clip = grad_clip
        if framework.in_dygraph_mode():
            return self._dygraph_minimize(loss, parameter_list or [])
        params_grads = self.backward(loss, startup_program, parameter_list,
                                     no_grad_set)
        optimize_ops = self.apply_gradients(params_grads)
        return optimize_ops, params_grads

    def _dygraph_minimize(self, loss, parameter_list):
        """Eager update path: build (once) a scratch program containing
        only the update ops via the SAME _append_optimize_op used by the
        static path, then run it jitted each step with param/grad values
        fed in.  Accumulators persist in a private scope.  Reference
        analog: dygraph reuses _append_optimize_op through the tracer
        (optimizer.py dygraph branch)."""
        from .executor import Executor
        params = [p for p in parameter_list
                  if getattr(p, 'trainable', True) and p.grad is not None]
        if not params:
            return [], []
        key = tuple(id(p) for p in params)
        if getattr(self, '_eager_key', None) != key:
            self._eager_key = key
            self._eager_scope = core.Scope()
            self._accumulators = {}
            self._learning_rate_map = {}
            main, startup = framework.Program(), framework.Program()
            with framework.program_guard(main, startup):
                block = main.global_block()
                pg = []
                for p in params:
                    pv = block.create_parameter(
                        shape=list(p.shape), dtype=p.dtype, name=p.name)
                    gv = block.create_var(
                        name=p.name + '@GRAD', shape=tuple(p.shape),
                        dtype=p.dtype)
                    pg.append((pv, gv))
                self._create_global_learning_rate()
                self._create_accumulators(block, [x for x, _ in pg])
                for item in pg:
                    self._append_optimize_op(block, item)
                self._finish_update(block, pg)
            self._eager_main = main
            self._eager_exe = Executor(core.XLAPlace(0))
            with core.scope_guard(self._eager_scope):
                self._eager_exe.run(startup)
        feed = {}
        for p in params:
            feed[p.name] = p.value
            feed[p.name + '@GRAD'] = p.grad
        with core.scope_guard(self._eager_scope):
            self._eager_exe.run(self._eager_main, feed=feed,
                                fetch_list=[])
            for p in params:
                p.value = core.as_array(
                    self._eager_scope.find_var(p.name))
        return [], []


class SGDOptimizer(Optimizer):
    type = 'sgd'

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        return block.append_op(
            'sgd',
            inputs={'Param': p, 'Grad': g,
                    'LearningRate': self._create_param_lr(param_and_grad)},
            outputs={'ParamOut': p}, infer_shape=False)


class MomentumOptimizer(Optimizer):
    type = 'momentum'

    def __init__(self, learning_rate, momentum, use_nesterov=False,
                 **kwargs):
        super(MomentumOptimizer, self).__init__(learning_rate, **kwargs)
        self._momentum = momentum
        self._use_nesterov = use_nesterov

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator('velocity', p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        velocity = self._get_accumulator('velocity', p)
        return block.append_op(
            'momentum',
            inputs={'Param': p, 'Grad': g, 'Velocity': velocity,
                    'LearningRate': self._create_param_lr(param_and_grad)},
            outputs={'ParamOut': p, 'VelocityOut': velocity},
            attrs={'mu': self._momentum,
                   'use_nesterov': self._use_nesterov},
            infer_shape=False)


class LarsMomentumOptimizer(Optimizer):
    type = 'lars_momentum'

    def __init__(self, learning_rate, momentum, lars_coeff=0.001,
                 lars_weight_decay=0.0005, **kwargs):
        super(LarsMomentumOptimizer, self).__init__(learning_rate, **kwargs)
        self._momentum = momentum
        self._lars_coeff = lars_coeff
        self._lars_weight_decay = lars_weight_decay

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator('velocity', p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        velocity = self._get_accumulator('velocity', p)
        return block.append_op(
            'lars_momentum',
            inputs={'Param': p, 'Grad': g, 'Velocity': velocity,
                    'LearningRate': self._create_param_lr(param_and_grad)},
            outputs={'ParamOut': p, 'VelocityOut': velocity},
            attrs={'mu': self._momentum, 'lars_coeff': self._lars_coeff,
                   'lars_weight_decay': self._lars_weight_decay},
            infer_shape=False)


class AdamOptimizer(Optimizer):
    type = 'adam'

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, lazy_mode=False, **kwargs):
        super(AdamOptimizer, self).__init__(learning_rate, **kwargs)
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator('moment1', p)
            self._add_accumulator('moment2', p)
        if parameters:
            # ONE shared beta-pow pair for the whole optimizer: every
            # dense param's pow follows the identical beta^t
            # trajectory, so the reference's per-param copies (an
            # artifact of its per-op design) only inflate the jit
            # boundary — for Transformer-base they alone added ~400
            # state arrays per step.  Exact math: each pow is read by
            # all adam ops at step t and advanced ONCE in
            # _finish_update.
            self._shared_pow_param = parameters[0]
            self._add_accumulator('beta1_pow_acc', parameters[0],
                                  fill_value=1.0, shape=[1])
            self._add_accumulator('beta2_pow_acc', parameters[0],
                                  fill_value=1.0, shape=[1])

    def _get_accumulator(self, name, param):
        if name in ('beta1_pow_acc', 'beta2_pow_acc'):
            param = self._shared_pow_param
        return super(AdamOptimizer, self)._get_accumulator(name, param)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        m1 = self._get_accumulator('moment1', p)
        m2 = self._get_accumulator('moment2', p)
        b1p = self._get_accumulator('beta1_pow_acc', p)
        b2p = self._get_accumulator('beta2_pow_acc', p)
        return block.append_op(
            'adam',
            inputs={'Param': p, 'Grad': g, 'Moment1': m1, 'Moment2': m2,
                    'Beta1Pow': b1p, 'Beta2Pow': b2p,
                    'LearningRate': self._create_param_lr(param_and_grad)},
            outputs={'ParamOut': p, 'Moment1Out': m1, 'Moment2Out': m2},
            attrs={'beta1': self._beta1, 'beta2': self._beta2,
                   'epsilon': self._epsilon},
            infer_shape=False)

    def _finish_update(self, block, params_grads):
        if not params_grads:
            return
        b1p = self._get_accumulator('beta1_pow_acc',
                                    params_grads[0][0])
        b2p = self._get_accumulator('beta2_pow_acc',
                                    params_grads[0][0])
        for acc, beta in ((b1p, self._beta1), (b2p, self._beta2)):
            # __optimizer_finish__ lets program rewrites that strip the
            # per-param optimize ops (async-PS transpiler) drop these
            # paired finish ops too, instead of leaving orphan updates
            block.append_op('scale', inputs={'X': acc},
                            outputs={'Out': acc},
                            attrs={'scale': beta,
                                   '__optimizer_finish__': True},
                            infer_shape=False)


class AdamWOptimizer(AdamOptimizer):
    type = 'adamw'

    def __init__(self, learning_rate=0.001, weight_decay=0.01, **kwargs):
        super(AdamWOptimizer, self).__init__(learning_rate, **kwargs)
        self._coeff = weight_decay

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        m1 = self._get_accumulator('moment1', p)
        m2 = self._get_accumulator('moment2', p)
        b1p = self._get_accumulator('beta1_pow_acc', p)
        b2p = self._get_accumulator('beta2_pow_acc', p)
        return block.append_op(
            'adamw',
            inputs={'Param': p, 'Grad': g, 'Moment1': m1, 'Moment2': m2,
                    'Beta1Pow': b1p, 'Beta2Pow': b2p,
                    'LearningRate': self._create_param_lr(param_and_grad)},
            outputs={'ParamOut': p, 'Moment1Out': m1, 'Moment2Out': m2},
            attrs={'beta1': self._beta1, 'beta2': self._beta2,
                   'epsilon': self._epsilon, 'coeff': self._coeff},
            infer_shape=False)


class AdagradOptimizer(Optimizer):
    type = 'adagrad'

    def __init__(self, learning_rate, epsilon=1e-6,
                 initial_accumulator_value=0.0, **kwargs):
        super(AdagradOptimizer, self).__init__(learning_rate, **kwargs)
        self._epsilon = epsilon
        self._initial = initial_accumulator_value

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator('moment', p, fill_value=self._initial)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        moment = self._get_accumulator('moment', p)
        return block.append_op(
            'adagrad',
            inputs={'Param': p, 'Grad': g, 'Moment': moment,
                    'LearningRate': self._create_param_lr(param_and_grad)},
            outputs={'ParamOut': p, 'MomentOut': moment},
            attrs={'epsilon': self._epsilon}, infer_shape=False)


class AdamaxOptimizer(Optimizer):
    type = 'adamax'

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, **kwargs):
        super(AdamaxOptimizer, self).__init__(learning_rate, **kwargs)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator('moment', p)
            self._add_accumulator('inf_norm', p)
            self._add_accumulator('beta1_pow_acc', p,
                                  fill_value=self._beta1, shape=[1])

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        return block.append_op(
            'adamax',
            inputs={'Param': p, 'Grad': g,
                    'Moment': self._get_accumulator('moment', p),
                    'InfNorm': self._get_accumulator('inf_norm', p),
                    'Beta1Pow': self._get_accumulator('beta1_pow_acc', p),
                    'LearningRate': self._create_param_lr(param_and_grad)},
            outputs={'ParamOut': p,
                     'MomentOut': self._get_accumulator('moment', p),
                     'InfNormOut': self._get_accumulator('inf_norm', p)},
            attrs={'beta1': self._beta1, 'beta2': self._beta2,
                   'epsilon': self._epsilon}, infer_shape=False)

    def _finish_update(self, block, params_grads):
        for p, g in params_grads:
            b1p = self._get_accumulator('beta1_pow_acc', p)
            block.append_op('scale', inputs={'X': b1p},
                            outputs={'Out': b1p},
                            attrs={'scale': self._beta1},
                            infer_shape=False)


class AdadeltaOptimizer(Optimizer):
    type = 'adadelta'

    def __init__(self, learning_rate, epsilon=1e-6, rho=0.95, **kwargs):
        super(AdadeltaOptimizer, self).__init__(learning_rate, **kwargs)
        self._epsilon, self._rho = epsilon, rho

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator('avg_squared_grad', p)
            self._add_accumulator('avg_squared_update', p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        asg = self._get_accumulator('avg_squared_grad', p)
        asu = self._get_accumulator('avg_squared_update', p)
        return block.append_op(
            'adadelta',
            inputs={'Param': p, 'Grad': g, 'AvgSquaredGrad': asg,
                    'AvgSquaredUpdate': asu},
            outputs={'ParamOut': p, 'AvgSquaredGradOut': asg,
                     'AvgSquaredUpdateOut': asu},
            attrs={'epsilon': self._epsilon, 'rho': self._rho},
            infer_shape=False)


class RMSPropOptimizer(Optimizer):
    type = 'rmsprop'

    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, **kwargs):
        super(RMSPropOptimizer, self).__init__(learning_rate, **kwargs)
        self._rho, self._epsilon = rho, epsilon
        self._momentum, self._centered = momentum, centered

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator('momentum', p)
            self._add_accumulator('mean_square', p)
            self._add_accumulator('mean_grad', p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        mom = self._get_accumulator('momentum', p)
        ms = self._get_accumulator('mean_square', p)
        mg = self._get_accumulator('mean_grad', p)
        return block.append_op(
            'rmsprop',
            inputs={'Param': p, 'Grad': g, 'Moment': mom,
                    'MeanSquare': ms, 'MeanGrad': mg,
                    'LearningRate': self._create_param_lr(param_and_grad)},
            outputs={'ParamOut': p, 'MomentOut': mom, 'MeanSquareOut': ms,
                     'MeanGradOut': mg},
            attrs={'decay': self._rho, 'epsilon': self._epsilon,
                   'momentum': self._momentum, 'centered': self._centered},
            infer_shape=False)


class FtrlOptimizer(Optimizer):
    type = 'ftrl'

    def __init__(self, learning_rate, l1=0.0, l2=0.0, lr_power=-0.5,
                 **kwargs):
        super(FtrlOptimizer, self).__init__(learning_rate, **kwargs)
        self._l1, self._l2, self._lr_power = l1, l2, lr_power

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator('squared', p)
            self._add_accumulator('linear', p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        sq = self._get_accumulator('squared', p)
        lin = self._get_accumulator('linear', p)
        return block.append_op(
            'ftrl',
            inputs={'Param': p, 'Grad': g, 'SquaredAccumulator': sq,
                    'LinearAccumulator': lin,
                    'LearningRate': self._create_param_lr(param_and_grad)},
            outputs={'ParamOut': p, 'SquaredAccumOut': sq,
                     'LinearAccumOut': lin},
            attrs={'l1': self._l1, 'l2': self._l2,
                   'lr_power': self._lr_power}, infer_shape=False)


class LambOptimizer(AdamOptimizer):
    type = 'lamb'

    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01,
                 beta1=0.9, beta2=0.999, epsilon=1e-6,
                 exclude_from_weight_decay_fn=None, **kwargs):
        super(LambOptimizer, self).__init__(learning_rate, beta1=beta1,
                                            beta2=beta2, epsilon=epsilon,
                                            **kwargs)
        self._weight_decay = lamb_weight_decay
        self._exclude_fn = exclude_from_weight_decay_fn

    # Lamb keeps PER-PARAM beta pows (its op advances them in-place via
    # Beta1PowOut, so sharing Adam's single pair would advance it once
    # per param per step — N+1 total with the inherited finish hook)
    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator('moment1', p)
            self._add_accumulator('moment2', p)
            self._add_accumulator('beta1_pow_acc', p, fill_value=1.0,
                                  shape=[1])
            self._add_accumulator('beta2_pow_acc', p, fill_value=1.0,
                                  shape=[1])

    def _get_accumulator(self, name, param):
        return Optimizer._get_accumulator(self, name, param)

    def _finish_update(self, block, params_grads):
        pass

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        wd = self._weight_decay
        if self._exclude_fn is not None and self._exclude_fn(p):
            wd = 0.0
        m1 = self._get_accumulator('moment1', p)
        m2 = self._get_accumulator('moment2', p)
        b1p = self._get_accumulator('beta1_pow_acc', p)
        b2p = self._get_accumulator('beta2_pow_acc', p)
        return block.append_op(
            'lamb',
            inputs={'Param': p, 'Grad': g, 'Moment1': m1, 'Moment2': m2,
                    'Beta1Pow': b1p, 'Beta2Pow': b2p,
                    'LearningRate': self._create_param_lr(param_and_grad)},
            outputs={'ParamOut': p, 'Moment1Out': m1, 'Moment2Out': m2,
                     'Beta1PowOut': b1p, 'Beta2PowOut': b2p},
            attrs={'beta1': self._beta1, 'beta2': self._beta2,
                   'epsilon': self._epsilon, 'weight_decay': wd},
            infer_shape=False)


class DpsgdOptimizer(Optimizer):
    type = 'dpsgd'

    def __init__(self, learning_rate, clip=10.0, batch_size=16.0,
                 sigma=1.0, **kwargs):
        super(DpsgdOptimizer, self).__init__(learning_rate, **kwargs)
        self._clip, self._sigma = clip, sigma

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        return block.append_op(
            'dpsgd',
            inputs={'Param': p, 'Grad': g,
                    'LearningRate': self._create_param_lr(param_and_grad)},
            outputs={'ParamOut': p},
            attrs={'clip': self._clip, 'sigma': self._sigma},
            infer_shape=False)


class RecomputeOptimizer(Optimizer):
    """Activation checkpointing. Reference: optimizer.py:3611 +
    backward.py:618 (_append_backward_ops_with_checkpoints_).

    On TPU the vjp-grad design already recomputes forward inside each grad
    op; whether XLA CSE dedupes (memory-heavy) or rematerializes is
    controlled by wrapping checkpoint spans in jax.checkpoint at segment
    lowering time.
    """

    def __init__(self, optimizer):
        self._optimizer = optimizer
        self._checkpoints = None

    def _set_checkpoints(self, checkpoints):
        self._checkpoints = checkpoints

    def backward(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None, callbacks=None):
        return append_backward(loss, parameter_list, no_grad_set,
                               callbacks, checkpoints=self._checkpoints)

    def apply_gradients(self, params_grads):
        return self._optimizer.apply_gradients(params_grads)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        params_grads = self.backward(loss, startup_program, parameter_list,
                                     no_grad_set)
        return self.apply_gradients(params_grads), params_grads


class ModelAverage(object):
    """Running parameter average for eval (reference optimizer.py:2759).

    Maintains sum accumulators in-graph; apply()/restore() swap averaged
    params in and out of the scope on the host."""

    def __init__(self, average_window_rate=0.15,
                 min_average_window=10000, max_average_window=10000,
                 **kwargs):
        self._avg = {}
        block = default_main_program().global_block()
        sb = default_startup_program().global_block()
        self._params = [p for p in block.all_parameters()
                        if getattr(p, 'trainable', True)]
        self._count_name = unique_name.generate('ma_count')
        block.create_var(name=self._count_name, shape=(1,),
                         dtype='float32', persistable=True)
        sb.create_var(name=self._count_name, shape=(1,),
                      dtype='float32', persistable=True)
        sb.append_op('fill_constant', outputs={'Out': self._count_name},
                     attrs={'shape': [1], 'dtype': 'float32',
                            'value': 0.0})
        with default_main_program()._role_guard('optimize'):
            block.append_op('increment', inputs={'X': self._count_name},
                            outputs={'Out': self._count_name},
                            attrs={'step': 1.0}, infer_shape=False)
            for p in self._params:
                name = unique_name.generate(p.name + '_ma_sum')
                block.create_var(name=name, shape=p.shape, dtype=p.dtype,
                                 persistable=True)
                sb.create_var(name=name, shape=p.shape, dtype=p.dtype,
                              persistable=True)
                sb.append_op('fill_constant', outputs={'Out': name},
                             attrs={'shape': list(p.shape),
                                    'dtype': p.dtype, 'value': 0.0})
                block.append_op('elementwise_add',
                                inputs={'X': name, 'Y': p},
                                outputs={'Out': name}, attrs={'axis': -1},
                                infer_shape=False)
                self._avg[p.name] = name
        self._backup = {}

    def apply(self, executor=None, need_restore=True):
        import contextlib

        @contextlib.contextmanager
        def guard():
            scope = core.global_scope()
            count = float(np.asarray(core.as_array(
                scope.find_var(self._count_name))).ravel()[0])
            count = max(count, 1.0)
            self._backup = {}
            for p in self._params:
                self._backup[p.name] = core.as_array(
                    scope.find_var(p.name))
                avg = core.as_array(scope.find_var(self._avg[p.name]))
                scope.set_var(p.name, avg / count)
            try:
                yield
            finally:
                if need_restore:
                    self.restore()
        return guard()

    def restore(self, executor=None):
        scope = core.global_scope()
        for name, val in self._backup.items():
            scope.set_var(name, val)
        self._backup = {}


class PipelineOptimizer(object):
    """Pipeline-parallel optimizer API (reference optimizer.py:3311 +
    PipelineTrainer/SectionWorker, framework/trainer.h:114).

    TPU-native: the SectionWorker thread/queue machinery is replaced by
    the shard_map GPipe schedule in parallel/pipeline.py (activations
    hop stages via ppermute, autodiff reverses the ring).  This wrapper
    keeps the fluid API for single-stage programs and points multi-stage
    users at pipeline_apply; full program-cutting onto the 'pp' axis is
    the planned follow-up.
    """

    def __init__(self, optimizer, cut_list=None, place_list=None,
                 concurrency_list=None, queue_size=30, sync_steps=1,
                 start_cpu_core_id=0):
        self._optimizer = optimizer
        self._cut_list = cut_list or []

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        """With cut_list: validates the cut and records the pipeline
        plan on the program (program._pipeline_plan), then appends the
        standard backward+update ops so exe.run keeps exact
        single-submission semantics.  The staged GPipe execution path
        over the plan is
        paddle_tpu.parallel.program_pipeline.build_train_step
        (parity-tested in tests/test_program_pipeline.py)."""
        if self._cut_list:
            from ..parallel.program_pipeline import split_program_stages
            program = loss.block.program
            # preserve grouping: each cut_list entry is ONE stage
            # boundary (possibly multiple vars — multi-slot scope queue)
            cut_groups = [
                [v.name if hasattr(v, 'name') else v for v in
                 (cuts if isinstance(cuts, (list, tuple)) else [cuts])]
                for cuts in self._cut_list]
            cut_names = [n for grp in cut_groups for n in grp]
            feeds = [v.name for v in program.global_block().vars.values()
                     if getattr(v, 'is_data', False)]
            # the pipeline input is the data var the FIRST stage reads
            # (ops up to the first cut producer), not merely the first
            # declared feed (labels may be declared first)
            first_cut = cut_names[0]
            stage0_reads = set()
            for op in program.global_block().ops:
                stage0_reads.update(op.input_arg_names)
                if first_cut in op.output_arg_names:
                    break
            candidates = [n for n in feeds if n in stage0_reads]
            if len(candidates) != 1:
                raise ValueError(
                    'PipelineOptimizer(cut_list=...) needs exactly one '
                    'layers.data input feeding the first stage; found '
                    '%r — restructure the feeds or use '
                    'parallel.program_pipeline.build_train_step with '
                    'an explicit input_name' % (candidates,))
            input_name = candidates[0]
            # validate the cut now so bad cut_lists fail at build
            split_program_stages(program, input_name, cut_groups,
                                 loss.name, allow_data_reads=True)
            program._pipeline_plan = {
                'input': input_name, 'cuts': cut_names,
                'cut_groups': cut_groups, 'output': loss.name}
        return self._optimizer.minimize(loss, startup_program,
                                        parameter_list, no_grad_set)


class ExponentialMovingAverage(object):
    """Reference: optimizer.py:3063."""

    def __init__(self, decay=0.999, thres_steps=None, name=None):
        self._decay = decay
        self._name = name or 'ema'
        self._ema_vars = {}

    def update(self):
        block = default_main_program().global_block()
        for p in block.all_parameters():
            if not p.trainable:
                continue
            name = p.name + '.' + self._name
            ema = block.create_var(name=name, shape=p.shape, dtype=p.dtype,
                                   persistable=True)
            ema.stop_gradient = True
            sb = default_startup_program().global_block()
            sb.create_var(name=name, shape=p.shape, dtype=p.dtype,
                          persistable=True)
            sb.append_op('fill_constant', outputs={'Out': name},
                         attrs={'shape': list(p.shape), 'dtype': p.dtype,
                                'value': 0.0})
            self._ema_vars[p.name] = ema
            # ema = decay*ema + (1-decay)*p
            tmp = block.create_var(
                name=unique_name.generate(name + '_tmp'),
                shape=p.shape, dtype=p.dtype)
            block.append_op('scale', inputs={'X': ema},
                            outputs={'Out': tmp},
                            attrs={'scale': self._decay})
            block.append_op('scale', inputs={'X': p},
                            outputs={'Out': name},
                            attrs={'scale': 1 - self._decay},
                            infer_shape=False)
            block.append_op('elementwise_add',
                            inputs={'X': tmp, 'Y': name},
                            outputs={'Out': name}, infer_shape=False)


# Short aliases matching fluid.optimizer namespace

class DecayedAdagradOptimizer(Optimizer):
    """Reference optimizer.py DecayedAdagradOptimizer over
    operators/optimizers/decayed_adagrad_op.cc."""
    type = 'decayed_adagrad'

    def __init__(self, learning_rate, decay=0.95, epsilon=1e-6,
                 **kwargs):
        super(DecayedAdagradOptimizer, self).__init__(learning_rate,
                                                      **kwargs)
        self._decay = decay
        self._epsilon = epsilon

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator('moment', p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        moment = self._get_accumulator('moment', p)
        return block.append_op(
            'decayed_adagrad',
            inputs={'Param': p, 'Grad': g, 'Moment': moment,
                    'LearningRate': self._create_param_lr(param_and_grad)},
            outputs={'ParamOut': p, 'MomentOut': moment},
            attrs={'decay': self._decay, 'epsilon': self._epsilon},
            infer_shape=False)


class LookaheadOptimizer(object):
    """Reference optimizer.py LookaheadOptimizer: fast weights step
    every iteration; every k steps slow <- slow + alpha*(fast-slow),
    fast <- slow.  In-graph rendering: a step counter + where() select
    (the reference uses a Switch block)."""

    def __init__(self, inner_optimizer, alpha=0.5, k=5):
        assert inner_optimizer is not None
        assert 0.0 <= alpha <= 1.0
        assert isinstance(k, int) and k > 0
        self.inner_optimizer = inner_optimizer
        self.alpha = alpha
        self.k = k

    def minimize(self, loss, startup_program=None):
        from . import layers
        from .framework import default_main_program, \
            default_startup_program
        mini_out = self.inner_optimizer.minimize(
            loss, startup_program=startup_program)
        main = loss.block.program
        startup = startup_program or default_startup_program()
        block = main.global_block()
        params = [p.name for p in block.all_parameters()]

        with main._role_guard('optimize'):
            k = layers.fill_constant([1], 'int32', self.k)
            one = layers.fill_constant([1], 'int32', 1)
            zero = layers.fill_constant([1], 'int32', 0)
            step = layers.autoincreased_step_counter(begin=1)
            step_i = layers.cast(step, 'int32')
            mod = layers.elementwise_mod(step_i, k)
            do_sync = layers.cast(layers.equal(mod, zero), 'float32')
            for name in params:
                fast = block.var(name)
                slow_name = name + '@SLOW'
                slow = block.create_var(name=slow_name,
                                        shape=fast.shape,
                                        dtype=fast.dtype,
                                        persistable=True)
                sb = startup.global_block()
                sb.create_var(name=slow_name, shape=fast.shape,
                              dtype=fast.dtype, persistable=True)
                sb.append_op('assign', inputs={'X': name},
                             outputs={'Out': slow_name},
                             infer_shape=False)
                # slow_new = slow + alpha*(fast-slow) when sync else slow
                diff = layers.elementwise_sub(fast, slow)
                cand = layers.elementwise_add(
                    slow, layers.scale(diff, scale=self.alpha))
                gate = do_sync  # [1] broadcasting over param dims
                inv = layers.elementwise_sub(
                    layers.fill_constant([1], 'float32', 1.0), gate)
                new_slow = layers.elementwise_add(
                    layers.elementwise_mul(cand, gate, axis=0
                                           if len(fast.shape) == 1
                                           else -1),
                    layers.elementwise_mul(slow, inv, axis=0
                                           if len(fast.shape) == 1
                                           else -1))
                block.append_op('assign', inputs={'X': new_slow},
                                outputs={'Out': slow_name},
                                infer_shape=False)
                new_fast = layers.elementwise_add(
                    layers.elementwise_mul(new_slow, gate,
                                           axis=0 if len(fast.shape) == 1
                                           else -1),
                    layers.elementwise_mul(fast, inv,
                                           axis=0 if len(fast.shape) == 1
                                           else -1))
                block.append_op('assign', inputs={'X': new_fast},
                                outputs={'Out': name},
                                infer_shape=False)
        return mini_out


DecayedAdagrad = DecayedAdagradOptimizer

SGD = SGDOptimizer
Momentum = MomentumOptimizer
Adam = AdamOptimizer
AdamW = AdamWOptimizer
Adagrad = AdagradOptimizer
Adamax = AdamaxOptimizer
Adadelta = AdadeltaOptimizer
RMSProp = RMSPropOptimizer
Ftrl = FtrlOptimizer
Lamb = LambOptimizer
LarsMomentum = LarsMomentumOptimizer
Dpsgd = DpsgdOptimizer


class DGCMomentumOptimizer(MomentumOptimizer):
    """Momentum + Deep Gradient Compression.

    Reference: optimizer.py:952 (DGCMomentumOptimizer) +
    operators/dgc_op.h + details/sparse_all_reduce_op_handle.h.  Before
    rampup_begin_step behaves as plain momentum; after, gradients pass
    through the dgc op (top-k + error feedback) before the update /
    collective all-reduce.
    """

    def __init__(self, learning_rate, momentum, rampup_begin_step=0,
                 rampup_step=1, sparsity=(0.999,), use_nesterov=False,
                 **kwargs):
        super(DGCMomentumOptimizer, self).__init__(
            learning_rate, momentum, use_nesterov, **kwargs)
        self._rampup_begin_step = rampup_begin_step
        self._sparsity = sparsity[-1] if isinstance(
            sparsity, (list, tuple)) else sparsity

    def _create_accumulators(self, block, parameters):
        super(DGCMomentumOptimizer, self)._create_accumulators(
            block, parameters)
        for p in parameters:
            self._add_accumulator('dgc_u', p)
            self._add_accumulator('dgc_v', p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        u = self._get_accumulator('dgc_u', p)
        v = self._get_accumulator('dgc_v', p)
        encoded = block.create_var(
            name=unique_name.generate(g.name + '_dgc'),
            shape=tuple(p.shape), dtype=p.dtype)
        encoded.stop_gradient = True
        block.append_op('dgc',
                        inputs={'Grad': g, 'U': u, 'V': v},
                        outputs={'EncodeGrad': encoded, 'UOut': u,
                                 'VOut': v, 'GradOut': encoded},
                        attrs={'m': self._momentum,
                               'sparsity_ratio': self._sparsity},
                        infer_shape=False)
        # momentum is already folded into the dgc accumulators (u), so
        # the parameter update is plain sgd on the encoded grad
        # (reference dgc_momentum op's DGC branch)
        return block.append_op(
            'sgd',
            inputs={'Param': p, 'Grad': encoded,
                    'LearningRate': self._create_param_lr((p, encoded))},
            outputs={'ParamOut': p}, infer_shape=False)
