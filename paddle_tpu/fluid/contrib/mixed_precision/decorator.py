"""AMP decorator: bf16/fp16 compute + dynamic loss scaling.

Reference: python/paddle/fluid/contrib/mixed_precision/decorator.py:27
(OptimizerWithMixedPrecision, :53-69 loss scaling) and fp16_utils.py
(black/white list program rewrite).

TPU-native re-design: instead of rewriting var dtypes and inserting cast
ops everywhere, white-list ops get an '__amp__' attr; their lowerings cast
operands to bfloat16 so the MXU runs at native precision with f32
accumulation, and XLA fuses the casts.  Loss scaling is kept on-device via
check_finite_and_unscale / update_loss_scaling ops (ops/amp_ops.py) — a
skipped step applies zero gradients instead of branching to the host.
"""

from ... import unique_name
from ...framework import default_main_program, default_startup_program
from .fp16_lists import AutoMixedPrecisionLists


# An op that carries this attribute is left unmarked whatever list its
# type is on: it computes in the dtypes its inputs arrive in, by
# jnp's promotion (a float32 residual stream plus a bfloat16 branch is
# float32; a matmul of float32 operands is a float32 matmul).
KEEP_FLOAT32 = '__amp_keep_float32__'


def keep_float32(*variables):
    """Exempt the ops that produced ``variables`` from the lists'
    placement: placement is by op TYPE for a whole program, and a
    model whose residual adds or output heads stay float32 beside
    bfloat16 matmuls (EvaByte's ``fp32_skip_add``, ``fp32_logits``)
    says so op by op.  Call it on a layer's output as the program is
    built; ``decorate(...).minimize`` honours it.  -> ``variables``
    (the one, or the tuple)."""
    for var in variables:
        var.op.attrs[KEEP_FLOAT32] = True
    return variables[0] if len(variables) == 1 else variables


# A white `mul` that carries this attribute multiplies bfloat16
# operands as every other and WRITES float32: the MXU's own
# accumulator, not rounded on its way out.
FLOAT32_OUTPUT = '__amp_float32_out__'


def float32_output(var):
    """Have the `mul` that produced ``var`` keep its float32
    accumulator as its output under AMP: a vocabulary head whose
    logits meet a float32 softmax (a float32 product of float32
    operands, which ``keep_float32`` would give, costs six bfloat16
    passes on the MXU).  Without AMP the op is as it was.  -> ``var``."""
    var.op.attrs[FLOAT32_OUTPUT] = True
    return var


def _mark_amp_ops(program, amp_lists):
    """White ops run their MXU dots in bf16 ('__amp__'); gray ops FOLLOW
    a low-precision input by casting their f32 inputs down
    ('__amp_gray__', applied in OpDef.run) — the reference
    fp16_utils._insert_cast_op rule.  Without the gray mark, jnp type
    promotion casts the bf16 matmul output back UP at every f32
    master-param bias add, and the whole downstream activation stream
    (residuals, attention operands) silently runs f32 at double HBM
    traffic.  Black ops cast up to f32 ('__amp_black__') for numerics
    (softmax/CE/reductions)."""
    # norm ops keep their f32 params (the reference rewrite also never
    # casts BN/LN Scale/Bias/stats): their lowerings already compute
    # stats in f32 and emit outputs in the input dtype, so the follow
    # rule is theirs for free without degrading the parameters
    no_harmonize = {'batch_norm', 'layer_norm', 'instance_norm',
                    'group_norm', 'sync_batch_norm',
                    'rms_norm', 'rotary_embedding', 'short_conv',
                    'eva_chunk_summary',
                    # float32 log decays beside bf16 q, k, v: cast
                    # neither way
                    'kda_attention',
                    # float32 steps and decays beside bf16 x, B, C:
                    # cast neither way
                    'selective_scan', 'ssd_scan',
                    # float32 phi / alpha / bias and maps beside a
                    # bf16 stream: cast neither way
                    'hyper_connection_pre', 'hyper_connection_post',
                    # f32 gates beside bf16 rows: cast neither way
                    'moe_route', 'moe_dispatch', 'moe_combine',
                    # compute in f32 internally; black-casting their
                    # bf16 inputs up would only double the buffer
                    # (SWCE's analytic-vjp residual is the logits AS
                    # THEY ARRIVED; softmax emits its input dtype)
                    'softmax_with_cross_entropy', 'softmax'}
    # an EXPLICIT custom placement overrides the exemption — the user
    # asked for the cast
    no_harmonize -= getattr(amp_lists, 'custom_placed', set())
    for block in program.blocks:
        for op in block.ops:
            if op.attrs.get(KEEP_FLOAT32):
                continue
            if op.type in amp_lists.white_list:
                op.attrs['__amp__'] = True
            elif op.type in amp_lists.gray_list - no_harmonize:
                op.attrs['__amp_gray__'] = True
            elif op.type in amp_lists.black_list - no_harmonize:
                op.attrs['__amp_black__'] = True
            elif op.type in amp_lists.black_list:
                # exempt from the input cast-up (f32-internal
                # lowerings), but the black rule's f32-OUTPUT contract
                # still applies to tiny per-row outputs: reported loss
                # keeps f32 precision (ADVICE r4)
                op.attrs['__amp_black_out__'] = True
    program._bump_version()


def _make_scalar(name, dtype, value):
    main = default_main_program().global_block()
    var = main.create_var(name=name, shape=(1,), dtype=dtype,
                          persistable=True)
    var.stop_gradient = True
    sb = default_startup_program().global_block()
    sb.create_var(name=name, shape=(1,), dtype=dtype, persistable=True)
    sb.append_op('fill_constant', outputs={'Out': name},
                 attrs={'shape': [1], 'dtype': dtype,
                        'value': float(value)})
    return var


class OptimizerWithMixedPrecision(object):
    def __init__(self, optimizer, amp_lists=None, init_loss_scaling=2**15,
                 use_dynamic_loss_scaling=True, incr_every_n_steps=1000,
                 decr_every_n_nan_or_inf=2, incr_ratio=2.0,
                 decr_ratio=0.5):
        self._optimizer = optimizer
        self._amp_lists = amp_lists or AutoMixedPrecisionLists()
        self._init_loss_scaling = init_loss_scaling
        self._use_dynamic = use_dynamic_loss_scaling
        self._incr_every_n_steps = incr_every_n_steps
        self._decr_every_n_nan_or_inf = decr_every_n_nan_or_inf
        self._incr_ratio = incr_ratio
        self._decr_ratio = decr_ratio
        self._loss_scaling = None
        # a fixed scale of 1 is no loss scaling (bfloat16 training as
        # published: f32's exponent range): no scaled loss, no
        # check_finite_and_unscale, so no op joins all the gradients
        # and each dies at its own parameter's update
        self._scales = bool(use_dynamic_loss_scaling) or \
            float(init_loss_scaling) != 1.0

    def get_loss_scaling(self):
        return self._loss_scaling

    def backward(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None, callbacks=None):
        program = loss.block.program
        _mark_amp_ops(program, self._amp_lists)
        if not self._scales:
            return self._optimizer.backward(
                loss, startup_program, parameter_list, no_grad_set,
                callbacks)
        self._loss_scaling = _make_scalar(
            unique_name.generate('loss_scaling'), 'float32',
            self._init_loss_scaling)
        block = program.global_block()
        scaled_loss = block.create_var(
            name=unique_name.generate('scaled_loss'), shape=loss.shape,
            dtype=loss.dtype)
        block.append_op('elementwise_mul',
                        inputs={'X': loss, 'Y': self._loss_scaling},
                        outputs={'Out': scaled_loss}, attrs={'axis': -1})
        self._scaled_loss = block.vars[scaled_loss.name]
        params_grads = self._optimizer.backward(
            self._scaled_loss, startup_program, parameter_list,
            no_grad_set, callbacks)
        return params_grads

    def apply_gradients(self, params_grads):
        with default_main_program()._role_guard('optimize'):
            return self._apply_gradients_impl(params_grads)

    def _apply_gradients_impl(self, params_grads):
        if not self._scales:
            return self._optimizer.apply_gradients(params_grads)
        block = default_main_program().global_block()
        grads = [g for _, g in params_grads if g is not None]
        unscaled = []
        for g in grads:
            u = block.create_var(
                name=unique_name.generate(g.name + '_unscaled'),
                shape=g.shape, dtype=g.dtype)
            u.stop_gradient = True
            unscaled.append(u)
        found_inf = block.create_var(
            name=unique_name.generate('found_inf'), shape=(), dtype='bool')
        found_inf.stop_gradient = True
        block.append_op('check_finite_and_unscale',
                        inputs={'X': grads, 'Scale': self._loss_scaling},
                        outputs={'Out': unscaled,
                                 'FoundInfinite': found_inf},
                        infer_shape=False)
        if self._use_dynamic:
            good = _make_scalar(unique_name.generate('good_steps'),
                                'int32', 0)
            bad = _make_scalar(unique_name.generate('bad_steps'),
                               'int32', 0)
            block.append_op(
                'update_loss_scaling',
                inputs={'FoundInfinite': found_inf,
                        'PrevLossScaling': self._loss_scaling,
                        'InGoodSteps': good, 'InBadSteps': bad},
                outputs={'LossScaling': self._loss_scaling,
                         'OutGoodSteps': good, 'OutBadSteps': bad},
                attrs={'incr_every_n_steps': self._incr_every_n_steps,
                       'decr_every_n_nan_or_inf':
                           self._decr_every_n_nan_or_inf,
                       'incr_ratio': self._incr_ratio,
                       'decr_ratio': self._decr_ratio},
                infer_shape=False)
        new_pg = []
        i = 0
        for p, g in params_grads:
            if g is None:
                new_pg.append((p, g))
            else:
                new_pg.append((p, unscaled[i]))
                i += 1
        return self._optimizer.apply_gradients(new_pg)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        params_grads = self.backward(loss, startup_program,
                                     parameter_list, no_grad_set)
        optimize_ops = self.apply_gradients(params_grads)
        return optimize_ops, params_grads


def decorate(optimizer, amp_lists=None, init_loss_scaling=2**15,
             incr_every_n_steps=1000, decr_every_n_nan_or_inf=2,
             incr_ratio=2.0, decr_ratio=0.5,
             use_dynamic_loss_scaling=True):
    """Reference: decorator.py decorate().  ``init_loss_scaling=1.0``
    with ``use_dynamic_loss_scaling=False`` is bfloat16 training with
    no loss scaling at all: the loss-scaling ops are left out."""
    return OptimizerWithMixedPrecision(
        optimizer, amp_lists, init_loss_scaling, use_dynamic_loss_scaling,
        incr_every_n_steps, decr_every_n_nan_or_inf, incr_ratio,
        decr_ratio)
