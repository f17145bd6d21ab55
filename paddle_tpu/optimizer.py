"""Optimizers (reference: python/paddle/fluid/optimizer.py:50).

`minimize()` = append_backward (one functional-vjp backward op) + one update
op per parameter; accumulators are persistable vars initialized in the
startup program.  The whole fwd+bwd+update chain lowers to a single XLA
program, so the reference's fuse_adam/fuse_sgd/fuse_all_reduce build passes
have no equivalent here — XLA fusion subsumes them.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from .core import unique_name
from .core.autodiff import append_backward
from .core.dtypes import canonical_dtype
from .core.program import Parameter, Program, Variable, default_main_program, default_startup_program
from .core.regularizer import append_regularization_ops
from .monitor import MONITOR as _MON


class Optimizer:
    _accumulator_prefix = "accum"

    def __init__(self, learning_rate, regularization=None, name=None):
        self.regularization = regularization
        self._name = name
        self._learning_rate = learning_rate
        self._lr_var: Optional[Variable] = None
        self._accumulators: Dict[str, Dict[str, Variable]] = {}

    # --- learning rate ---------------------------------------------------
    def _create_global_learning_rate(self):
        if self._lr_var is not None:
            return
        if isinstance(self._learning_rate, Variable):
            self._lr_var = self._learning_rate
            return
        name = unique_name.generate("learning_rate")
        main_block = default_main_program().global_block()
        self._lr_var = main_block.create_var(name, shape=(1,), dtype="float32", persistable=True)
        startup = default_startup_program().global_block()
        startup.create_var(name, shape=(1,), dtype="float32", persistable=True)
        startup.append_op(
            "fill_constant",
            outputs={"Out": [name]},
            attrs={"shape": [1], "dtype": "float32", "value": float(self._learning_rate)},
        )

    @property
    def lr_var(self):
        return self._lr_var

    # --- accumulators ----------------------------------------------------
    def _add_accumulator(self, name: str, param: Parameter, fill_value: float = 0.0,
                         shape=None, dtype=None):
        if name in self._accumulators and param.name in self._accumulators[name]:
            return self._accumulators[name][param.name]
        var_name = f"{param.name}_{name}_0"
        shape = list(shape if shape is not None else param.shape)
        dtype = canonical_dtype(dtype or param.dtype)
        main_block = default_main_program().global_block()
        v = main_block.create_var(var_name, shape=shape, dtype=dtype, persistable=True)
        startup = default_startup_program().global_block()
        startup.create_var(var_name, shape=shape, dtype=dtype, persistable=True)
        startup.append_op(
            "fill_constant",
            outputs={"Out": [var_name]},
            attrs={"shape": shape, "dtype": dtype, "value": float(fill_value)},
        )
        # an accumulator of the parameter's shape lies where the parameter lies: a hinted parameter's moments take
        # its hint, in the main and in the start-up program (`parallel.shard_parameters`)
        for program in (default_main_program(), default_startup_program()):
            hint = program.sharding_hints.get(param.name)
            if hint is not None and tuple(shape) == tuple(param.shape):
                program.sharding_hints[var_name] = hint
        self._accumulators.setdefault(name, {})[param.name] = v
        return v

    def _get_accumulator(self, name, param):
        return self._accumulators[name][param.name]

    # --- hooks subclasses implement --------------------------------------
    def _create_accumulators(self, block, parameters):
        pass

    def _append_optimize_op(self, block, param_and_grad):
        raise NotImplementedError

    def _finish_update(self, block, params_grads):
        pass

    # --- public API -------------------------------------------------------
    def apply_gradients(self, params_grads) -> List:
        from .clip import append_gradient_clip_ops

        block = default_main_program().global_block()
        self._create_global_learning_rate()
        # SelectedRows grads (is_sparse embeddings) bypass clip/regularization
        # op rewrites — those append dense-tensor ops onto the grad var
        # (reference pserver mode likewise routes sparse grads around the
        # dense grad pipeline, distribute_transpiler.py:1428)
        sparse_set = set()
        for op in block.ops:
            if op.type == "backward":
                sparse_set.update(op.attrs.get("sparse_param_names", []))
        sparse_pg = [(p, g) for p, g in params_grads if p.name in sparse_set]
        dense_pg = [(p, g) for p, g in params_grads if p.name not in sparse_set]
        dense_pg = append_gradient_clip_ops(dense_pg)
        dense_pg = append_regularization_ops(dense_pg, self.regularization)
        params_grads = dense_pg + sparse_pg
        self._create_accumulators(block, [p for p, _ in params_grads])
        ops = []
        for pg in params_grads:
            ops.append(self._append_optimize_op(block, pg))
        self._finish_update(block, params_grads)
        return ops

    def backward(self, loss, startup_program=None, parameter_list=None, no_grad_set=None,
                 callbacks=None):
        return append_backward(loss, parameter_list, no_grad_set, callbacks)

    def apply_optimize(self, loss, startup_program, params_grads):
        """reference optimizer.py apply_optimize: the apply_gradients half
        of minimize (grad clip etc. included)."""
        return self.apply_gradients(params_grads)

    def get_opti_var_name_list(self):
        """reference optimizer.py get_opti_var_name_list: names of the
        accumulator variables this optimizer created."""
        return [v.name for by_param in self._accumulators.values()
                for v in by_param.values()]

    def load(self, stat_dict):
        """reference optimizer.py load (dygraph checkpoints): install
        accumulator values by name."""
        for name, by_param in self._accumulators.items():
            for pname, var in by_param.items():
                if var.name in stat_dict:
                    from .core.scope import global_scope

                    global_scope().set_var(var.name, stat_dict[var.name])

    def minimize(self, loss, startup_program=None, parameter_list=None, no_grad_set=None):
        from .dygraph import base as _dy

        if _dy.enabled():
            return self._dygraph_minimize(loss, parameter_list)
        with _MON.span("program.optimize", program=loss.block.program._uuid[:8]):
            params_grads = self.backward(loss, startup_program, parameter_list, no_grad_set)
            optimize_ops = self.apply_gradients(params_grads)
        return optimize_ops, params_grads

    # --- dygraph (eager) path --------------------------------------------
    def _dygraph_minimize(self, loss, parameter_list):
        """Applies the update rule eagerly from each param's .grad
        (reference: optimizer.py dygraph branch — grads come from
        loss.backward(), which the caller invokes first)."""
        if parameter_list is None:
            raise ValueError("dygraph minimize() needs parameter_list")
        if not hasattr(self, "_eager_state"):
            self._eager_state: Dict[int, dict] = {}
        lr = self._learning_rate() if callable(self._learning_rate) else self._learning_rate
        updated = []
        for p in parameter_list:
            if p.grad is None or p.stop_gradient:
                continue
            st = self._eager_state.setdefault(id(p), {})
            p.value = self._eager_update(p.value, p.grad, float(lr), st)
            updated.append(p)
        return [], [(p, p.grad) for p in updated]

    def _eager_update(self, p, g, lr, state):
        raise NotImplementedError(
            f"{type(self).__name__} has no eager (dygraph) update rule yet"
        )


class SGDOptimizer(Optimizer):
    def _eager_update(self, p, g, lr, state):
        return p - lr * g

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        return block.append_op(
            "sgd",
            inputs={"Param": [p.name], "Grad": [g.name], "LearningRate": [self._lr_var.name]},
            outputs={"ParamOut": [p.name]},
        )


class MomentumOptimizer(Optimizer):
    def __init__(self, learning_rate, momentum, use_nesterov=False, **kw):
        super().__init__(learning_rate, **kw)
        self._momentum = momentum
        self._use_nesterov = use_nesterov

    def _eager_update(self, p, g, lr, state):
        import jax.numpy as jnp

        v = state.get("velocity")
        v = jnp.zeros_like(p) if v is None else v
        v_new = self._momentum * v + g
        if self._use_nesterov:
            p_new = p - lr * (g + self._momentum * v_new)
        else:
            p_new = p - lr * v_new
        state["velocity"] = v_new
        return p_new

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("velocity", p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        v = self._get_accumulator("velocity", p)
        return block.append_op(
            "momentum",
            inputs={
                "Param": [p.name],
                "Grad": [g.name],
                "Velocity": [v.name],
                "LearningRate": [self._lr_var.name],
            },
            outputs={"ParamOut": [p.name], "VelocityOut": [v.name]},
            attrs={"mu": self._momentum, "use_nesterov": self._use_nesterov},
        )


class AdamOptimizer(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 lazy_mode=False, **kw):
        super().__init__(learning_rate, **kw)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._lazy_mode = lazy_mode

    def _eager_update(self, p, g, lr, state):
        import jax.numpy as jnp

        m1 = state.get("m1")
        m1 = jnp.zeros_like(p) if m1 is None else m1
        m2 = state.get("m2")
        m2 = jnp.zeros_like(p) if m2 is None else m2
        b1p = state.get("b1p", 1.0) * self._beta1
        b2p = state.get("b2p", 1.0) * self._beta2
        m1 = self._beta1 * m1 + (1 - self._beta1) * g
        m2 = self._beta2 * m2 + (1 - self._beta2) * jnp.square(g)
        lr_t = lr * (1 - b2p) ** 0.5 / (1 - b1p)
        state.update(m1=m1, m2=m2, b1p=b1p, b2p=b2p)
        return p - lr_t * m1 / (jnp.sqrt(m2) + self._epsilon)

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment1", p)
            self._add_accumulator("moment2", p)
            # beta powers MUST be f32 regardless of param dtype: bf16 cannot
            # represent 0.999 (rounds to 1.0), which zeroes the bias-corrected
            # lr and silently freezes training (r5 chip round)
            self._add_accumulator("beta1_pow_acc", p, fill_value=self._beta1, shape=[1],
                                  dtype="float32")
            self._add_accumulator("beta2_pow_acc", p, fill_value=self._beta2, shape=[1],
                                  dtype="float32")

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        m1 = self._get_accumulator("moment1", p)
        m2 = self._get_accumulator("moment2", p)
        b1p = self._get_accumulator("beta1_pow_acc", p)
        b2p = self._get_accumulator("beta2_pow_acc", p)
        return block.append_op(
            "adam",
            inputs={
                "Param": [p.name],
                "Grad": [g.name],
                "Moment1": [m1.name],
                "Moment2": [m2.name],
                "Beta1Pow": [b1p.name],
                "Beta2Pow": [b2p.name],
                "LearningRate": [self._lr_var.name],
            },
            outputs={
                "ParamOut": [p.name],
                "Moment1Out": [m1.name],
                "Moment2Out": [m2.name],
                "Beta1PowOut": [b1p.name],
                "Beta2PowOut": [b2p.name],
            },
            attrs={"beta1": self._beta1, "beta2": self._beta2, "epsilon": self._epsilon,
                   "lazy_mode": self._lazy_mode},
        )


class AdagradOptimizer(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, initial_accumulator_value=0.0, **kw):
        super().__init__(learning_rate, **kw)
        self._epsilon = epsilon
        self._initial = initial_accumulator_value

    def _eager_update(self, p, g, lr, state):
        import jax.numpy as jnp

        m = state.get("moment")
        m = jnp.full_like(p, self._initial) if m is None else m
        m = m + jnp.square(g)
        state["moment"] = m
        return p - lr * g / (jnp.sqrt(m) + self._epsilon)

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p, fill_value=self._initial)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        m = self._get_accumulator("moment", p)
        return block.append_op(
            "adagrad",
            inputs={
                "Param": [p.name],
                "Grad": [g.name],
                "Moment": [m.name],
                "LearningRate": [self._lr_var.name],
            },
            outputs={"ParamOut": [p.name], "MomentOut": [m.name]},
            attrs={"epsilon": self._epsilon},
        )


class RMSPropOptimizer(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0, centered=False, **kw):
        super().__init__(learning_rate, **kw)
        self._rho, self._epsilon, self._momentum, self._centered = rho, epsilon, momentum, centered

    def _eager_update(self, p, g, lr, state):
        import jax.numpy as jnp

        if not state:
            state.update(ms=jnp.zeros_like(p), mg=jnp.zeros_like(p),
                         mom=jnp.zeros_like(p))
        ms, mg, mom = state["ms"], state["mg"], state["mom"]
        ms = self._rho * ms + (1 - self._rho) * jnp.square(g)
        if self._centered:
            mg = self._rho * mg + (1 - self._rho) * g
            denom = jnp.sqrt(ms - jnp.square(mg) + self._epsilon)
        else:
            denom = jnp.sqrt(ms + self._epsilon)
        mom = self._momentum * mom + lr * g / denom
        state.update(ms=ms, mg=mg, mom=mom)
        return p - mom

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("mean_square", p)
            self._add_accumulator("mean_grad", p)
            self._add_accumulator("momentum", p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        ms = self._get_accumulator("mean_square", p)
        mg = self._get_accumulator("mean_grad", p)
        mom = self._get_accumulator("momentum", p)
        return block.append_op(
            "rmsprop",
            inputs={
                "Param": [p.name],
                "Grad": [g.name],
                "MeanSquare": [ms.name],
                "MeanGrad": [mg.name],
                "Moment": [mom.name],
                "LearningRate": [self._lr_var.name],
            },
            outputs={
                "ParamOut": [p.name],
                "MeanSquareOut": [ms.name],
                "MeanGradOut": [mg.name],
                "MomentOut": [mom.name],
            },
            attrs={
                "decay": self._rho,
                "epsilon": self._epsilon,
                "momentum": self._momentum,
                "centered": self._centered,
            },
        )


class AdamaxOptimizer(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8, **kw):
        super().__init__(learning_rate, **kw)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _eager_update(self, p, g, lr, state):
        import jax.numpy as jnp

        if not state:
            state.update(m=jnp.zeros_like(p), inf=jnp.zeros_like(p), b1p=1.0)
        m, inf = state["m"], state["inf"]
        b1p = state["b1p"] * self._beta1
        m = self._beta1 * m + (1 - self._beta1) * g
        inf = jnp.maximum(self._beta2 * inf, jnp.abs(g))
        state.update(m=m, inf=inf, b1p=b1p)
        return p - (lr / (1 - b1p)) * m / (inf + self._epsilon)

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p)
            self._add_accumulator("inf_norm", p)
            self._add_accumulator("beta1_pow_acc", p, fill_value=self._beta1, shape=[1],
                                  dtype="float32")

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        m = self._get_accumulator("moment", p)
        inf = self._get_accumulator("inf_norm", p)
        b1p = self._get_accumulator("beta1_pow_acc", p)
        op = block.append_op(
            "adamax",
            inputs={
                "Param": [p.name],
                "Grad": [g.name],
                "Moment": [m.name],
                "InfNorm": [inf.name],
                "Beta1Pow": [b1p.name],
                "LearningRate": [self._lr_var.name],
            },
            outputs={"ParamOut": [p.name], "MomentOut": [m.name], "InfNormOut": [inf.name]},
            attrs={"beta1": self._beta1, "beta2": self._beta2, "epsilon": self._epsilon},
        )
        # beta1_pow update (reference does this in _finish_update via scale op)
        block.append_op(
            "scale",
            inputs={"X": [b1p.name]},
            outputs={"Out": [b1p.name]},
            attrs={"scale": self._beta1},
        )
        return op


class AdadeltaOptimizer(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, rho=0.95, **kw):
        super().__init__(learning_rate, **kw)
        self._epsilon, self._rho = epsilon, rho

    def _eager_update(self, p, g, lr, state):
        import jax.numpy as jnp

        if not state:
            state.update(g2=jnp.zeros_like(p), u2=jnp.zeros_like(p))
        g2, u2 = state["g2"], state["u2"]
        g2 = self._rho * g2 + (1 - self._rho) * jnp.square(g)
        upd = -jnp.sqrt((u2 + self._epsilon) / (g2 + self._epsilon)) * g
        u2 = self._rho * u2 + (1 - self._rho) * jnp.square(upd)
        state.update(g2=g2, u2=u2)
        return p + upd

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("avg_squared_grad", p)
            self._add_accumulator("avg_squared_update", p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        asg = self._get_accumulator("avg_squared_grad", p)
        asu = self._get_accumulator("avg_squared_update", p)
        return block.append_op(
            "adadelta",
            inputs={
                "Param": [p.name],
                "Grad": [g.name],
                "AvgSquaredGrad": [asg.name],
                "AvgSquaredUpdate": [asu.name],
                "LearningRate": [self._lr_var.name],
            },
            outputs={
                "ParamOut": [p.name],
                "AvgSquaredGradOut": [asg.name],
                "AvgSquaredUpdateOut": [asu.name],
            },
            attrs={"epsilon": self._epsilon, "rho": self._rho},
        )


class FtrlOptimizer(Optimizer):
    def __init__(self, learning_rate, l1=0.0, l2=0.0, lr_power=-0.5, **kw):
        super().__init__(learning_rate, **kw)
        self._l1, self._l2, self._lr_power = l1, l2, lr_power

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("squared", p)
            self._add_accumulator("linear", p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        sq = self._get_accumulator("squared", p)
        lin = self._get_accumulator("linear", p)
        return block.append_op(
            "ftrl",
            inputs={
                "Param": [p.name],
                "Grad": [g.name],
                "SquaredAccumulator": [sq.name],
                "LinearAccumulator": [lin.name],
                "LearningRate": [self._lr_var.name],
            },
            outputs={"ParamOut": [p.name], "SquaredAccumOut": [sq.name], "LinearAccumOut": [lin.name]},
            attrs={"l1": self._l1, "l2": self._l2, "lr_power": self._lr_power},
        )


class LambOptimizer(AdamOptimizer):
    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9, beta2=0.999,
                 epsilon=1e-6, **kw):
        super().__init__(learning_rate, beta1, beta2, epsilon, **kw)
        self._weight_decay = lamb_weight_decay

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        m1 = self._get_accumulator("moment1", p)
        m2 = self._get_accumulator("moment2", p)
        b1p = self._get_accumulator("beta1_pow_acc", p)
        b2p = self._get_accumulator("beta2_pow_acc", p)
        return block.append_op(
            "lamb",
            inputs={
                "Param": [p.name],
                "Grad": [g.name],
                "Moment1": [m1.name],
                "Moment2": [m2.name],
                "Beta1Pow": [b1p.name],
                "Beta2Pow": [b2p.name],
                "LearningRate": [self._lr_var.name],
            },
            outputs={
                "ParamOut": [p.name],
                "Moment1Out": [m1.name],
                "Moment2Out": [m2.name],
                "Beta1PowOut": [b1p.name],
                "Beta2PowOut": [b2p.name],
            },
            attrs={
                "beta1": self._beta1,
                "beta2": self._beta2,
                "epsilon": self._epsilon,
                "weight_decay": self._weight_decay,
            },
        )


class LarsMomentumOptimizer(MomentumOptimizer):
    """Layer-adaptive rate scaling (reference optimizer.py:1044
    LarsMomentumOptimizer over lars_momentum_op.cc)."""

    def __init__(self, learning_rate, momentum, lars_coeff=0.001,
                 lars_weight_decay=0.0005, **kw):
        super().__init__(learning_rate, momentum, **kw)
        self._lars_coeff = lars_coeff
        self._lars_weight_decay = lars_weight_decay

    def _eager_update(self, p, g, lr, state):
        import jax.numpy as jnp

        wd = self._lars_weight_decay
        p_norm = jnp.sqrt(jnp.sum(jnp.square(p)))
        g_norm = jnp.sqrt(jnp.sum(jnp.square(g)))
        local_lr = jnp.where(
            (p_norm > 0) & (g_norm > 0),
            lr * self._lars_coeff * p_norm / (g_norm + wd * p_norm),
            lr,
        )
        v = state.get("velocity")
        v = jnp.zeros_like(p) if v is None else v
        v_new = self._momentum * v + local_lr * (g + wd * p)
        state["velocity"] = v_new
        return p - v_new

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        v = self._get_accumulator("velocity", p)
        return block.append_op(
            "lars_momentum",
            inputs={"Param": [p.name], "Grad": [g.name], "Velocity": [v.name],
                    "LearningRate": [self._lr_var.name]},
            outputs={"ParamOut": [p.name], "VelocityOut": [v.name]},
            attrs={"mu": self._momentum, "lars_coeff": self._lars_coeff,
                   "lars_weight_decay": self._lars_weight_decay},
        )


class DecayedAdagradOptimizer(Optimizer):
    """reference optimizer.py DecayedAdagradOptimizer over
    decayed_adagrad_op.h: exponentially-decayed squared-gradient moment."""

    def __init__(self, learning_rate, decay=0.95, epsilon=1e-6, **kw):
        super().__init__(learning_rate, **kw)
        self._decay = decay
        self._epsilon = epsilon

    def _eager_update(self, p, g, lr, state):
        import jax.numpy as jnp

        m = state.get("moment")
        m = jnp.zeros_like(p) if m is None else m
        m = self._decay * m + (1.0 - self._decay) * g * g
        state["moment"] = m
        return p - lr * g / (jnp.sqrt(m) + self._epsilon)

    def _create_accumulators(self, block, parameters):
        for p in parameters:
            self._add_accumulator("moment", p)

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        m = self._get_accumulator("moment", p)
        return block.append_op(
            "decayed_adagrad",
            inputs={"Param": [p.name], "Grad": [g.name], "Moment": [m.name],
                    "LearningRate": [self._lr_var.name]},
            outputs={"ParamOut": [p.name], "MomentOut": [m.name]},
            attrs={"decay": self._decay, "epsilon": self._epsilon},
        )


class DGCMomentumOptimizer(MomentumOptimizer):
    """Deep Gradient Compression momentum (reference optimizer.py:786
    DGCMomentumOptimizer, arXiv:1712.01887): before each momentum update a
    `dgc` op sparsifies the gradient — top-(1-sparsity) of the
    error-feedback buffer with momentum correction and factor masking,
    ramping sparsity over rampup_step beginning at rampup_begin_step.
    As in the reference, parameters with < 16384 elements, SelectedRows
    grads, and non-fp32 params bypass compression; also as in the
    reference, the momentum op still consumes the compressed grad (the
    dgc op ALSO momentum-corrects U — reference optimizer.py:786 does not
    override _append_optimize_op), so effective steps compound: deploy
    with rampup warmup and an accordingly modest lr.

    TPU deviation (recorded): under GSPMD the grad is already summed over
    dp — wire compression is XLA's job on ICI — so the op runs with
    single-worker semantics on the summed grad; the multi-worker sparse
    slab exchange for DCN-spanning topologies is parallel/dgc.py."""

    _DGC_MIN_NUMEL = 16384  # reference _append_dgc_ops threshold

    def __init__(self, learning_rate, momentum, rampup_begin_step,
                 rampup_step=1, sparsity=(0.999,), use_nesterov=False,
                 local_grad_clip_norm=None, num_trainers=None,
                 regularization=None, name=None):
        super().__init__(learning_rate, momentum, use_nesterov,
                         regularization=regularization, name=name)
        self._rampup_begin_step = float(rampup_begin_step)
        self._rampup_step = float(rampup_step)
        self._sparsity = [float(s) for s in sparsity]
        self._clip_norm = 0.0
        if local_grad_clip_norm is not None:
            if not isinstance(num_trainers, int) or num_trainers <= 0:
                raise ValueError("DGCMomentumOptimizer: local_grad_clip_norm "
                                 "needs a positive int num_trainers")
            self._clip_norm = float(local_grad_clip_norm) / (num_trainers * num_trainers)
        self._counter_var = None

    def _dgc_eligible(self, param, grad):
        numel = 1
        for d in param.shape:
            numel *= int(d)
        return (numel >= self._DGC_MIN_NUMEL
                and str(param.dtype) in ("float32", "fp32")
                and getattr(grad, "type", None) != "selected_rows")

    def _ensure_counter(self, block):
        if self._counter_var is not None:
            return self._counter_var
        name = unique_name.generate("dgc_counter")
        self._counter_var = block.create_var(name, shape=(1,), dtype="float32",
                                             persistable=True)
        startup = default_startup_program().global_block()
        startup.create_var(name, shape=(1,), dtype="float32", persistable=True)
        startup.append_op("fill_constant", outputs={"Out": [name]},
                          attrs={"shape": [1], "dtype": "float32", "value": -1.0})
        # counter reads `step` starting at 0 (reference begins at begin-1
        # and prepends the increment)
        block.append_op("increment", inputs={"X": [name]},
                        outputs={"Out": [name]}, attrs={"step": 1.0})
        return self._counter_var

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        if self._dgc_eligible(p, g):
            counter = self._ensure_counter(block)
            # u/v allocated lazily so ineligible params don't carry two
            # param-sized fp32 buffers for nothing
            u = self._add_accumulator("dgc_u", p)
            v = self._add_accumulator("dgc_v", p)
            g_out = block.create_var(unique_name.generate(f"{g.name}@DGC"),
                                     shape=g.shape, dtype=g.dtype)
            block.append_op(
                "dgc",
                inputs={"Grad": [g.name], "U": [u.name], "V": [v.name],
                        "CurrentStep": [counter.name]},
                outputs={"GradOut": [g_out.name], "UOut": [u.name],
                         "VOut": [v.name]},
                attrs={"m": self._momentum,
                       "rampup_begin_step": self._rampup_begin_step,
                       "rampup_step": self._rampup_step,
                       "sparsity": self._sparsity,
                       "clip_norm": self._clip_norm},
            )
            g = g_out
        return super()._append_optimize_op(block, (p, g))


class ExponentialMovingAverage:
    """EMA shadow parameters (reference optimizer.py:2431):
    `update()` appends shadow := decay*shadow + (1-decay)*param ops into the
    main program (run them every step); `apply(exe, scope)` context swaps
    bias-corrected shadows into the params for eval and restores after."""

    def __init__(self, decay=0.999, thres_steps=None, name=None):
        self._decay = decay
        self._name = name or "ema"
        self._pairs = []  # (param Variable, shadow name)
        self._step_var = None

    def update(self):
        from .core.initializer import ConstantInitializer
        from .core.param_attr import ParamAttr
        from .layers import tensor as tensor_layers

        program = default_main_program()
        block = program.global_block()
        helper_block = block
        self._step_var = tensor_layers.create_global_var(
            [1], 0, "float32", persistable=True, name=f"{self._name}_step")
        # step += 1
        helper_block.append_op("increment", inputs={"X": [self._step_var.name]},
                               outputs={"Out": [self._step_var.name]},
                               attrs={"step": 1.0})
        for p in program.all_parameters():
            if not p.trainable:
                continue
            shadow_name = f"{self._name}@{p.name}"
            from .core.program import default_startup_program

            sblock = default_startup_program().global_block()
            sblock.create_var(shadow_name, shape=p.shape, dtype=p.dtype, persistable=True)
            block.create_var(shadow_name, shape=p.shape, dtype=p.dtype, persistable=True)
            # startup: shadow = 0
            sblock.append_op(
                "fill_constant", outputs={"Out": [shadow_name]},
                attrs={"shape": list(p.shape or []), "dtype": str(p.dtype), "value": 0.0})
            # main: shadow = decay*shadow + (1-decay)*param
            scaled_s = block.create_var(shape=p.shape, dtype=p.dtype)
            block.append_op("scale", inputs={"X": [shadow_name]},
                            outputs={"Out": [scaled_s.name]},
                            attrs={"scale": self._decay})
            scaled_p = block.create_var(shape=p.shape, dtype=p.dtype)
            block.append_op("scale", inputs={"X": [p.name]},
                            outputs={"Out": [scaled_p.name]},
                            attrs={"scale": 1.0 - self._decay})
            block.append_op("sum", inputs={"X": [scaled_s.name, scaled_p.name]},
                            outputs={"Out": [shadow_name]})
            self._pairs.append((p, shadow_name))

    def apply(self, executor=None, scope=None, need_restore=True):
        """Context manager: swap bias-corrected EMA values into the params."""
        import contextlib

        import numpy as np

        from .core.scope import global_scope

        scope = scope or global_scope()
        ema = self

        @contextlib.contextmanager
        def guard():
            saved = {}
            step = float(np.asarray(scope.find_var(ema._step_var.name)).reshape(-1)[0])
            corr = 1.0 - ema._decay ** max(step, 1.0)
            for p, shadow in ema._pairs:
                saved[p.name] = scope.find_var(p.name)
                sh = np.asarray(scope.find_var(shadow))
                scope.set_var(p.name, (sh / corr).astype(sh.dtype))
            try:
                yield
            finally:
                if need_restore:
                    for n, v in saved.items():
                        scope.set_var(n, v)

        return guard()

    def restore(self, executor=None):
        pass  # the apply() context restores; kept for API parity


class ModelAverage(Optimizer):
    """Bounded-window parameter averaging (reference optimizer.py:2241,
    which rotates sum_1/sum_2/sum_3 windows of max_average_window steps;
    here a single sum+count pair halves on reaching max_average_window —
    effective window ~2x max, O(1) state): `update()` appends the
    accumulation ops, `apply()` swaps the window average in, restoring on
    context exit."""

    def __init__(self, average_window_rate=0.15, min_average_window=10000,
                 max_average_window=10000, name=None):
        super().__init__(0.0, name=name)
        self._max_window = max_average_window
        self._name = name or "model_avg"
        self._pairs = []
        self._count_var = None

    def update(self):
        from .layers import tensor as tensor_layers

        program = default_main_program()
        block = program.global_block()
        self._count_var = tensor_layers.create_global_var(
            [1], 0, "float32", persistable=True, name=f"{self._name}_n")
        from .core.program import default_startup_program

        sblock = default_startup_program().global_block()
        for p in program.all_parameters():
            if not p.trainable:
                continue
            acc = f"{self._name}@{p.name}"
            block.create_var(acc, shape=p.shape, dtype=p.dtype, persistable=True)
            sblock.create_var(acc, shape=p.shape, dtype=p.dtype, persistable=True)
            sblock.append_op(
                "fill_constant", outputs={"Out": [acc]},
                attrs={"shape": list(p.shape or []), "dtype": str(p.dtype), "value": 0.0})
            block.append_op(
                "model_average_accum",
                inputs={"Sum": [acc], "Count": [self._count_var.name], "Param": [p.name]},
                outputs={"SumOut": [acc]},
                attrs={"max_average_window": self._max_window})
            self._pairs.append((p, acc))
        block.append_op(
            "model_average_count",
            inputs={"Count": [self._count_var.name]},
            outputs={"CountOut": [self._count_var.name]},
            attrs={"max_average_window": self._max_window})

    def apply(self, executor=None, scope=None, need_restore=True):
        import contextlib

        import numpy as np

        from .core.scope import global_scope

        scope = scope or global_scope()
        avg = self

        @contextlib.contextmanager
        def guard():
            saved = {}
            n = float(np.asarray(scope.find_var(avg._count_var.name)).reshape(-1)[0])
            n = max(n, 1.0)
            for p, acc in avg._pairs:
                saved[p.name] = scope.find_var(p.name)
                s = np.asarray(scope.find_var(acc))
                scope.set_var(p.name, (s / n).astype(s.dtype))
            try:
                yield
            finally:
                if need_restore:
                    for k, v in saved.items():
                        scope.set_var(k, v)

        return guard()

    def restore(self, executor=None):
        pass


class DpsgdOptimizer(Optimizer):
    """Differentially-private SGD (reference optimizer.py Dpsgd over
    dpsgd_op.cc): clip the gradient's L2 norm, add Gaussian noise, step."""

    def __init__(self, learning_rate, clip=10.0, batch_size=16.0, sigma=1.0, **kw):
        super().__init__(learning_rate, **kw)
        self._clip, self._sigma, self._batch_size = clip, sigma, batch_size

    def _append_optimize_op(self, block, param_and_grad):
        p, g = param_and_grad
        return block.append_op(
            "dpsgd",
            inputs={"Param": [p.name], "Grad": [g.name],
                    "LearningRate": [self._lr_var.name]},
            outputs={"ParamOut": [p.name]},
            attrs={"clip": self._clip, "sigma": self._sigma,
                   "batch_size": self._batch_size},
        )


class PipelineOptimizer:
    """Program-level pipeline parallelism (reference: optimizer.py:2661
    PipelineOptimizer + SectionWorker).

    Usage: tag the repeated middle blocks of the network with
    `with fluid.device_guard(s):` for s = 0..S-1, then
    `PipelineOptimizer(inner_opt, num_microbatches=M).minimize(loss)`.
    The tagged segments are cut out of the main block into one canonical
    sub-block, per-stage parameters are stacked, and a single `pipeline` op
    (ops/pipeline_ops.py) replaces them — GPipe over a `pp` mesh axis, or
    sequential execution without one.

    TPU-first constraint: stages must be structurally identical (same op
    sequence, same param shapes) — the repeated-transformer-block case that
    pipelining on an SPMD machine actually wants.  Head and tail (embedding,
    loss, optimizer) run outside the pipelined region on every device."""

    def __init__(self, optimizer, num_microbatches: int = 4, axis_name: str = "pp"):
        self._optimizer = optimizer
        self._num_microbatches = num_microbatches
        self._axis_name = axis_name

    # delegate the non-minimize surface
    def __getattr__(self, item):
        return getattr(self._optimizer, item)

    def minimize(self, loss, startup_program=None, parameter_list=None, no_grad_set=None):
        self._cut(loss.block.program)
        return self._optimizer.minimize(loss, startup_program, parameter_list, no_grad_set)

    # -- the program cutter ------------------------------------------------
    def _cut(self, program):
        block = program.global_block()
        ops = block.ops
        tags = [op.attrs.get("pipeline_stage") for op in ops]
        stage_ids = sorted({t for t in tags if t is not None})
        if not stage_ids:
            raise ValueError(
                "PipelineOptimizer: no ops tagged with fluid.device_guard(stage)")
        S = len(stage_ids)
        if stage_ids != list(range(S)):
            raise ValueError(f"PipelineOptimizer: stages must be 0..{S-1}, got {stage_ids}")

        # contiguous, ordered segments
        seg_range = {}
        for i, t in enumerate(tags):
            if t is None:
                continue
            lo, hi = seg_range.get(t, (i, i))
            seg_range[t] = (min(lo, i), max(hi, i))
        bounds = [seg_range[s] for s in range(S)]
        for s, (lo, hi) in enumerate(bounds):
            if any(tags[i] != s for i in range(lo, hi + 1)):
                raise ValueError(
                    f"PipelineOptimizer: stage {s} ops are not contiguous "
                    f"(found a different tag inside [{lo},{hi}])")
            if s and bounds[s - 1][1] >= lo:
                raise ValueError("PipelineOptimizer: stage segments out of order")
            if s and bounds[s - 1][1] + 1 != lo:
                gap = [ops[i].type for i in range(bounds[s - 1][1] + 1, lo)]
                raise ValueError(
                    f"PipelineOptimizer: untagged ops {gap} sit between stage "
                    f"{s-1} and stage {s}; everything between the first and "
                    f"last device_guard region must belong to a stage")

        segs = [ops[lo:hi + 1] for lo, hi in bounds]

        def is_param(name):
            v = block._find_var_recursive(name)
            from .core.program import Parameter

            return isinstance(v, Parameter)

        # isomorphism + per-stage params (positional correspondence)
        sig0 = [(o.type, sorted(o.inputs), sorted(o.outputs)) for o in segs[0]]
        stage_params = []
        for s, seg in enumerate(segs):
            sig = [(o.type, sorted(o.inputs), sorted(o.outputs)) for o in seg]
            if sig != sig0:
                raise ValueError(
                    f"PipelineOptimizer: stage {s} is not structurally identical "
                    f"to stage 0 (op sequence {sig} vs {sig0}); pipeline stages "
                    f"must be repeated blocks")
            pnames, seen = [], set()
            for o in seg:
                for n in o.input_arg_names:
                    if n not in seen and is_param(n):
                        seen.add(n)
                        pnames.append(n)
            stage_params.append(pnames)
            if len(pnames) != len(stage_params[0]):
                raise ValueError("PipelineOptimizer: stages read different param counts")
            for a, b in zip(pnames, stage_params[0]):
                if tuple(block.var(a).shape or ()) != tuple(block.var(b).shape or ()):
                    raise ValueError(
                        f"PipelineOptimizer: param shape mismatch {a} vs {b}")
            # persistable writes (BN running stats) can't cross the stage cut
            for o in seg:
                for n in o.output_arg_names:
                    v = block._find_var_recursive(n)
                    if v is not None and v.persistable:
                        raise ValueError(
                            f"PipelineOptimizer: stage {s} op {o.type!r} writes "
                            f"persistable {n!r}; pipelined stages must be "
                            f"stateless (use is_test norms or stat-free blocks)")

        # boundary carries: exactly one non-param tensor in and out per stage
        def carries(seg, prev_outputs):
            produced = {n for o in seg for n in o.output_arg_names}
            reads = []
            for o in seg:
                for n in o.input_arg_names:
                    if n in produced or is_param(n) or n in reads:
                        continue
                    reads.append(n)
            ext = [n for n in reads if prev_outputs is None or n in prev_outputs]
            return ext, produced

        prev_prod = None
        cins = []
        for s, seg in enumerate(segs):
            ext, produced = carries(seg, prev_prod)
            if len(ext) != 1:
                raise ValueError(
                    f"PipelineOptimizer: stage {s} must consume exactly one "
                    f"boundary tensor, found {ext}")
            cins.append(ext[0])
            prev_prod = produced
        # canonical carry-out: stage1's carry-in IS a stage0 product, and the
        # canonical block is stage0's ops verbatim — so its name is the carry
        cout0 = cins[1] if S > 1 else None
        # final output: the unique last-stage product read by the tail
        lo_last, hi_last = bounds[-1]
        tail_ops = ops[hi_last + 1:]
        last_prod = {n for o in segs[-1] for n in o.output_arg_names}
        tail_reads = [n for o in tail_ops for n in o.input_arg_names if n in last_prod]
        final_outs = list(dict.fromkeys(tail_reads))
        if len(final_outs) != 1:
            raise ValueError(
                f"PipelineOptimizer: the tail must read exactly one pipeline "
                f"output, found {final_outs}")
        final_out = final_outs[0]
        if S > 1:
            # positional analogue in stage0 must be cout0 (same slot chain)
            pos = None
            for oi, o in enumerate(segs[-1]):
                for slot, names in o.outputs.items():
                    if final_out in names:
                        pos = (oi, slot, names.index(final_out))
            canon_final = segs[0][pos[0]].outputs[pos[1]][pos[2]]
            if canon_final != cout0:
                raise ValueError(
                    "PipelineOptimizer: inter-stage carry and final output sit "
                    "at different positions in the stage body — stages must "
                    "chain through one tensor")
        else:
            pos = None
            for oi, o in enumerate(segs[0]):
                for slot, names in o.outputs.items():
                    if final_out in names:
                        pos = (oi, slot, names.index(final_out))
            cout0 = final_out

        # canonical sub-block = stage0's ops
        sub = program.create_block(parent_idx=0)
        program.rollback()
        for o in segs[0]:
            o.attrs.pop("pipeline_stage", None)
            o.block = sub
        sub.ops = list(segs[0])

        flat_params = [n for s in range(S) for n in stage_params[s]]
        head = ops[:bounds[0][0]]
        pipe_op_inputs = {"X": [cins[0]], "Params": flat_params}
        from .core.program import Operator

        pipe = Operator(block, "pipeline", pipe_op_inputs, {"Out": [final_out]},
                        {"sub_block": sub.idx, "num_stages": S,
                         "num_microbatches": self._num_microbatches,
                         "axis_name": self._axis_name,
                         "canonical_params": list(stage_params[0]),
                         "carry_in": cins[0], "carry_out": cout0})
        block.ops = head + [pipe] + tail_ops
        program._bump()


# reference exports both Xxx and XxxOptimizer names
SGD = SGDOptimizer
Momentum = MomentumOptimizer
Adam = AdamOptimizer
Adagrad = AdagradOptimizer
RMSProp = RMSPropOptimizer
Adamax = AdamaxOptimizer
Adadelta = AdadeltaOptimizer
Ftrl = FtrlOptimizer
Lamb = LambOptimizer
Dpsgd = DpsgdOptimizer
LarsMomentum = LarsMomentumOptimizer
DGCMomentum = DGCMomentumOptimizer
DecayedAdagrad = DecayedAdagradOptimizer
