"""LayerHelper: shared plumbing for layer functions.

Reference: python/paddle/fluid/layer_helper.py — creates parameters in both
the main and startup programs, appends ops, applies the `act` attr.
"""
from __future__ import annotations

from typing import Optional

from . import unique_name
from .initializer import ConstantInitializer, XavierInitializer
from .param_attr import ParamAttr
from .program import default_main_program, default_startup_program

# Mixed-precision master-weight policy (r5 chip round fix):
# trainable parameters requested in a low-precision float are CREATED as
# float32 masters — every consuming op lowers through match_dtype, which
# casts the master to the activation dtype inside the compiled step, so the
# program still computes in bf16 on the MXU.  Without this the r4 bf16
# models created bf16 params, whose bf16 Adam beta-pow accumulators rounded
# 0.999 -> 1.0 and made lr_t = lr*sqrt(1-b2p)/(1-b1p) identically ZERO:
# bf16+Adam params silently never trained.  Toggle for experiments only.
_MASTER_WEIGHTS = True
_LOW_PRECISION = ("bfloat16", "float16", "fp16", "bf16")


def _master_dtype(dtype):
    if _MASTER_WEIGHTS and str(dtype) in _LOW_PRECISION:
        return "float32"
    return dtype


class LayerHelper:
    def __init__(self, layer_type: str, **kwargs):
        self.layer_type = layer_type
        self.kwargs = kwargs
        name = kwargs.get("name")
        self.name = name if name is not None else unique_name.generate(layer_type)

    @property
    def main_program(self):
        return default_main_program()

    @property
    def startup_program(self):
        return default_startup_program()

    @property
    def main_block(self):
        return self.main_program.current_block()

    def append_op(self, *args, **kw):
        return self.main_block.append_op(*args, **kw)

    def create_parameter(self, attr, shape, dtype, is_bias: bool = False, default_initializer=None):
        import copy

        # copy so a ParamAttr reused across layers doesn't get a name pinned
        # by the first layer (reference layer_helper_base.py does the same)
        attr = copy.copy(ParamAttr._to_attr(attr))
        if attr.name is None:
            attr.name = unique_name.generate(f"{self.name}.w" if not is_bias else f"{self.name}.b")
        init = attr.initializer or default_initializer
        if init is None:
            init = ConstantInitializer(0.0) if is_bias else XavierInitializer()
        shape = [int(s) for s in shape]
        dtype = _master_dtype(dtype)
        # parameter lives in the main program; its init op lives in startup
        param = self.main_program.global_block().create_parameter(
            attr.name,
            shape=shape,
            dtype=dtype,
            trainable=attr.trainable,
            regularizer=attr.regularizer,
        )
        param.optimize_attr = {"learning_rate": attr.learning_rate}
        startup_block = self.startup_program.global_block()
        sv = startup_block.create_var(attr.name, shape=shape, dtype=dtype, persistable=True)
        init(sv, startup_block)
        return param

    def create_variable_for_type_inference(self, dtype, shape=None):
        return self.main_block.create_var(
            unique_name.generate(f"{self.name}.tmp"), shape=shape, dtype=dtype
        )

    def append_activation(self, out):
        act = self.kwargs.get("act")
        if act is None:
            return out
        if isinstance(act, str):
            act = {"type": act}
        act_type = act.pop("type")
        res = self.create_variable_for_type_inference(out.dtype, shape=out.shape)
        self.append_op(act_type, inputs={"X": [out.name]}, outputs={"Out": [res.name]}, attrs=act)
        return res

    def append_bias_op(self, out, bias_attr, shape, dim_start: int = 1):
        if bias_attr is False:
            return out
        size = shape[-1] if isinstance(shape, (list, tuple)) else shape
        b = self.create_parameter(bias_attr, [int(size)], out.dtype, is_bias=True)
        res = self.create_variable_for_type_inference(out.dtype, shape=out.shape)
        self.append_op(
            "elementwise_add",
            inputs={"X": [out.name], "Y": [b.name]},
            outputs={"Out": [res.name]},
            attrs={"axis": dim_start},
        )
        return res
