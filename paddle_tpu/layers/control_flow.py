"""Control-flow layers (reference: python/paddle/fluid/layers/
control_flow.py — While:628, increment, array ops, less_than w/ cond out,
Switch; StaticRNN:278).

`While` keeps the reference's with-block builder API; the sub-block lowers
to one `lax.while_loop` (ops/control_flow_ops.py), so loops run on-device.
`Repeat` is the loop that can be trained through: one sub-block run a fixed
number of times over carried variables (`lax.scan`, which has a reverse mode
where `lax.while_loop` has none).
"""
from __future__ import annotations

from ..core import unique_name
from ..core.layer_helper import LayerHelper
from ..core.program import Variable, default_main_program


def increment(x, value=1.0, in_place=True):
    helper = LayerHelper("increment")
    if in_place:
        out = x
    else:
        out = helper.create_variable_for_type_inference(x.dtype, shape=x.shape)
    helper.append_op(
        "increment", inputs={"X": [x.name]}, outputs={"Out": [out.name]}, attrs={"step": float(value)}
    )
    return out


def less_than(x, y, cond=None):
    helper = LayerHelper("less_than")
    if cond is None:
        cond = helper.create_variable_for_type_inference("bool", shape=(1,))
    helper.append_op(
        "less_than", inputs={"X": [x.name], "Y": [y.name]}, outputs={"Out": [cond.name]}
    )
    return cond


def equal(x, y, cond=None):
    helper = LayerHelper("equal")
    if cond is None:
        cond = helper.create_variable_for_type_inference("bool", shape=(1,))
    helper.append_op("equal", inputs={"X": [x.name], "Y": [y.name]}, outputs={"Out": [cond.name]})
    return cond


def greater_than(x, y, cond=None):
    helper = LayerHelper("greater_than")
    if cond is None:
        cond = helper.create_variable_for_type_inference("bool", shape=(1,))
    helper.append_op(
        "greater_than", inputs={"X": [x.name], "Y": [y.name]}, outputs={"Out": [cond.name]}
    )
    return cond


class While:
    """reference control_flow.py:628.

    cond = layers.less_than(i, n)
    w = layers.While(cond)
    with w.block():
        ...body ops...
        layers.increment(i)
        layers.less_than(i, n, cond=cond)
    """

    def __init__(self, cond: Variable, is_test: bool = False, name: str = None):
        self.cond_var = cond
        self.helper = LayerHelper("while", name=name)

    def block(self):
        return _WhileBlockGuard(self)


class _WhileBlockGuard:
    def __init__(self, while_op: While):
        self.w = while_op
        self.main = default_main_program()

    def __enter__(self):
        self.parent_block = self.main.current_block()
        self.sub_block = self.main.create_block()
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            self.main.rollback()  # don't leave builders appending to a dead sub-block
            return False
        sub_idx = self.sub_block.idx
        self.main.rollback()
        # external inputs: names read in sub-block but defined outside
        defined = set()
        reads = []
        for op in self.sub_block.ops:
            for n in op.input_arg_names:
                if n not in defined:
                    reads.append(n)
            defined.update(op.output_arg_names)
        x_names = sorted({n for n in reads if self.parent_block.has_var(n)})
        self.parent_block.append_op(
            "while",
            inputs={"X": x_names, "Condition": [self.w.cond_var.name]},
            outputs={},
            attrs={"sub_block": sub_idx},
        )
        return False


class Repeat:
    """One sub-block run `times` times over the same outer variables, each
    pass reading what the last one left in the carried variables: a looped
    (weight-shared) stack of layers is `times` passes over one body.

        loop = layers.Repeat(times=4, recompute=True)
        with loop.block():
            x = loop.carry(h0)               # h0 on the first pass, then the last pass's update
            y = layers.fc(x, d)              # parameters and other outer variables are captured
            loop.update(x, y)
            loop.output(y)                   # stacked a pass
        ys = loop()                          # [times, *y.shape]
        last = loop.final(x)                 # the carried variable after the last pass

    The program holds ONE `repeat` op with one sub-block, lowered once to a
    `lax.scan` over the passes (ops/control_flow_ops.py), and it is
    differentiable: a parameter the body reads gets ONE gradient, the sum of
    its uses over the passes, accumulated in the parameter's dtype.  Shapes
    are the same on every pass (XLA's requirement).

    `recompute` is an attribute of the op, so a property of the `Program` as
    the gradients' fusion boundary is: backward then keeps only what each pass
    READS (the carried variables) and computes the pass's forward again, so
    the step holds one pass's activations, not `times` passes', for a second
    forward of the body.  `DynamicRNN` / `StaticRNN` do not serve: they scan
    the TIME axis of a step input and mask by each row's length, where this
    runs the same whole tensors through the body again."""

    def __init__(self, times: int, recompute: bool = False, name: str = None):
        if int(times) < 1:
            raise ValueError(f"Repeat: times={times!r}; a loop runs once at least")
        self.times = int(times)
        self.recompute = bool(recompute)
        self.helper = LayerHelper("repeat", name=name)
        self.main = default_main_program()
        self._carries = []   # dict(sub, init, update)
        self._outputs = []   # sub-block Variables
        self._sub_block = None
        self._out_vars = None
        self._final_vars = None

    def block(self):
        return _RepeatGuard(self)

    def _require_in_block(self):
        if self._sub_block is None or self.main.current_block() is not self._sub_block:
            raise RuntimeError("call inside `with loop.block():`")

    def carry(self, init: Variable) -> Variable:
        """The body's view of a carried variable: `init` on the first pass,
        afterwards what `update` named on the pass before."""
        self._require_in_block()
        sub = self._sub_block.create_var(unique_name.generate("repeat.carry"),
                                         shape=init.shape, dtype=init.dtype)
        self._carries.append({"sub": sub, "init": init, "update": None})
        return sub

    def update(self, carried: Variable, new: Variable):
        self._require_in_block()
        for c in self._carries:
            if c["sub"].name == carried.name:
                c["update"] = new
                return
        raise ValueError(f"{carried.name!r} is not a carried variable of this loop")

    def output(self, *outputs):
        """Body variables to keep from every pass, stacked on a new leading axis."""
        self._require_in_block()
        self._outputs.extend(outputs)

    def final(self, carried: Variable) -> Variable:
        """The carried variable after the last pass."""
        if self._final_vars is None:
            raise RuntimeError("Repeat block not finished")
        for c, v in zip(self._carries, self._final_vars):
            if c["sub"].name == carried.name:
                return v
        raise ValueError(f"{carried.name!r} is not a carried variable of this loop")

    def __call__(self):
        if self._out_vars is None:
            raise RuntimeError("Repeat block not finished")
        return self._out_vars[0] if len(self._out_vars) == 1 else self._out_vars

    def _finalize(self, parent_block, sub_block):
        if not self._carries:
            raise ValueError("Repeat needs at least one carried variable: without one every pass computes the same")
        for c in self._carries:
            if c["update"] is None:
                raise ValueError(f"carried variable {c['sub'].name!r} never updated")
        own = {c["sub"].name for c in self._carries}
        captured = [n for n in _external_reads(self.main, sub_block) if n not in own]
        self._out_vars = [
            parent_block.create_var(unique_name.generate("repeat.out"), dtype=o.dtype,
                                    shape=None if o.shape is None else (self.times,) + tuple(o.shape))
            for o in self._outputs]
        self._final_vars = [
            parent_block.create_var(unique_name.generate("repeat.final"), shape=c["sub"].shape, dtype=c["sub"].dtype)
            for c in self._carries]
        parent_block.append_op(
            "repeat",
            inputs={"Init": [c["init"].name for c in self._carries], "X": captured},
            outputs={"Out": [v.name for v in self._out_vars], "Final": [v.name for v in self._final_vars]},
            attrs={"sub_block": sub_block.idx, "times": self.times, "recompute": self.recompute,
                   "carry_vars": [c["sub"].name for c in self._carries],
                   "carry_updates": [c["update"].name for c in self._carries],
                   "out_vars": [o.name for o in self._outputs]})


def _external_reads(program, block) -> list:
    """Names the ops of `block` (and of the sub-blocks under them) read that no
    op of theirs wrote before: what the body captures from outside, sorted."""
    defined, reads = set(), set()

    def walk(blk):
        for op in blk.ops:
            reads.update(n for n in op.input_arg_names if n not in defined)
            sub = op.attrs.get("sub_block")
            if isinstance(sub, int):
                walk(program.blocks[sub])
            defined.update(op.output_arg_names)

    walk(block)
    return sorted(reads)


class _RepeatGuard:
    def __init__(self, loop: Repeat):
        self.loop = loop

    def __enter__(self):
        main = self.loop.main
        self.parent_block = main.current_block()
        self.loop._sub_block = main.create_block()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.loop.main.rollback()
        if exc_type is None:
            self.loop._finalize(self.parent_block, self.loop._sub_block)
        return False


def array_write(x, i, array=None):
    helper = LayerHelper("array_write")
    if array is None:
        array = helper.create_variable_for_type_inference(x.dtype)
        helper.append_op("create_array", outputs={"Out": [array.name]})
    inputs = {"X": [x.name], "I": [i.name], "Array": [array.name]}
    helper.append_op("array_write", inputs=inputs, outputs={"Out": [array.name]})
    return array


def create_array(dtype="float32"):
    helper = LayerHelper("create_array")
    array = helper.create_variable_for_type_inference(dtype)
    helper.append_op("create_array", outputs={"Out": [array.name]})
    return array


def array_read(array, i):
    helper = LayerHelper("array_read")
    out = helper.create_variable_for_type_inference(array.dtype)
    helper.append_op(
        "array_read", inputs={"X": [array.name], "I": [i.name]}, outputs={"Out": [out.name]}
    )
    return out


def array_length(array):
    helper = LayerHelper("array_length")
    out = helper.create_variable_for_type_inference("int32", shape=(1,))
    helper.append_op("array_length", inputs={"X": [array.name]}, outputs={"Out": [out.name]})
    return out


def cond(pred, true_fn, false_fn=None):
    """Modern two-branch conditional (maps to lax.cond).  Both branches
    build sub-blocks; returns the true branch's outputs (merged via
    select on the predicate)."""
    main = default_main_program()
    helper = LayerHelper("cond")

    parent = main.current_block()
    tb = main.create_block()
    t_out = true_fn()
    main.rollback()
    t_idx = tb.idx
    parent.append_op(
        "conditional_block",
        inputs={"Cond": [pred.name]},
        outputs={},
        attrs={"sub_block": t_idx},
    )
    if false_fn is None:
        return t_out
    fb = main.create_block()
    f_out = false_fn()
    main.rollback()
    # invert predicate
    not_pred = helper.create_variable_for_type_inference("bool", shape=pred.shape)
    helper.append_op("logical_not", inputs={"X": [pred.name]}, outputs={"Out": [not_pred.name]})
    parent.append_op(
        "conditional_block",
        inputs={"Cond": [not_pred.name]},
        outputs={},
        attrs={"sub_block": fb.idx},
    )
    if t_out is None or f_out is None:
        return t_out
    single = not isinstance(t_out, (list, tuple))
    t_list = [t_out] if single else list(t_out)
    f_list = [f_out] if single else list(f_out)
    outs = []
    for tv, fv in zip(t_list, f_list):
        sel = helper.create_variable_for_type_inference(tv.dtype, shape=tv.shape)
        mask = helper.create_variable_for_type_inference("int32", shape=(1,))
        helper.append_op("cast", inputs={"X": [pred.name]}, outputs={"Out": [mask.name]},
                         attrs={"out_dtype": "int32"})
        helper.append_op(
            "select_input",
            inputs={"X": [fv.name, tv.name], "Mask": [mask.name]},
            outputs={"Out": [sel.name]},
        )
        outs.append(sel)
    return outs[0] if single else outs
