"""Program IR: Program / Block / Operator / Variable.

Reference counterparts: `framework/framework.proto:24-188` (ProgramDesc /
BlockDesc / OpDesc / VarDesc) and `python/paddle/fluid/framework.py`
(Variable:355, Operator:963, Block:1413, Program:2752, program_guard:3749).

Design differences from the reference (TPU-first):
  * The IR is *only* a build-time artifact.  Nothing interprets it op-by-op at
    runtime; the executor lowers a whole block to one JAX/XLA computation,
    compiles it once and caches it (see core/executor.py).  So ops carry no
    kernels — just a type, slot-named inputs/outputs and attrs, mirroring
    OpDesc (framework.proto:43).
  * Serialization is JSON (`Program.to_dict`/`from_dict`) instead of protobuf;
    the shape of the data matches ProgramDesc closely so a proto codec can be
    slotted in later without touching builders.
  * Every mutation bumps `Program.version`, which keys the executor's
    compile cache — the TPU analogue of the reference's
    `use_program_cache` (executor.py:564).
"""
from __future__ import annotations

import contextlib
import copy
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from ..monitor import MONITOR as _MON
from . import unique_name
from .dtypes import canonical_dtype


class Variable:
    """A named tensor slot inside a Block (reference: framework.py:355).

    shape uses -1 for the dynamic batch dimension; concrete shapes are bound
    at feed time and are part of the executor's compile-cache key.
    """

    def __init__(
        self,
        block: "Block",
        name: str,
        shape: Optional[Sequence[int]] = None,
        dtype: Any = "float32",
        lod_level: int = 0,
        persistable: bool = False,
        stop_gradient: bool = False,
        trainable: bool = False,
        is_data: bool = False,
        initializer=None,
        regularizer=None,
        error_clip=None,
    ):
        self.block = block
        self.name = name
        self.shape = tuple(int(s) for s in shape) if shape is not None else None
        self.dtype = canonical_dtype(dtype)
        self.lod_level = lod_level
        self.persistable = persistable
        self.stop_gradient = stop_gradient
        self.trainable = trainable
        self.is_data = is_data
        self.initializer = initializer
        self.regularizer = regularizer
        self.error_clip = error_clip
        # Filled by ops/layers for parity with `Variable.op` in the reference.
        self.op: Optional["Operator"] = None

    # --- convenience used by layers -------------------------------------
    @property
    def program(self) -> "Program":
        return self.block.program

    def astype(self, dtype):
        from ..layers import tensor as tensor_layers

        return tensor_layers.cast(self, dtype)

    # Python operator sugar (reference: framework.py monkey-patches these).
    def _binary(self, other, op):
        from ..layers import math_sugar

        return math_sugar.binary(self, other, op)

    def __add__(self, other):
        return self._binary(other, "elementwise_add")

    __radd__ = __add__

    def __sub__(self, other):
        return self._binary(other, "elementwise_sub")

    def __rsub__(self, other):
        from ..layers import math_sugar

        return math_sugar.binary(other, self, "elementwise_sub")

    def __mul__(self, other):
        return self._binary(other, "elementwise_mul")

    __rmul__ = __mul__

    def __truediv__(self, other):
        return self._binary(other, "elementwise_div")

    def __matmul__(self, other):
        from ..layers import nn

        return nn.matmul(self, other)

    def __neg__(self):
        from ..layers import math_sugar

        return math_sugar.binary(self, -1.0, "elementwise_mul")

    def __repr__(self):
        return (
            f"Variable(name={self.name!r}, shape={self.shape}, dtype={self.dtype}, "
            f"persistable={self.persistable})"
        )

    __str__ = __repr__

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "shape": list(self.shape) if self.shape is not None else None,
            "dtype": self.dtype,
            "lod_level": self.lod_level,
            "persistable": self.persistable,
            "stop_gradient": self.stop_gradient,
            "trainable": self.trainable,
            "is_data": self.is_data,
        }


class Parameter(Variable):
    """A trainable persistable Variable (reference: framework.py Parameter)."""

    def __init__(self, block, name, **kw):
        kw.setdefault("persistable", True)
        kw.setdefault("trainable", True)
        super().__init__(block, name, **kw)
        self.optimize_attr = kw.get("optimize_attr", {"learning_rate": 1.0})


class Operator:
    """One op descriptor (reference: framework.py:963 / OpDesc framework.proto:43).

    inputs/outputs map slot name -> list of variable names.  attrs are
    JSON-serializable python values.  Sub-blocks (control flow) are referenced
    by block index in attrs["sub_block"].
    """

    def __init__(
        self,
        block: "Block",
        type: str,
        inputs: Optional[Dict[str, List[str]]] = None,
        outputs: Optional[Dict[str, List[str]]] = None,
        attrs: Optional[Dict[str, Any]] = None,
    ):
        self.block = block
        self.type = type
        self.inputs = {k: list(v) for k, v in (inputs or {}).items()}
        self.outputs = {k: list(v) for k, v in (outputs or {}).items()}
        self.attrs = dict(attrs or {})

    def input(self, slot: str) -> List[str]:
        return self.inputs.get(slot, [])

    def output(self, slot: str) -> List[str]:
        return self.outputs.get(slot, [])

    @property
    def input_arg_names(self) -> List[str]:
        return [n for ns in self.inputs.values() for n in ns]

    @property
    def output_arg_names(self) -> List[str]:
        return [n for ns in self.outputs.values() for n in ns]

    def attr(self, name: str, default=None):
        return self.attrs.get(name, default)

    def __repr__(self):
        ins = {k: v for k, v in self.inputs.items()}
        outs = {k: v for k, v in self.outputs.items()}
        return f"Operator({self.type}, in={ins}, out={outs})"

    def to_dict(self) -> dict:
        return {
            "type": self.type,
            "inputs": self.inputs,
            "outputs": self.outputs,
            "attrs": _jsonify_attrs(self.attrs),
        }


def _jsonify_attrs(attrs: dict) -> dict:
    out = {}
    for k, v in attrs.items():
        if isinstance(v, np.ndarray):
            out[k] = {"__ndarray__": v.tolist(), "dtype": str(v.dtype)}
        elif isinstance(v, (np.integer,)):
            out[k] = int(v)
        elif isinstance(v, (np.floating,)):
            out[k] = float(v)
        else:
            out[k] = v
    return out


def _dejsonify_attrs(attrs: dict) -> dict:
    out = {}
    for k, v in attrs.items():
        if isinstance(v, dict) and "__ndarray__" in v:
            out[k] = np.array(v["__ndarray__"], dtype=v["dtype"])
        else:
            out[k] = v
    return out


class Block:
    """An ordered list of ops plus a var table (reference: framework.py:1413)."""

    def __init__(self, program: "Program", idx: int, parent_idx: int = -1):
        self.program = program
        self.idx = idx
        self.parent_idx = parent_idx
        self.vars: Dict[str, Variable] = {}
        self.ops: List[Operator] = []

    @property
    def parent_block(self) -> Optional["Block"]:
        if self.parent_idx < 0:
            return None
        return self.program.blocks[self.parent_idx]

    # --- vars ------------------------------------------------------------
    def create_var(self, name: Optional[str] = None, **kw) -> Variable:
        if name is None:
            name = unique_name.generate("tmp")
        if name in self.vars:
            return self.vars[name]
        v = Variable(self, name, **kw)
        self.vars[name] = v
        self.program._bump()
        return v

    def create_parameter(self, name: str, shape, dtype, **kw) -> Parameter:
        p = Parameter(self, name, shape=shape, dtype=dtype, **kw)
        self.vars[name] = p
        self.program._bump()
        return p

    def var(self, name: str) -> Variable:
        v = self._find_var_recursive(name)
        if v is None:
            raise KeyError(f"variable {name!r} not found in block {self.idx}")
        return v

    def has_var(self, name: str) -> bool:
        return self._find_var_recursive(name) is not None

    def _find_var_recursive(self, name: str) -> Optional[Variable]:
        blk: Optional[Block] = self
        while blk is not None:
            if name in blk.vars:
                return blk.vars[name]
            blk = blk.parent_block
        return None

    def all_parameters(self) -> List[Parameter]:
        return [v for v in self.vars.values() if isinstance(v, Parameter)]

    # --- ops -------------------------------------------------------------
    def append_op(self, type: str, inputs=None, outputs=None, attrs=None) -> Operator:
        from .registry import infer_and_check  # late import: registry needs Block

        op = Operator(self, type, _normalize_io(inputs), _normalize_io(outputs), attrs)
        if _device_guard_stage is not None and "pipeline_stage" not in op.attrs:
            op.attrs["pipeline_stage"] = _device_guard_stage
        if unique_name.scope_path() and "op_namescope" not in op.attrs:
            op.attrs["op_namescope"] = unique_name.scope_path()
        if _recompute_segment is not None and self.idx == 0:
            op.attrs["recompute_segment"] = _recompute_segment
        self.ops.append(op)
        infer_and_check(op, self)
        self.program._bump()
        return op

    def _insert_op(self, index: int, type: str, inputs=None, outputs=None, attrs=None) -> Operator:
        op = Operator(self, type, _normalize_io(inputs), _normalize_io(outputs), attrs)
        self.ops.insert(index, op)
        self.program._bump()
        return op

    def to_dict(self) -> dict:
        return {
            "idx": self.idx,
            "parent_idx": self.parent_idx,
            "vars": [v.to_dict() for v in self.vars.values()],
            "ops": [op.to_dict() for op in self.ops],
        }


def _normalize_io(io) -> Dict[str, List[str]]:
    """Accept {slot: Variable|name|list-of-either} and normalize to names."""
    if io is None:
        return {}
    out: Dict[str, List[str]] = {}
    for slot, v in io.items():
        if v is None:
            continue
        if not isinstance(v, (list, tuple)):
            v = [v]
        names = []
        for item in v:
            if isinstance(item, Variable):
                names.append(item.name)
            elif isinstance(item, str):
                names.append(item)
            else:
                raise TypeError(f"bad io entry for slot {slot!r}: {item!r}")
        out[slot] = names
    return out


class Program:
    """A list of Blocks; block 0 is global (reference: framework.py:2752)."""

    def __init__(self):
        import uuid

        self.blocks: List[Block] = [Block(self, 0)]
        # stable identity for executor compile-cache keys (id() can be reused
        # after gc; deepcopy in clone() gets a fresh one below)
        self._uuid = uuid.uuid4().hex
        self.current_block_idx = 0
        self.random_seed: Optional[int] = None
        self.version = 0
        # sharding hints attached by the parallel layer (mesh axis -> dim), and
        # the mesh whose axes they name (`parallel.shard_parameters(mesh=)`):
        # with both, `Executor.run` of this program places its state as hinted
        self.sharding_hints: Dict[str, Any] = {}
        self.sharding_mesh = None
        self.sharding_batch_axis = "dp"
        self._recompute_segments = 0   # `recompute_scope`s opened on this program
        self._seed_counter = 0

    def _bump(self):
        self.version += 1

    def block(self, index: int):
        """reference Program.block(index)."""
        return self.blocks[index]

    def to_string(self, throw_on_error=False, with_details=False):
        """reference Program.to_string: the serialized program text."""
        import json

        return json.dumps(self.to_dict(), indent=2, default=str)

    @staticmethod
    def parse_from_string(s: str):
        """reference Program.parse_from_string over the JSON serde."""
        import json

        return Program.from_dict(json.loads(s))

    def global_block(self) -> Block:
        return self.blocks[0]

    def current_block(self) -> Block:
        return self.blocks[self.current_block_idx]

    def create_block(self, parent_idx: Optional[int] = None) -> Block:
        parent = self.current_block_idx if parent_idx is None else parent_idx
        b = Block(self, len(self.blocks), parent)
        self.blocks.append(b)
        self.current_block_idx = b.idx
        self._bump()
        return b

    def rollback(self):
        self.current_block_idx = self.current_block().parent_idx

    def all_parameters(self) -> List[Parameter]:
        return self.global_block().all_parameters()

    def list_vars(self):
        for blk in self.blocks:
            yield from blk.vars.values()

    def clone(self, for_test: bool = False) -> "Program":
        """Deep-copy the program.  for_test=True switches is_test attrs on
        (dropout becomes identity, batch_norm uses running stats) and prunes
        the backward/optimizer tail, mirroring Program.clone(for_test=True)
        in the reference (framework.py:2752 area)."""
        import uuid

        with _MON.span("program.clone", source=self._uuid[:8],
                       for_test=for_test) as cloning:
            # the mesh is devices, not description: the clone refers to the same one (the memo hands it on as it is)
            p = copy.deepcopy(self, {id(self.sharding_mesh): self.sharding_mesh})
            p._uuid = uuid.uuid4().hex
            cloning.annotate(program=p._uuid[:8])
            if for_test:
                for blk in p.blocks:
                    cut = None
                    for i, op in enumerate(blk.ops):
                        if op.type == "backward":
                            cut = i
                            break
                        if "is_test" in op.attrs:
                            op.attrs["is_test"] = True
                    if cut is not None and blk.idx == 0:
                        blk.ops = blk.ops[:cut]
            p._bump()
        return p

    def to_dict(self) -> dict:
        return {
            "version": 1,
            "random_seed": self.random_seed,
            "blocks": [b.to_dict() for b in self.blocks],
        }

    @staticmethod
    def from_dict(d: dict) -> "Program":
        p = Program()
        p.random_seed = d.get("random_seed")
        p.blocks = []
        for bd in d["blocks"]:
            b = Block(p, bd["idx"], bd.get("parent_idx", -1))
            for vd in bd["vars"]:
                v = Variable(
                    b,
                    vd["name"],
                    shape=vd["shape"],
                    dtype=vd["dtype"],
                    lod_level=vd.get("lod_level", 0),
                    persistable=vd.get("persistable", False),
                    stop_gradient=vd.get("stop_gradient", False),
                    is_data=vd.get("is_data", False),
                )
                if vd.get("trainable"):
                    v.__class__ = Parameter
                    v.trainable = True
                    v.optimize_attr = {"learning_rate": 1.0}
                b.vars[v.name] = v
            for od in bd["ops"]:
                b.ops.append(
                    Operator(b, od["type"], od["inputs"], od["outputs"], _dejsonify_attrs(od["attrs"]))
                )
            p.blocks.append(b)
        if not p.blocks:
            p.blocks = [Block(p, 0)]
        p._bump()
        return p

    def __repr__(self):
        lines = [f"Program(version={self.version})"]
        for blk in self.blocks:
            lines.append(f"  Block {blk.idx} (parent {blk.parent_idx}):")
            for op in blk.ops:
                lines.append(f"    {op}")
        return "\n".join(lines)


# --- default program / guard machinery (reference: framework.py:3749) -----

_main_program = Program()
_startup_program = Program()
_device_guard_stage: Optional[int] = None


@contextlib.contextmanager
def device_guard(device=None):
    """Reference: framework.device_guard("gpu:0") — tags appended ops with a
    pipeline stage for PipelineOptimizer to cut on.  Accepts an int stage or
    a "gpu:N"/"tpu:N" string (device kind is irrelevant on a mesh; only the
    stage index survives)."""
    global _device_guard_stage
    prev = _device_guard_stage
    if device is None:
        _device_guard_stage = None
    elif isinstance(device, int):
        _device_guard_stage = device
    else:
        tail = str(device).rsplit(":", 1)[-1]
        # "cpu" / "gpu" with no index (reference accepts these): no stage tag
        _device_guard_stage = int(tail) if tail.isdigit() else None
    try:
        yield
    finally:
        _device_guard_stage = prev


def default_main_program() -> Program:
    return _main_program


def default_startup_program() -> Program:
    return _startup_program


@contextlib.contextmanager
def program_guard(main_program: Program, startup_program: Optional[Program] = None):
    global _main_program, _startup_program
    old_main, old_startup = _main_program, _startup_program
    _main_program = main_program
    if startup_program is not None:
        _startup_program = startup_program
    # every layer call of a model runs under this `with`: with the monitor
    # on it is the `program.build` span (a nested guard's is its child)
    mon_on = _MON.enabled
    ops0 = _op_count(main_program) if mon_on else 0
    with _MON.span("program.build", program=main_program._uuid[:8]) as building:
        try:
            yield
        finally:
            _main_program = old_main
            _startup_program = old_startup
            if mon_on:
                building.annotate(ops=_op_count(main_program) - ops0)


def _op_count(program: Program) -> int:
    return sum(len(blk.ops) for blk in program.blocks)


def switch_main_program(program: Program) -> Program:
    global _main_program
    old = _main_program
    _main_program = program
    return old


_recompute_segment: Optional[int] = None


@contextlib.contextmanager
def recompute_scope():
    """Marks the ops appended to the main block inside it as ONE segment that
    backward computes again: the lowering runs the segment as a
    `jax.checkpoint` (core/lowering.py: `run_ops`), so backward keeps what the
    segment READS and, of what it makes, only what is dear to make again and
    the chip has room for: matrix products' outputs and kernels' residuals,
    chosen once a trace from the program's shapes and the memory the step's
    state leaves free (core/lowering.py: `plan_kept`; nothing where the chip
    is full).  Everything else, the elementwise work, norms, gates, is made
    again from the layer's input and the kept values: the same numbers either
    way.  The ops stay ops of the block, with
    their own scopes, statistics and fetchable outputs; a `for_test` clone,
    which has no backward, runs them as they are.  Nested scopes are one
    segment, the outermost.  A segment's number is its program's own (the
    n-th scope opened on it), so a program built twice carries the same
    attributes whatever else the process built before."""
    global _recompute_segment
    if _recompute_segment is not None:
        yield
        return
    program = default_main_program()
    program._recompute_segments += 1
    _recompute_segment = program._recompute_segments
    try:
        yield
    finally:
        _recompute_segment = None


@contextlib.contextmanager
def name_scope(prefix=None):
    """reference framework.name_scope: prefixes generated op/var names for
    readability (debugging/graphviz), and, as the reference's `op_namescope`
    attribute does, marks the ops appended inside it: the lowering opens a
    `jax.named_scope` of the same path round each of them
    (core/lowering.py), so a device profile can tell a model's parts apart
    where their op types are the same.  Repeated sibling scopes dedup
    (encoder, encoder_1) and nesting composes (outer/inner); counters are NOT
    reset, so layers in identically-named scopes never collide."""
    from . import unique_name

    if prefix:
        with unique_name.name_scope_guard(prefix):
            yield
    else:
        yield
