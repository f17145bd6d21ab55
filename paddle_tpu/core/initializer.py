"""Initializers: append init ops to the startup program.

Reference: python/paddle/fluid/initializer.py (Constant, Uniform, Normal,
TruncatedNormal, Xavier, MSRA, Bilinear, NumpyArrayInitializer).  Same
model: an initializer appends one op writing the parameter into the startup
block; the executor runs the startup program once and the arrays land in the
Scope as device buffers.
"""
from __future__ import annotations

import numpy as np


class Initializer:
    def __call__(self, var, block):
        raise NotImplementedError


class ConstantInitializer(Initializer):
    def __init__(self, value: float = 0.0):
        self.value = value

    def __call__(self, var, block):
        return block.append_op(
            "fill_constant",
            outputs={"Out": [var.name]},
            attrs={"shape": list(var.shape), "dtype": var.dtype, "value": float(self.value)},
        )


class UniformInitializer(Initializer):
    def __init__(self, low: float = -1.0, high: float = 1.0, seed: int = 0):
        self.low, self.high, self.seed = low, high, seed

    def __call__(self, var, block):
        return block.append_op(
            "uniform_random",
            outputs={"Out": [var.name]},
            attrs={
                "shape": list(var.shape),
                "dtype": var.dtype,
                "min": self.low,
                "max": self.high,
                "seed": self.seed,
            },
        )


class NormalInitializer(Initializer):
    def __init__(self, loc: float = 0.0, scale: float = 1.0, seed: int = 0):
        self.loc, self.scale, self.seed = loc, scale, seed

    def __call__(self, var, block):
        return block.append_op(
            "gaussian_random",
            outputs={"Out": [var.name]},
            attrs={
                "shape": list(var.shape),
                "dtype": var.dtype,
                "mean": self.loc,
                "std": self.scale,
                "seed": self.seed,
            },
        )


class TruncatedNormalInitializer(Initializer):
    def __init__(self, loc: float = 0.0, scale: float = 1.0, seed: int = 0):
        self.loc, self.scale, self.seed = loc, scale, seed

    def __call__(self, var, block):
        return block.append_op(
            "truncated_gaussian_random",
            outputs={"Out": [var.name]},
            attrs={
                "shape": list(var.shape),
                "dtype": var.dtype,
                "mean": self.loc,
                "std": self.scale,
                "seed": self.seed,
            },
        )


def _fans(var):
    shape = var.shape
    if len(shape) < 2:
        return int(shape[0]), int(shape[0])
    receptive = int(np.prod(shape[2:])) if len(shape) > 2 else 1
    fan_in = int(shape[1]) * receptive if len(shape) > 2 else int(shape[0])
    fan_out = int(shape[0]) * receptive if len(shape) > 2 else int(shape[1])
    return fan_in, fan_out


class XavierInitializer(Initializer):
    def __init__(self, uniform: bool = True, fan_in=None, fan_out=None, seed: int = 0):
        self.uniform, self.fan_in, self.fan_out, self.seed = uniform, fan_in, fan_out, seed

    def __call__(self, var, block):
        fi, fo = _fans(var)
        fi = self.fan_in if self.fan_in is not None else fi
        fo = self.fan_out if self.fan_out is not None else fo
        if self.uniform:
            limit = float(np.sqrt(6.0 / (fi + fo)))
            return UniformInitializer(-limit, limit, self.seed)(var, block)
        std = float(np.sqrt(2.0 / (fi + fo)))
        return NormalInitializer(0.0, std, self.seed)(var, block)


class MSRAInitializer(Initializer):
    def __init__(self, uniform: bool = True, fan_in=None, seed: int = 0):
        self.uniform, self.fan_in, self.seed = uniform, fan_in, seed

    def __call__(self, var, block):
        fi, _ = _fans(var)
        fi = self.fan_in if self.fan_in is not None else fi
        if self.uniform:
            limit = float(np.sqrt(6.0 / fi))
            return UniformInitializer(-limit, limit, self.seed)(var, block)
        std = float(np.sqrt(2.0 / fi))
        return NormalInitializer(0.0, std, self.seed)(var, block)


class NumpyArrayInitializer(Initializer):
    def __init__(self, value: np.ndarray):
        self.value = np.asarray(value)

    def __call__(self, var, block):
        return block.append_op(
            "assign_value",
            outputs={"Out": [var.name]},
            attrs={"values": self.value, "dtype": var.dtype, "shape": list(self.value.shape)},
        )


class SoftplusInverseLogUniformInitializer(Initializer):
    """b with softplus(b) log-uniform on [low, high]: a state-space layer's step
    bias as Mamba initialises it (dt = exp(U(ln low, ln high)), b = dt +
    ln(1 - exp(-dt)), the inverse of the softplus the layer applies).  Ops of
    the start-up program: one draw and five elementwise ops on it."""

    def __init__(self, low: float = 1e-3, high: float = 1e-1, seed: int = 0):
        self.low, self.high, self.seed = float(low), float(high), seed

    def __call__(self, var, block):
        from . import unique_name

        def step(kind, source, **attrs):
            out = block.create_var(unique_name.generate(f"{var.name}.init"), shape=var.shape, dtype=var.dtype)
            block.append_op(kind, inputs={"X": [source.name]}, outputs={"Out": [out.name]}, attrs=attrs)
            return out

        drawn = block.create_var(unique_name.generate(f"{var.name}.init"), shape=var.shape, dtype=var.dtype)
        UniformInitializer(float(np.log(self.low)), float(np.log(self.high)), self.seed)(drawn, block)
        dt = step("exp", drawn)
        tail = step("log", step("scale", step("exp", step("scale", dt, scale=-1.0)), scale=-1.0, bias=1.0))
        return block.append_op("elementwise_add", inputs={"X": [dt.name], "Y": [tail.name]},
                               outputs={"Out": [var.name]}, attrs={"axis": -1})


# reference-style aliases (initializer.py exports these names)
Constant = ConstantInitializer
Uniform = UniformInitializer
Normal = NormalInitializer
TruncatedNormal = TruncatedNormalInitializer
Xavier = XavierInitializer
MSRA = MSRAInitializer


class BilinearInitializer(Initializer):
    """reference initializer.py BilinearInitializer: bilinear upsampling
    kernel for conv_transpose weights [c_out, c_in, k, k]."""

    def _value(self, shape, dtype):
        import numpy as np

        # the value depends only on the last two axes: build one k x k tile
        # and broadcast it (O(k^2), not O(prod(shape)))
        kh, kw = shape[-2], shape[-1]
        f = int(np.ceil(kw / 2.0))
        c = (2 * f - 1 - f % 2) / (2.0 * f)
        xs = 1 - np.abs(np.arange(kw) / f - c)
        ys = 1 - np.abs(np.arange(kh) / f - c)
        tile = np.outer(ys, xs).astype("float32")
        return np.broadcast_to(tile, shape).astype(dtype).copy()

    def __call__(self, var, block):
        import numpy as np

        value = self._value(tuple(int(d) for d in var.shape), "float32")
        block.append_op(
            "assign_value",
            outputs={"Out": [var.name]},
            attrs={"shape": list(value.shape), "dtype": "float32",
                   "values": value.reshape(-1).tolist()},
        )


def force_init_on_cpu():
    """reference initializer.force_init_on_cpu: always False here — there
    is no separate CPU init placement under XLA (PJRT owns placement)."""
    return False


class init_on_cpu:
    """reference initializer.init_on_cpu context: accepted no-op (PJRT owns
    placement)."""

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False
