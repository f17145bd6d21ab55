"""ResNet for ImageNet: how the benchmark builds it through the framework
(to train and to serve), a plain float32 reference of the same architecture,
and the operations one image needs.

Architecture: He et al. 2015 (arXiv:1512.03385), Table 1, bottleneck blocks
[3, 4, 6, 3] at depth 50, the published 7x7/2 stem.  Departures of the
program under test (`paddle_tpu.models.resnet.build`), which the reference
follows so that the two compute the same function:

  * the stride of a down-sampling bottleneck sits on its 3x3 convolution
    (the "v1.5" variant most frameworks train), not on the first 1x1;
  * batch-norm epsilon 1e-5, momentum 0.9, no bias on any convolution;
  * the reference runs inference: batch norm with the running statistics
    (the `for_test` clone of the train program, or the saved model).
"""
from __future__ import annotations

import numpy as np

FEEDS = ("img", "label")
STAGES = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}
#: Reference check: max |program logit - reference logit| over the largest
#: |reference logit|, on 8 seeded images (train) or 32 sampled responses
#: (serve).  The program rounds every activation of 53 convolutions and the
#: classifier's output to bf16 (2**-9 relative each) over f32 master
#: weights and f32 batch-norm arithmetic; measured on the chip the logits
#: agree to a few 1e-3 (PERF.md, Findings).  A dropped or reordered layer, a
#: wrong running statistic or an int8 path is off by 1e-1 and more; weights
#: themselves stored in bf16 would double the error and fail.
REFERENCE_RTOL = 2e-2


def _build(cfg: dict, job: dict, train: bool):
    from paddle_tpu.models import resnet

    return resnet.build(
        depth=cfg["depth"], class_dim=cfg["num_classes"],
        image_shape=(3, cfg["image_size"], cfg["image_size"]),
        learning_rate=job.get("learning_rate", 0.1),
        momentum=job.get("momentum", 0.9), with_optimizer=train,
        is_test=not train, dtype=cfg["compute_dtype"])


def build(cfg: dict, job: dict):
    """(main, startup, feed variables by name, loss variable, the variable
    the reference is compared on) of the train program, with the zoo's
    default stem and layout."""
    main, startup, feeds, fetches = _build(cfg, job, train=True)
    return main, startup, feeds, fetches["loss"], [fetches["logits"].name]


def build_inference(cfg: dict, job: dict):
    """(main, startup, feed names, logits variable) of the program that is
    saved with `io.save_inference_model` and served."""
    main, startup, _, fetches = _build(cfg, job, train=False)
    return main, startup, ["img"], fetches["logits"]


def make_batch(rng: np.random.RandomState, cfg: dict, job: dict,
               rows: int) -> dict:
    """One host batch as a reader yields it: float32 images in [0, 1) and
    int64 labels."""
    size = cfg["image_size"]
    img = rng.random_sample((rows, 3, size, size)).astype("float32")
    label = rng.randint(0, cfg["num_classes"], size=(rows, 1)).astype("int64")
    return {"img": img, "label": label}


def _convs(cfg: dict):
    """(c_in, c_out, kernel, stride, input height) of every convolution in
    the order the program creates them: stem, then per block the shortcut
    (where the shape changes), 1x1, 3x3, 1x1."""
    h = cfg["image_size"]
    out = [(3, 64, 7, 2, h)]
    h = h // 2 // 2  # stem stride, then the 3x3/2 max pool
    c_in = 64
    for stage, blocks in enumerate(STAGES[cfg["depth"]]):
        width = 64 * 2 ** stage
        for b in range(blocks):
            stride = 2 if (b == 0 and stage > 0) else 1
            if c_in != width * 4 or stride != 1:
                out.append((c_in, width * 4, 1, stride, h))
            out.append((c_in, width, 1, 1, h))
            out.append((width, width, 3, stride, h))
            h //= stride
            out.append((width, width * 4, 1, 1, h))
            c_in = width * 4
    return out


def flops_per_sample(cfg: dict, job: dict) -> float:
    """Operations the forward and backward passes of one image require:
    convolutions and the classifier, 2 per multiply-add, backward twice
    the forward.  (8.2e9 forward at depth 50 and 224x224: the "4.1 GFLOPs"
    usually quoted counts multiply-adds.)"""
    forward = 2.0 * 2048 * cfg["num_classes"]
    for c_in, c_out, k, stride, h in _convs(cfg):
        forward += 2.0 * c_in * c_out * k * k * (h // stride) ** 2
    return 3.0 * forward


def parameter_names(program) -> dict:
    """The program's parameter names in creation order, by the op that reads
    them: {"conv": [filter, ...], "bn": [(scale, bias, mean, variance),
    ...], "fc": (weight, bias)}.  Names come from global counters, so they
    are read from the program and never assumed."""
    ops = program.global_block().ops
    conv = [op.inputs["Filter"][0] for op in ops if op.type == "conv2d"]
    bn = [tuple(op.inputs[k][0] for k in ("Scale", "Bias", "Mean", "Variance"))
          for op in ops if op.type == "batch_norm"]
    i = max(j for j, op in enumerate(ops) if op.type == "mul")
    return {"conv": conv, "bn": bn,
            "fc": (ops[i].inputs["Y"][0], ops[i + 1].inputs["Y"][0])}


def reference(params: dict, batch: dict, cfg: dict, program):
    """(logits,) of `batch["img"]` in plain float32 jax.numpy; `params` maps
    parameter names to arrays, and `program` says which name is which
    layer's (`parameter_names`)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    names = parameter_names(program)
    conv_names, bn_names = iter(names["conv"]), iter(names["bn"])

    def p(name):
        return jnp.asarray(params[name], jnp.float32)

    def conv_bn(x, stride, pad, relu=True):
        x = lax.conv_general_dilated(
            x, p(next(conv_names)), (stride, stride), [(pad, pad)] * 2,
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        scale, bias, mean, var = (p(n)[None, :, None, None]
                                  for n in next(bn_names))
        x = (x - mean) / jnp.sqrt(var + 1e-5) * scale + bias
        return jnp.maximum(x, 0.0) if relu else x

    with jax.default_matmul_precision("highest"):
        x = conv_bn(jnp.asarray(batch["img"], jnp.float32), 2, 3)
        x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                              [(0, 0), (0, 0), (1, 1), (1, 1)])
        for stage, blocks in enumerate(STAGES[cfg["depth"]]):
            width = 64 * 2 ** stage
            for b in range(blocks):
                stride = 2 if (b == 0 and stage > 0) else 1
                short = x
                if x.shape[1] != width * 4 or stride != 1:
                    short = conv_bn(x, stride, 0, relu=False)
                y = conv_bn(x, 1, 0)
                y = conv_bn(y, stride, 1)
                y = conv_bn(y, 1, 0, relu=False)
                x = jnp.maximum(short + y, 0.0)
        w, b = names["fc"]
        return (x.mean((2, 3)) @ p(w) + p(b),)


def reference_error(got, want) -> float:
    """How far the program's logits are from the reference's, as
    `REFERENCE_RTOL` counts it.  `got` and `want` are one-element
    sequences (the variables `build` names), or the arrays themselves."""
    got = np.asarray(got[0] if isinstance(got, (list, tuple)) else got, "f8")
    want = np.asarray(want[0] if isinstance(want, (list, tuple)) else want, "f8")
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-6))
