"""Importing this package registers all op lowerings."""
from . import (  # noqa: F401
    control_flow_ops,
    detection_ops,
    linear_attention_ops,
    math_ops,
    misc_ops,
    moe_ops,
    nn_ops,
    optimizer_ops,
    pipeline_ops,
    sequence_ops,
    sparse_index_ops,
    ssd_ops,
    ssm_ops,
    tail_ops,
    tensor_ops,
)
