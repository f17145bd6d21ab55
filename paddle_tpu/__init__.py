"""paddle_tpu: a TPU-native framework with PaddlePaddle-Fluid capabilities.

Public surface mirrors `paddle.fluid` (reference: python/paddle/fluid/
__init__.py) so reference-era programs port by changing the import:

    import paddle_tpu as fluid
    x = fluid.layers.data("x", [784])
    ...
    exe = fluid.Executor(fluid.TPUPlace(0))
"""
from . import ops  # registers all op lowerings  # noqa: F401
from . import layers  # noqa: F401
from . import optimizer  # noqa: F401
from .core import initializer, regularizer, unique_name  # noqa: F401
from .core.autodiff import append_backward, calc_gradient  # noqa: F401
from . import backward  # noqa: F401
from .backward import gradients  # noqa: F401
from . import evaluator  # noqa: F401
from .core.executor import CUDAPinnedPlace, cpu_places, cuda_pinned_places, cuda_places, CPUPlace, CUDAPlace, Executor, TPUPlace  # noqa: F401
from .core.param_attr import ParamAttr, WeightNormParamAttr  # noqa: F401
from .core.program import (  # noqa: F401
    Program,
    Variable,
    default_main_program,
    default_startup_program,
    device_guard,
    name_scope,
    program_guard,
    recompute_scope,
)
from .core.scope import Scope, global_scope, scope_guard  # noqa: F401
from . import parallel  # noqa: F401
from . import param_server  # noqa: F401
from .parallel import BuildStrategy, CompiledProgram, ExecutionStrategy, ParallelExecutor  # noqa: F401
from . import parallel as compiler  # reference exposes fluid.compiler.CompiledProgram  # noqa: F401
from . import clip  # noqa: F401
from . import io  # noqa: F401
from .lod import LoDTensor, LoDTensorArray, create_lod_tensor  # noqa: F401
from . import models  # noqa: F401
from . import reader  # noqa: F401
from .reader import DataFeeder, DataLoader, PyReader  # noqa: F401
from . import contrib  # mixed_precision decorator etc.  # noqa: F401
from . import flags  # noqa: F401
from .flags import get_flags, set_flags  # noqa: F401
from . import dataset  # noqa: F401
from .dataset import InMemoryDataset, QueueDataset  # noqa: F401
from . import inference  # noqa: F401
from . import recordio  # noqa: F401
from . import datasets  # noqa: F401
from . import nets  # noqa: F401
from . import debugger  # noqa: F401
from . import install_check  # noqa: F401
from .checkpoint_manager import CheckpointManager  # noqa: F401
from . import fleet as _fleet_mod  # noqa: F401
from .fleet import fleet  # the singleton (reference incubate.fleet)  # noqa: F401
from . import transpiler  # noqa: F401
from .transpiler import DistributeTranspiler, DistributeTranspilerConfig  # noqa: F401
from .core import passes  # noqa: F401
from .core import analysis  # static program verifier/lints (ISSUE 6)  # noqa: F401
from .core import resource_plan  # static peak-HBM/cost planner (ISSUE 12)  # noqa: F401
from . import dygraph  # noqa: F401
from . import dygraph_grad_clip  # noqa: F401
from . import recordio_writer  # noqa: F401
from . import metrics  # noqa: F401
from . import monitor  # noqa: F401  (observability: spans/counters/exporters)
from . import profiler  # noqa: F401  (compat facade over monitor)
from . import pipeline  # noqa: F401  (overlapped train_loop driver)
from .pipeline import train_loop  # noqa: F401
from .core.executor import FetchHandle  # noqa: F401
from . import errors  # noqa: F401  (failure taxonomy: classify + classes)
from . import faults  # noqa: F401  (deterministic fault injection)
from . import resilience  # noqa: F401  (fault-tolerant train loop)
from .faults import FaultInjector  # noqa: F401
from .resilience import (RetryPolicy, ResilienceStats,  # noqa: F401
                         resilient_train_loop)
from . import dist_resilience  # noqa: F401  (heartbeats + collective watchdog)
from . import integrity  # noqa: F401  (silent-corruption sentinel)
from . import serving  # noqa: F401  (continuous-batching model server)
from . import chaos  # noqa: F401  (seeded multi-fault campaign engine)
# paddle_tpu.launch (the gang launcher) is deliberately NOT imported here:
# `python -m paddle_tpu.launch` would re-execute an already-imported module
# (runpy RuntimeWarning); import it explicitly where needed.

__version__ = "0.1.0"


def in_dygraph_mode():
    """reference fluid.in_dygraph_mode."""
    from .dygraph import base as _dy

    return _dy.enabled()


def create_random_int_lodtensor(recursive_seq_lens, base_shape, place, low, high):
    """reference fluid.create_random_int_lodtensor."""
    import numpy as np

    seqs = [np.random.randint(low, high + 1, (ln,) + tuple(base_shape)).astype("int64")
            for ln in recursive_seq_lens[0]]
    return LoDTensor(seqs)


from .transpiler import memory_optimize, release_memory  # noqa: F401,E402
