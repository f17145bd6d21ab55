"""python3 tools/lowered_hash.py [cell ...]: the module name and the sha256 of each one-chip train cell's step as THIS
checkout lowers it for the TPU (StableHLO; the Mosaic kernels' serialised bodies stripped: they hold the checkout's
paths).  Nothing is compiled or run, so it needs no chip: a cell takes from ten seconds to a minute on a CPU.

Run in a `git archive` of the parent and in the tree, it says whether an edit to shared code changed a bystander
cell's program at all: equal lines mean the cell's compiled step, and so its numbers, cannot have moved.
`tests/test_lowering_one_path.py` pins five cells through `lowered`.  The four-chip cells are left out: BERT's dp4 cell
is the one-chip cell's program on a mesh, Jamba's is pinned by `tests/test_chip_compile.py`."""
import hashlib
import os
import re
import sys
from collections import defaultdict
from unittest import mock

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import jax  # noqa: E402
import numpy as np  # noqa: E402

#: a Mosaic custom call's serialised body inside the lowered text's escaped `backend_config`
KERNEL_BODY = re.compile(r'(\\22body\\22: \\22)[A-Za-z0-9+/=]+')


def one_chip_train_cells():
    """The names of the manifest's one-chip train cells, in its order."""
    from benchmark import manifest as mf

    manifest = mf.load()
    return [w["name"] for w in manifest["workloads"]
            if w["chips"] == 1 and mf.read_json(mf.traffic_path(w["traffic"])).get("kind") == "train"]


def traced_step(cell):
    """(module name, the traced step) of the program the benchmark builds for `cell`, on shapes alone."""
    import paddle_tpu as fluid
    from benchmark import manifest as mf
    from paddle_tpu.core import executor as ex

    manifest = mf.load()
    workload = mf.cell(manifest, cell)
    cfg, job = mf.config_of(manifest, workload), mf.read_json(mf.traffic_path(workload["traffic"]))
    model = mf.model_module(cfg)
    # an inner `jax.jit` traced before in this process is found again in JAX's caches, and functions that share one
    # traced object lower to one private function: the text's numbering would depend on what the process ran before
    jax.clear_caches()
    # names come from process-wide tables (the parameters' counters; a `name_scope` met a second time in a process is
    # numbered and the ops carry it): tables of this call's own, so that a cell's line does not depend on what the
    # process built before it
    with fluid.unique_name.guard(), mock.patch.object(fluid.unique_name, "_scope_children", defaultdict(lambda: defaultdict(int))):
        main, startup, feeds, loss, _ = model.build(cfg, job)
    main.random_seed = startup.random_seed = 3
    # the state the start-up program would make, as shapes: nothing runs
    scope = fluid.Scope()
    for v in startup.global_block().vars.values():
        if v.persistable:
            scope.set_var(v.name, jax.ShapeDtypeStruct(tuple(v.shape), v.dtype))
    shapes = {n: jax.ShapeDtypeStruct(tuple(job["batch_per_chip"] if d == -1 else d for d in feeds[n].shape),
                                      np.int32 if "int" in str(feeds[n].dtype) else feeds[n].dtype)
              for n in model.FEEDS}
    step = ex._CompiledStep(main, list(shapes), [loss.name], scope, platform="tpu",
                            feed_shapes={n: s.shape for n, s in shapes.items()})
    as_shape = lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype)  # noqa: E731
    return step.module, step.jfn.trace({n: as_shape(scope.find_var(n)) for n in step.rw_names},
                                       {n: as_shape(scope.find_var(n)) for n in step.ro_names},
                                       shapes, as_shape(jax.random.PRNGKey(0)))


def lowered(cell):
    """(module name, sha256 of the step lowered for the TPU, without the kernels' bodies)."""
    module, traced = traced_step(cell)
    text = KERNEL_BODY.sub(r"\1", traced.lower(lowering_platforms=("tpu",)).as_text())
    return module, hashlib.sha256(text.encode()).hexdigest()


def main(argv):
    for cell in argv or one_chip_train_cells():
        print(cell, *lowered(cell), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
