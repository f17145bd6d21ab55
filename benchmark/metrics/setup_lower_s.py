"""Seconds of set-up under the executor's `executor.prepare` (its children
`analysis.verify`, `analysis.plan`, `executor.build`) and `executor.lower`
spans on the caller's thread, the observed `jax.trace` and `jax.lower` events
inside them included: verifying, planning, building the step and tracing it
to StableHLO.  Python that no compile cache serves: what `setup_compile_s`
keeps on a warm start beside the cache loads.
One partition with its four siblings: `benchmark/setup_timeline.py`."""
from benchmark import setup_timeline

LAYER = 'executor (core/executor.py)'
UNIT = 's'
BETTER = 'lower'
SOURCE = 'program_span'
MOVES = 'setup_s'


def read(ctx: dict):
    return setup_timeline.read_metric(ctx, setup_timeline.LOWER)
