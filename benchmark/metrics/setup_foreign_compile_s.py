"""Seconds of set-up the caller's thread spent in JAX traces, lowerings and
backend compiles (the monitor's observed `jax.trace`, `jax.lower`,
`jax.backend_compile` events, the `jax.cache_load` inside the last counted
once) that neither `executor.lower` nor `executor.compile` encloses: compiles
asked for beside the executor, by the caller (the benchmark's reference and
probe) or by the program's own host code (an eager `jnp` call in the scope,
the loader, the feed path).
One partition with its four siblings: `benchmark/setup_timeline.py`."""
from benchmark import setup_timeline

LAYER = 'executor (core/executor.py)'
UNIT = 's'
BETTER = 'lower'
SOURCE = 'program_span'
MOVES = 'setup_s'


def read(ctx: dict):
    return setup_timeline.read_metric(ctx, setup_timeline.FOREIGN)
