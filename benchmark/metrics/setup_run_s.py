"""Seconds of set-up that are the self time of every other program span on the
caller's thread (`executor.execute`, `.fetch`, `.enqueue`, `.feed_place`,
`pipeline.*` of the warm-up steps): the start-up program's run, the `for_test`
clone's run and its copy to the host, the warm-up steps; less what
`executor.lower` and `executor.compile` took under them and less the foreign
compiles.
One partition with its four siblings: `benchmark/setup_timeline.py`."""
from benchmark import setup_timeline

LAYER = 'executor (core/executor.py)'
UNIT = 's'
BETTER = 'lower'
SOURCE = 'program_span'
MOVES = 'setup_s'


def read(ctx: dict):
    return setup_timeline.read_metric(ctx, setup_timeline.RUN)
