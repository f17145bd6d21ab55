"""The program's `executor.compile_cache_miss` counter at the end of the run:
how many of the executor's own compiles JAX's persistent cache did not serve
(a `correct` run compiles nothing in its window, so all of them are set-up).
Falls to 0 on a cell's second run in a checkout, where `setup_compile_s` is
then cache loads and tracing only."""
from benchmark import program_trace

LAYER = 'executor (core/executor.py)'
UNIT = 'count'
BETTER = 'lower'
SOURCE = 'program_counter'
MOVES = 'setup_s'

MISS = "executor.compile_cache_miss"


def read(ctx: dict):
    if not ctx["monitor"].get("setup"):
        return None
    counters = program_trace.program_monitor().counter_values()
    if "executor.compile_cache_hit" not in counters and MISS not in counters:
        return None  # a program that does not tell a cache load from a compile
    return counters.get(MISS, 0)
