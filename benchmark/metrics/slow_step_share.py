"""Share of the window's `pipeline_step` records whose `t_step_wall_s` is over
1.5 times their median: how often a step takes about two.  The record of
such a step says what the host did in it (`t_next_batch_s`, `t_dispatch_s`,
`t_host_blocked_s`)."""
from benchmark import program_trace

LAYER = 'entry: input (pipeline.train_loop, reader.DataLoader)'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'program_counter'
MOVES = 'train_samples_per_s'


def read(ctx: dict):
    first = ctx["traffic"].get("warmup_steps")
    if first is None:
        return None
    return program_trace.slow_step_share(
        program_trace.program_monitor().step_records(), first)
