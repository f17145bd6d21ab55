"""Share of the loop's time in the window that its thread spent inside the
program's `pipeline.next_batch` span, the pull from the loader: near 0 while
the producer thread keeps ahead.  `loader_wait_share`'s twin from inside: the
same wait, on the program's own clock, without a wrapped loader."""
from benchmark import program_trace

LAYER = 'entry: input (pipeline.train_loop, reader.DataLoader)'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'program_span'
MOVES = 'train_samples_per_s'


def read(ctx: dict):
    return program_trace.read_loop_metric(ctx, "next_batch_wait_share")
