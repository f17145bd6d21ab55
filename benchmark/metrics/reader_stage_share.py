"""Busy time of the loader's producer thread (its `reader.stage` spans: one
batch validated, cast and handed to the device) over the loop's time in the
window.  100% is the loader's Python at its limit.  `device_put` only queues
the copy: the runtime's threads that lay the bytes out and send them are in no
program span (PERF.md, section 5), so the whole loader is nearer its limit
than this share says."""
from benchmark import program_trace

LAYER = 'entry: input (pipeline.train_loop, reader.DataLoader)'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'program_span'
MOVES = 'train_samples_per_s'


def read(ctx: dict):
    return program_trace.read_loop_metric(ctx, "reader_stage_share")
