"""Peak device memory of the train step as XLA planned it, per device:
arguments + outputs - aliased + temporaries of `compiled.memory_analysis()`.
The runtime's `peak_bytes_in_use` misses the temporaries (PR 21).  The room
left bounds the batch; a cell that no longer fits fails outright."""
LAYER = 'XLA: device'
UNIT = 'GB'
BETTER = 'lower'
SOURCE = 'program_counter'
MOVES = 'train_samples_per_s'


def read(ctx: dict):
    if not ctx["executables"]:
        return None
    from benchmark.run import executable_bytes

    return max(executable_bytes(e) for e in ctx["executables"]) / 1e9
