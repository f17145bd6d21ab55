"""The `kda` ops of the train step whose log decay is ONE number a head a token
([b, T, H]; Gated DeltaNet) and not one a channel ([b, T, H, K]; Kimi Delta
Attention): the program's trace-time counter `lowering.scalar_decay_scans`
(core/lowering.py: `count_layer_forms`, counted once a trace of a program with
a backward pass, from the program's own ops, by the rank of each op's `G`).
3 in Qwen3-Next's cell, whose kernels then take the decay out of the chunk's
Grams and read it as [b, T, H]; a change that quietly writes the decay out over
the 128 channels again reads fewer.  Nothing where the counter is absent or 0 (a
parent without it, Kimi Linear's cell, a program without the op)."""
from benchmark import program_trace

LAYER = 'lowering (core/lowering.py)'
UNIT = 'count'
BETTER = 'higher'
SOURCE = 'program_counter'
MOVES = 'train_samples_per_s'


def read(ctx: dict):
    if "traffic" not in ctx:
        return None
    return program_trace.program_monitor().counter_values().get("lowering.scalar_decay_scans") or None
