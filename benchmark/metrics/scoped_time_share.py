"""Share of the device's operation time whose HLO instruction carries one of
the lowering's `op<idx>:<type>` scopes in the compiled program's metadata.
It moves no end-to-end metric by itself: it says how much of the
`breakdown` by op type can be trusted."""
LAYER = 'lowering (core/lowering.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'


def read(ctx: dict):
    t = ctx["trace"]
    if not t.get("devices") or not ctx["executables"]:
        return None
    return 100.0 * t["scoped_share"]
