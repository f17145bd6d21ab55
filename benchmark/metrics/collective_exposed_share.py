"""The part of `collective_time_share` in which no other operation ran on
that device: communication that the backward pass did not hide."""
LAYER = 'multi-chip (parallel/*)'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'


def read(ctx: dict):
    t = ctx["trace"]
    if not t.get("devices") or ctx["stats"].get("chips", 1) < 2:
        return None
    return 100.0 * t["collective_exposed_share"]
