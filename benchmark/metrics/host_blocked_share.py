"""Share of the loop's time the host spent waiting on the device: the
program's `pipeline.host_blocked` span over the window.  High is good: a
host that waits is a host that is not in the device's way."""
LAYER = 'entry: input (pipeline.train_loop, reader.DataLoader)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'program_span'
MOVES = 'train_samples_per_s'


def read(ctx: dict):
    blocked = ctx["monitor"].get("window", {}).get("pipeline.host_blocked")
    if not blocked or "loop_s" not in ctx["stats"]:
        return None
    return 100.0 * blocked / ctx["stats"]["loop_s"]
