"""Share of the window the training loop spent inside `next(loader)`, by the
benchmark's own timer round it.  Near 0 while the loader's producer thread
keeps ahead of the device."""
LAYER = 'entry: input (pipeline.train_loop, reader.DataLoader)'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'host_clock'
MOVES = 'train_samples_per_s'


def read(ctx: dict):
    s = ctx["stats"]
    if "loader_wait_s" not in s:
        return None
    return 100.0 * s["loader_wait_s"] / s["window_s"]
