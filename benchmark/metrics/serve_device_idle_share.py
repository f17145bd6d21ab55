"""`device_idle_share` for the serving cells.  High by design below the knee:
it is the room the chip has left, and what a faster host path would turn
into a higher sustainable rate."""
LAYER = 'XLA: device'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'serve_rows_per_s'


from benchmark.metrics.device_idle_share import read  # noqa: E402,F401  (the same reading, another cell's metric)
