"""`recompiles_in_window` for the serving cells, where a compile after warm
shows in the tail: the `executor.recompile` counter from the first request
of the warming traffic to the last of the window."""
LAYER = 'executor (core/executor.py)'
UNIT = 'count'
BETTER = 'lower'
SOURCE = 'program_counter'
MOVES = 'request_p99_ms'


from benchmark.metrics.recompiles_in_window import read  # noqa: E402,F401  (the same reading, another cell's metric)
