"""Padded rows over rows executed (real + padded), from the server's own
`stats()` over the run: the work the device did for nobody."""
LAYER = 'serving (serving/server.py, batcher.py)'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'program_counter'
MOVES = 'request_p50_ms'


def read(ctx: dict):
    s = ctx["stats"]
    if "padded_rows" not in s or not s["rows"] + s["padded_rows"]:
        return None
    return 100.0 * s["padded_rows"] / (s["rows"] + s["padded_rows"])
