"""`Server.queue_wait_frac()`: of the time completed requests spent in the
server, the share spent queued before a batch picked them."""
LAYER = 'serving (serving/server.py, batcher.py)'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'program_counter'
MOVES = 'request_p99_ms'


def read(ctx: dict):
    q = ctx["stats"].get("queue_wait_frac")
    return None if q is None else 100.0 * q
