"""99th percentile of a request's actual send time minus its due time.  A
generator that runs late offers less load than the cell says, and a
starved generator must not read as a fast server."""
LAYER = 'load generator (benchmark)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'host_clock'
MOVES = 'request_p99_ms'


def read(ctx: dict):
    return ctx["stats"].get("generator_lag_p99_ms")
