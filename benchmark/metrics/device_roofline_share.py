"""The least time the chip could take for the steps in the trace over the
time it was busy.  The trace of this JAX carries no per-op flops or bytes
(trace_reduce.py), so the least time is that of the whole step:
max(flops / peak FLOP/s, bytes accessed / peak HBM B/s) from
`compiled.cost_analysis()` of the step (per device), times the step's runs
in the traced window."""
LAYER = 'ops: kernels (ops/*.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'


def read(ctx: dict):
    t, peaks = ctx["trace"], ctx["peaks"]
    if not t.get("devices") or not ctx["executables"] or not t.get("main_module_runs"):
        return None
    cost = ctx["executables"][-1].cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    if not cost or "flops" not in cost:
        return None
    least = max(cost["flops"] / peaks["bf16_flops_per_s"],
                cost.get("bytes accessed", 0.0) / peaks["hbm_bytes_per_s"])
    return 100.0 * least * t["main_module_runs"] / t["busy_s"]
