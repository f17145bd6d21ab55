"""Device ms a step spends in the routed experts: the instructions whose
lowering scope (`op<idx>:<type>` in `compiled.as_text()`) is `moe_router` or
`moe_experts`, forward and backward, over the main module's runs in the traced
window.  The router, the sort, the row gathers, the three grouped products
and the weighted combine; not the norm before them nor the residual add
after.  Nothing where the program has no such scope."""
LAYER = 'ops: kernels (ops/*.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'

SCOPES = ("moe_router.fwd", "moe_router.bwd", "moe_experts.fwd", "moe_experts.bwd")


def read(ctx: dict):
    t = ctx["trace"]
    by_label = t.get("by_label", {})
    if not t.get("main_module_runs") or not any(s in by_label for s in SCOPES):
        return None
    return 1e3 * sum(by_label.get(s, 0.0) for s in SCOPES) / t["main_module_runs"]
