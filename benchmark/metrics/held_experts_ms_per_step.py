"""Device ms a step spends in the routed experts of a layer that holds a share
of them: the instructions whose lowering scope (`op<idx>:<type>` in
`compiled.as_text()`) is `moe_router` or `moe_experts`, forward and backward,
over the main module's runs in the traced window.  The router's scores and
its two top-k's, the sort by local expert, the row gathers and scatter-adds
over the bound's rows, the three grouped products, the masters' casts.  It is
`moe_ms_per_step` read by instruction (as `attention_ms_per_step` is, so that
a kernel call whose text runs over several lines is seen) under a name of its
own, because that metric's list is pinned to its first cell by a test
(PERF.md, defect 13a).  Nothing where the program has no such scope."""
import re

from benchmark.metrics import attention_roofline_share

LAYER = 'ops: kernels (ops/*.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'

SCOPE = re.compile(r"/op\d+:moe_(router|experts)(/|$)")


def read(ctx: dict):
    spent = attention_roofline_share.seconds_under(ctx, SCOPE)
    return 1e3 * spent if spent else None
