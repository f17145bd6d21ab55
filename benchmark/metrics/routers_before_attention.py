"""The sparse layers of the train step whose router stands AHEAD of the
layer's attention: the program's trace-time counter
`lowering.routers_before_attention` (core/lowering.py: `count_layer_forms`,
counted once a trace of a program with a backward pass, from the program's own
ops: the `moe_router` ops between which and the `moe_experts` that reads their
choice a `fused_attention` stands).  4 in SmallThinker's cell; a change that
moves a router back behind its attention reads fewer.  Nothing where the
counter is absent or 0 (a parent without it, a program whose routers stand
beside their experts).

Beside the value, on an `info` line `moe_routing`, what those routers chose in
the window's logged steps, as the other held cells' lines have it: layer by
layer the median share of the (token, slot) assignments that fell on held
experts (`held_rows_share`: the program's `moe_routing` step records), and the
largest share any logged step saw; no assignment to a held expert is dropped."""
import json
from statistics import median

from benchmark import program_trace

LAYER = 'lowering (core/lowering.py)'
UNIT = 'count'
BETTER = 'higher'
SOURCE = 'program_counter'
MOVES = 'train_samples_per_s'


def read(ctx: dict):
    if "traffic" not in ctx:
        return None
    monitor = program_trace.program_monitor()
    ahead = monitor.counter_values().get("lowering.routers_before_attention")
    if not ahead:
        return None
    found = [r for r in monitor.step_records() if r.get("kind") == "moe_routing" and "held_rows_share" in r
             and r["pipeline_step"] >= ctx["traffic"].get("warmup_steps", 0)]
    if found:
        dropped = [(r["pipeline_step"], r["dropped_tokens"]) for r in found if r["dropped_tokens"]]
        assert not dropped, f"moe.dropped_tokens is not 0 at steps {dropped[:4]}"
        held = [r["held_rows_share"] for r in found]
        print(json.dumps({"info": "moe_routing", "logged_steps": len(found),
                          "held_rows_share": [median(layer) for layer in zip(*held)],
                          "held_rows_share_max": max(max(h) for h in held)}), flush=True)
    return ahead
