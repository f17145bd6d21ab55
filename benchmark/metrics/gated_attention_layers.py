"""The attention layers of the train step whose output passes a sigmoid gate a
head before the out projection: the program's trace-time counter
`lowering.gated_attention_layers` (core/lowering.py: `count_layer_forms`,
counted once a trace of a program with a backward pass, from the program's own
ops: the `sigmoid` ops in a name scope `attention_gate`).  5 in Laguna-XS.2's
cell; a change that quietly drops a layer's gate reads fewer.  Nothing where the
counter is absent or 0 (a parent without it, a program without
`attention_gate=`).

Beside the value, on an `info` line `attention_forms`, the two other counters of
the same trace: `query_heads_by_layer`, the query heads of each
`fused_attention` op in the step's order (`lowering.query_heads_by_layer.<i>`:
[48, 64, 64, 64, 48] here), and `rotary_tables`, the distinct rotary
descriptions among the step's `rotary_embedding` ops (2 here: YaRN over half a
head, plain over the whole); and on an `info` line `moe_routing`, what the
routers chose in the window's logged steps, as the other held cells' lines have
it: layer by layer the median share of the (token, slot) assignments that fell
on held experts (`held_rows_share`: the program's `moe_routing` step records),
and the largest share any logged step saw; no assignment to a held expert is
dropped."""
import json
from statistics import median

from benchmark import program_trace

LAYER = 'lowering (core/lowering.py)'
UNIT = 'count'
BETTER = 'higher'
SOURCE = 'program_counter'
MOVES = 'train_samples_per_s'
HEADS = "lowering.query_heads_by_layer."


def read(ctx: dict):
    if "traffic" not in ctx:
        return None
    monitor = program_trace.program_monitor()
    counted = monitor.counter_values()
    gated = counted.get("lowering.gated_attention_layers")
    if not gated:
        return None
    heads = {int(name[len(HEADS):]): value for name, value in counted.items() if name.startswith(HEADS)}
    print(json.dumps({"info": "attention_forms", "query_heads_by_layer": [heads[i] for i in sorted(heads)],
                      "rotary_tables": counted.get("lowering.rotary_tables", 0)}), flush=True)
    found = [r for r in monitor.step_records() if r.get("kind") == "moe_routing" and "held_rows_share" in r
             and r["pipeline_step"] >= ctx["traffic"].get("warmup_steps", 0)]
    if found:
        dropped = [(r["pipeline_step"], r["dropped_tokens"]) for r in found if r["dropped_tokens"]]
        assert not dropped, f"moe.dropped_tokens is not 0 at steps {dropped[:4]}"
        held = [r["held_rows_share"] for r in found]
        print(json.dumps({"info": "moe_routing", "logged_steps": len(found),
                          "held_rows_share": [median(layer) for layer in zip(*held)],
                          "held_rows_share_max": max(max(h) for h in held)}), flush=True)
    return gated
