"""The share of a step's (token, slot) choices that the routers' bias moved:
those the unbiased scores' top-k would not have made, the worst (largest)
layer's, median over the window's logged steps: the `bias_moved_share` of the
program's `moe_routing` step records (`pipeline.train_loop` publishes one per
logged step for a layer built with `layers.moe(bias_attr=...)`, with the gauge
`moe.bias_moved_share`).  0 is a bias that is a zero added; the weights are the
unbiased scores either way.  The cell also asserts here what the program
promises: no logged step left an assignment to a held expert out; and prints
the same records' `held_rows_share` (median a layer) on an `info` line, for a
cell whose `held_expert_rows_share` no list carries.  Nothing where the
program has no such record."""
import json
from statistics import median

from benchmark import program_trace

LAYER = 'lowering (core/lowering.py)'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'program_counter'
MOVES = 'train_samples_per_s'


def read(ctx: dict):
    first = (ctx.get("traffic") or {}).get("warmup_steps")
    if first is None:
        return None
    return bias_moved_share(program_trace.program_monitor().step_records(), first)


def bias_moved_share(records, first_step: int):
    found = [r for r in records if r.get("kind") == "moe_routing" and "bias_moved_share" in r
             and r["pipeline_step"] >= first_step]
    if not found:
        return None
    dropped = [(r["pipeline_step"], r["dropped_tokens"]) for r in found if r["dropped_tokens"]]
    assert not dropped, f"moe.dropped_tokens is not 0 at steps {dropped[:4]}"
    held = [r["held_rows_share"] for r in found if "held_rows_share" in r]
    print(json.dumps({"info": "moe_routing", "logged_steps": len(found),
                      "bias_moved_share": [median(layer) for layer in zip(*(r["bias_moved_share"] for r in found))],
                      "held_rows_share": [median(layer) for layer in zip(*held)],
                      "held_rows_share_max": max((max(h) for h in held), default=None)}), flush=True)
    return 100.0 * median(max(r["bias_moved_share"]) for r in found)
