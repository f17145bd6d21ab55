"""Of the (512 queries, 512 keys) chunk pairs on or under the diagonal, the
share that holds a chosen (query, key) pair, the worst (largest) layer's, median
over the window's logged steps: the `chunk_pairs_touched_share` of the program's
`sparse_index` step records (`pipeline.train_loop` publishes one per logged
step from the op's own `Stats`).  It is what a kernel that skips the key blocks
no query of a block chose could NOT leave out: 100 says that block skipping
saves nothing at this grain, whatever share of the pairs the picks are.  The
cell also asserts here what the program promises: every logged step's picks are
the sum over the queries of min(topk, position + 1), the alignment term is
finite, and no logged step left an assignment to a held expert out (the
`moe_routing` records' `dropped_tokens`; the run's `sparse_index` line holds
the records' medians a layer).  Nothing where the program has no such record."""
import json
from statistics import median

from benchmark import program_trace

LAYER = 'lowering (core/lowering.py)'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'program_counter'
MOVES = 'train_samples_per_s'


def read(ctx: dict):
    job, cfg = ctx.get("traffic") or {}, ctx.get("config") or {}
    first, topk = job.get("warmup_steps"), (cfg.get("sa_config") or {}).get("topk")
    if first is None or topk is None:
        return None
    return touched_share(program_trace.program_monitor().step_records(), first, topk, job["seq_len"])


def touched_share(records, first_step: int, topk: int, seq_len: int):
    found = [r for r in records if r.get("kind") == "sparse_index" and r["pipeline_step"] >= first_step]
    if not found:
        return None
    k = min(topk, seq_len)
    for r in found:
        for picks, queries in zip(r["picks"], r["queries"]):
            want = (k * (k + 1) // 2 + (seq_len - k) * k) * (queries // seq_len)
            assert picks == want, f"step {r['pipeline_step']}: {picks} picks, {want} by min(topk, t + 1)"
        bad = [v for v in r.get("index_kl", []) if not (v == v and abs(v) != float("inf"))]
        assert not bad, f"step {r['pipeline_step']}: index_kl {r.get('index_kl')}"
    routed = [r for r in records if r.get("kind") == "moe_routing" and r["pipeline_step"] >= first_step]
    dropped = [(r["pipeline_step"], r["dropped_tokens"]) for r in routed if r["dropped_tokens"]]
    assert not dropped, f"moe.dropped_tokens is not 0 at steps {dropped[:4]}"

    def by_layer(name, records=found):
        return [median(layer) for layer in zip(*(r[name] for r in records if name in r))]

    print(json.dumps({"info": "sparse_index", "logged_steps": len(found), "picks": found[-1]["picks"],
                      **{name: by_layer(name) for name in ("picks_per_query", "recent_share", "chunk_pairs_touched_share",
                                                           "index_kl")},
                      "dropped_tokens": 0, "held_rows_share": by_layer("held_rows_share", routed)}), flush=True)
    return 100.0 * median(max(r["chunk_pairs_touched_share"]) for r in found)
