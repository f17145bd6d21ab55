"""The least time the chip could take for the held latent experts' grouped
products of a step, over the device OWN time of the instructions that compute
them.  The least time is max(operations / peak FLOP/s, bytes / peak HBM B/s) of
`latent_expert_gemm_flops` and `latent_expert_gemm_bytes` in the model's module
(benchmark/models/nemotron_h.py: two products of 1024 x 2688 a row, forward and
backward, nothing for padding or for what backward makes again; the held
experts' matrices once a pass and the rows' latents and hidden rows) over the
ROWS THAT HELD EXPERTS RECEIVED in the run: the mean `held_rows_share` of the
window's `moe_routing` step records times a chip's tokens x 22 (a uniform
router's share, 1/16, where the run logged none).  The instructions are those
under the `expert_gemm` scope inside the `latent_experts` scope (`ops/moe_ops.py:
grouped_matmul`: the kernel calls and the rows' padding, forward, backward and
recomputed; not the sort, the gathers or the way back round them).  With 352
rows an expert the matrices' bytes decide, not the operations.  Nothing where
the program has no such scope or the model no such function."""
import re
from statistics import mean

from benchmark import program_trace
from benchmark.metrics import attention_roofline_share, ssm_ms_per_step

LAYER = 'ops: kernels (ops/*.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'
MOVES = 'train_samples_per_s'

SCOPE = re.compile(r"/latent_experts(_\d+)?/.*/expert_gemm/")


def held_rows_a_layer(records, first_step: int, assignments: int):
    """Rows a layer that held experts received on a chip, the mean over the
    window's logged steps and layers; None where no step logged any."""
    found = [r for r in records if r.get("kind") == "moe_routing" and "held_rows_share" in r
             and r["pipeline_step"] >= first_step]
    return assignments * mean(mean(r["held_rows_share"]) for r in found) if found else None


def read(ctx: dict):
    model = ctx.get("model")
    if not hasattr(model, "latent_expert_gemm_flops") or not ctx.get("executables"):
        return None
    spent = ssm_ms_per_step.own_ms_under(ctx, SCOPE)
    if not spent:
        return None
    cfg, job = ctx["config"], ctx["traffic"]
    rows = held_rows_a_layer(program_trace.program_monitor().step_records(), job["warmup_steps"],
                             job["batch_per_chip"] * job["seq_len"] * cfg["num_experts_per_tok"])
    least = attention_roofline_share.least_seconds(
        model.latent_expert_gemm_flops(cfg, job, rows), model.latent_expert_gemm_bytes(cfg, job, rows), ctx["peaks"])
    return 100.0 * least / (spent / 1e3)
