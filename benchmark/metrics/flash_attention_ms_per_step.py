"""Device ms a step spends in attention where `fused_attention` takes the
stock flash kernel under a causal mask: `attention_ms_per_step`'s reading,
scope and reader, under a name of its own because that metric's list is
pinned to its first cell by a test (PERF.md, defect 13a).  Nothing where the
program has no `fused_attention` scope."""
from benchmark.metrics.attention_ms_per_step import BETTER, LAYER, MOVES, SOURCE, UNIT, read  # noqa: F401
