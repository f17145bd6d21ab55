"""Parameter-sharding hints + elastic row repartitioning.

A Program carries `sharding_hints`: var name -> PartitionSpec-style tuple of
mesh-axis names (None = replicated dim).  The executor turns hints into
`in_shardings`/`out_shardings` for the jitted step, so tensor-parallel
layouts are declarative — GSPMD inserts the all-gathers/reduce-scatters.
The reference has no TP (SURVEY.md §2c: absent in 2019); this is the
documented new capability.

Elastic resume (ISSUE 9) adds the consolidate-and-resplit primitives:
`row_range` is the ONE canonical row partition (contiguous blocks, the
layout `parallel/embedding.py`'s row-sharded lookup assumes), and
`repartition_selected_rows` / `consolidate_selected_rows` move a sparse
row-slab table between rank sets by row id — so a checkpoint saved by N
workers restores onto M without dropping or duplicating a row.  Dense
arrays need no special helper: `io.load_sharded`'s region reader already
consolidates arbitrary shard layouts and re-splits them for whatever mesh
the restoring gang brings.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


def shard_parameters(program, rules: Dict[str, Tuple[Optional[str], ...]], mesh=None, batch_axis: str = "dp"):
    """Attach sharding hints by param-name regex.

    rules: {name_regex: partition_spec_tuple}, e.g.
        {r".*ffn1.w.*": (None, "tp"), r".*ffn2.w.*": ("tp", None)}
    First matching rule wins.  Returns the number of params annotated.

    With `mesh` the program also carries the mesh the hints refer to (and the
    axis that splits its feeds' rows, `batch_axis`): `Executor.run` of the
    program itself, not only of a `CompiledProgram.with_mesh` round it, then
    places every persistable as its hint says.  Hint the START-UP program too
    and its draws are born split: no chip ever holds a hinted parameter whole.
    An optimizer built afterwards gives its accumulators their parameter's hint,
    in both programs.  A later `CompiledProgram.with_mesh` has to name the same
    devices in the same order (`make_mesh`'s default order) and the same batch
    axis: `Executor.run` refuses another.
    """
    count = 0
    compiled = [(re.compile(pat), spec) for pat, spec in rules.items()]
    for v in program.list_vars():
        if not v.persistable:
            continue
        for pat, spec in compiled:
            if pat.fullmatch(v.name):
                program.sharding_hints[v.name] = tuple(spec)
                count += 1
                break
    if mesh is not None:
        program.sharding_mesh, program.sharding_batch_axis = mesh, batch_axis
    program._bump()
    return count


# --- elastic row repartitioning (ISSUE 9) -----------------------------------

def row_range(height: int, rank: int, world: int) -> Tuple[int, int]:
    """[lo, hi) row ids rank `rank` of `world` owns under the canonical
    contiguous partition.  Remainder rows go to the leading ranks (ceil
    split), matching the equal-local-shape layout the row-sharded lookup
    (`parallel/embedding.py`) and GSPMD both produce when `height` divides
    evenly — and degrading deterministically when it does not."""
    if not (0 <= rank < world):
        raise ValueError(f"row_range: rank {rank} outside world {world}")
    per = -(-height // world)  # ceil
    lo = min(rank * per, height)
    return lo, min(lo + per, height)


def consolidate_selected_rows(shards: Sequence[Tuple[np.ndarray, np.ndarray]],
                              height: int) -> Tuple[np.ndarray, np.ndarray]:
    """Merge per-rank (rows, values) slabs into one global slab sorted by
    row id.  Sentinel rows (id == height, the MergeAdd parking slot) are
    dropped; a row id appearing in more than one shard is an inconsistent
    save and raises — the canonical partition is disjoint, so duplicates
    mean two ranks both believed they owned the row."""
    from ..errors import CheckpointError

    all_rows: List[np.ndarray] = []
    all_vals: List[np.ndarray] = []
    for rows, vals in shards:
        rows = np.asarray(rows)
        vals = np.asarray(vals)
        live = rows != height
        all_rows.append(rows[live])
        all_vals.append(vals[live])
    rows = np.concatenate(all_rows) if all_rows else np.zeros((0,), np.int32)
    vals = (np.concatenate(all_vals, axis=0) if all_vals
            else np.zeros((0, 1), np.float32))
    order = np.argsort(rows, kind="stable")
    rows, vals = rows[order], vals[order]
    if rows.size and np.any(rows[1:] == rows[:-1]):
        dup = sorted(set(rows[1:][rows[1:] == rows[:-1]].tolist()))
        raise CheckpointError(
            f"consolidate_selected_rows: row id(s) {dup[:8]} appear in more "
            f"than one rank's shard — the saved partition overlaps, so the "
            f"consolidated table would double-count those rows")
    return rows, vals


def repartition_selected_rows(rows: np.ndarray, values: np.ndarray,
                              height: int, rank: int, world: int
                              ) -> Tuple[np.ndarray, np.ndarray]:
    """Slice a consolidated (rows, values) slab down to the rows rank
    `rank` of `world` owns by row id (`row_range`).  Zero-copy views where
    numpy allows; exact — the union over all ranks is the input and the
    pieces are disjoint."""
    rows = np.asarray(rows)
    values = np.asarray(values)
    lo, hi = row_range(height, rank, world)
    keep = (rows >= lo) & (rows < hi)
    return rows[keep], values[keep]
