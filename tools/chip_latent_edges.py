"""What the compiled step writes at the latent attention's edge: the
instructions under a `latent_attention` scope that write an array of the
operands' size and are neither a matrix product nor a kernel call (slices,
rotations, the spreading of k_r, concats, the scaling, the moves to and from
heads-major, JAX's transposes of all these), by direction: forward, made again
in backward (`rematted_computation`), backward (`transpose(`).  The kernels
need four such arrays forward (q 201, k 201, v 134, out 134 MB at Kanana-2's
shape), two made again (q, k) and three backward (dq twice: the partials' sum
and its way back through scale and rotation; d_up 268): whatever else is
written there is the edge's own.  PERF.md, section 6, PR 55, has the readings.

    python3 tools/chip_latent_edges.py                  the cell's whole step, compiled here for the described
                                                        v5e, no chip (~2.5 min): the table of one sparse layer
    python3 tools/chip_latent_edges.py --layer 2048     ONE latent attention layer at (H 32, 192 / 128) and that
                                                        many positions, forward and backward (~15 s; what
                                                        tests/test_chip_compile.py holds)
    chiprun -- python3 tools/chip_latent_edges.py --trace [seed]
                                                        one traced run of the cell on the chip, and the own ms a
                                                        step of the same instructions beside the run's line

    chiprun -- python3 tools/chip_latent_edges.py --check
                                                        the four kernels of `ops/latent_kernels.py` on the chip at the
                                                        cell's shape: how far each lies from the plain `jax.numpy`
                                                        pass (a kernel that passes interpreted can be wrong on the
                                                        chip: PERF.md, PR 33) and its ms, beside the bytes it moves

`edges(text, scope)` and `one_layer_step(devices, positions)` are the test's.
"""
import json
import os
import re
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CELL = "kanana-2-30b-a3b.train-mla-s16384"
#: the layer whose table is printed: a sparse one in the middle of the five (sibling scopes are numbered)
LAYER = re.compile(r"/latent_attention_2/")
ANY_LAYER = re.compile(r"/latent_attention(_\d+)?/")
#: an instruction is listed from this many bytes written on (at 16384 positions; the floor follows the positions)
FLOOR = 30e6
_ITEM = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8, "u64": 8}
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*?) ([\w-]+)\((.*)$")
_ARRAY = re.compile(r"\b(pred|[suf]\d+|bf16)\[([\d,]*)\]")
_PASS = re.compile(r"/(latent_(?:queries|keys_values|queries_back|up_back))(?:/|$)")


def _bytes(shape: str) -> int:
    """The bytes of an instruction's result, a tuple's members together."""
    return sum(_ITEM.get(kind, 0) * int(eval("*".join(dims.split(",")) or "1")) for kind, dims in _ARRAY.findall(shape))


def computations(text: str) -> dict:
    """{computation: its lines} of a compiled module's text."""
    found, name = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            name = head.group(1)
            found[name] = []
        elif name is not None:
            found[name].append(line)
    return found


def edges(text: str, scope=ANY_LAYER, floor: float = FLOOR) -> list:
    """[(direction, kind, MB written, shape, op_name)] of the instructions of
    the module's entry computation under `scope` that write `floor` bytes or
    more and are neither a product (a fusion round a convolution or a dot), an
    attention kernel's call (a custom call) nor the partials' sum (a
    `reduce_sum`, the fused backward kernel's), and the calls of the edge's own
    kernels (`kernel:<name>`): what the edge writes.  `kind` is the last part of
    the instruction's `op_name`, what JAX called the operation."""
    parts = computations(text)
    entry = re.search(r"^ENTRY %?([\w.\-]+) ", text, re.M).group(1)
    found = []
    for line in parts[entry]:
        m = _INSTRUCTION.match(line)
        name = re.search(r'op_name="([^"]*)"', line)
        if not m or not name or not scope.search(name.group(1)):
            continue
        shape, opcode, written = m.group(2), m.group(3), _bytes(m.group(2))
        way = "again" if "rematted_computation" in name.group(1) else "backward" if "transpose(" in name.group(1) else "forward"
        passed = _PASS.search(name.group(1)) if opcode == "custom-call" else None
        if passed:      # one of `ops/latent_kernels.py`'s: what it writes is the edge's, and all of it is needed
            found.append((way, "kernel:" + passed.group(1), written / 1e6, shape.split("{")[0], name.group(1)))
        if written < floor or opcode in ("custom-call", "get-tuple-element", "bitcast", "tuple", "parameter"):
            continue
        called = re.search(r"calls=%?([\w.\-]+)", line)
        body = "\n".join(parts.get(called.group(1), ())) if called else line
        if re.search(r"\b(convolution|dot)\(", body):
            continue
        kind = name.group(1).rsplit("/", 1)[-1]
        if kind == "reduce_sum" and "block_sparse_attention" in name.group(1):
            continue
        found.append((way, kind, written / 1e6, shape.split("{")[0], name.group(1)))
    return found


def table(found: list) -> str:
    rows, total = [], defaultdict(float)
    for way in ("forward", "again", "backward"):
        for _, kind, mb, shape, name in sorted((f for f in found if f[0] == way), key=lambda f: -f[2]):
            rows.append(f"  {way:9s} {mb:8.1f} MB  {kind:24s} {shape:34s} ...{name[-70:]}")
            total[way] += mb
    rows.append("  " + "   ".join(f"{way} {total[way]:.0f} MB" for way in ("forward", "again", "backward"))
                + f"   all {sum(total.values()):.0f} MB")
    return "\n".join(rows)


def _compiled(main, startup, feeds, fetch, device):
    """(`main`'s train step through `_CompiledStep`, compiled for `device`; the `lowering.` counters of its trace)."""
    import jax
    from jax.sharding import SingleDeviceSharding

    import paddle_tpu as fluid
    from paddle_tpu import monitor
    from paddle_tpu.core import executor as ex

    scope, one = fluid.Scope(), SingleDeviceSharding(device)
    for v in startup.global_block().vars.values():
        if v.persistable:
            scope.set_var(v.name, jax.ShapeDtypeStruct(tuple(v.shape), v.dtype))
    step = ex._CompiledStep(main, list(feeds), fetch, scope, platform="tpu", feed_shapes={n: s.shape for n, s in feeds.items()})

    def on(v):
        return jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one)

    monitor.reset()
    monitor.enable()
    try:
        compiled = step.jfn.lower({n: on(scope.find_var(n)) for n in step.rw_names}, {n: on(scope.find_var(n)) for n in step.ro_names},
                                  {n: on(s) for n, s in feeds.items()}, on(jax.random.PRNGKey(0))).compile()
        counted = {k: v for k, v in monitor.MONITOR.counter_values().items() if k.startswith("lowering.")}
    finally:
        monitor.disable()
        monitor.reset()
    return compiled, counted


def one_layer_step(devices, positions: int, heads: int = 32, nope: int = 128, rope: int = 64, v_dim: int = 128,
                   d_model: int = 2048, rank: int = 512, interleave: bool = True):
    """ONE latent attention layer at Kanana-2's widths over `positions`, in a
    `recompute_scope`, its loss and an SGD step, through `_CompiledStep` and
    compiled for `devices[0]` (a described v5e's): (compiled, counters)."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.models import transformer

    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [positions, d_model], dtype="bfloat16")
        at = fluid.layers.data("positions", [positions], dtype="int32")
        x.stop_gradient = False
        with fluid.recompute_scope():
            y = transformer.latent_attention(x, d_model, heads, "l", rank, nope, rope, v_dim, positions=at,
                                             rope_theta=1e6, rope_interleave=interleave)
        loss = fluid.layers.mean(fluid.layers.square(fluid.layers.cast(y, "float32")))
        fluid.optimizer.SGD(1e-3).minimize(loss)
    feeds = {"x": jax.ShapeDtypeStruct((1, positions, d_model), jnp.bfloat16),
             "positions": jax.ShapeDtypeStruct((1, positions), jnp.int32)}
    return _compiled(main, startup, feeds, [loss.name], devices[0])


def cell_step(devices):
    """The cell's whole train step as `tests/test_chip_compile.py: _kept_step`
    compiles it: (compiled, counters)."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from benchmark import manifest as mf
    from benchmark.models import kanana
    from paddle_tpu.monitor import memstats

    memstats.device_bytes_limit = lambda *a: 16_909_336_064    # the v5e's: what `plan_kept` reads on the chip
    cfg, job = mf.read_json("benchmark/configs/kanana-2-30b-a3b.json"), mf.read_json("benchmark/traffic/train-mla-s16384.json")
    with fluid.unique_name.guard():
        main, startup, _, loss, _ = kanana.build(cfg, job)
    main.random_seed = startup.random_seed = 3
    feeds = {n: jax.ShapeDtypeStruct((job["batch_per_chip"], job["seq_len"]), jnp.int32) for n in kanana.FEEDS}
    return _compiled(main, startup, feeds, [loss.name], devices[0])


def traced(argv):
    """One traced run of the cell through `benchmark.run`, and an `info` line
    `latent_edges`: the own device ms a step of the instructions `edges`
    lists in the step's compiled text, by direction and kind."""
    from benchmark import run
    from benchmark.metrics import recompute_ms_per_step as own

    inner = run.per_layer_metrics

    def per_layer_metrics(manifest, r, out, reduced, peaks):
        metrics = inner(manifest, r, out, reduced, peaks)
        try:
            spent, _ = own.own_ms({"trace": reduced, "executables": out.executables, "stats": out.stats,
                                   "monitor": out.monitor_delta, "cell": r.cell})
            ms, listed = defaultdict(float), 0.0
            for executable in out.executables:
                text = executable.as_text()
                names = own.op_names(text)
                for way, kind, _, _, name in edges(text):
                    took = sum(t for instruction, t in spent.items() if names.get(instruction) == name)
                    ms[f"{way}:{kind}"] += took
                    listed += took
            print(json.dumps({"info": "latent_edges", "own_ms_per_step": round(listed, 3),
                              "by_way_and_kind": {k: round(v, 3) for k, v in sorted(ms.items(), key=lambda kv: -kv[1])}}), flush=True)
        except Exception as e:   # the run's line is what matters
            print(json.dumps({"info": "latent_edges", "error": repr(e)}), flush=True)
        return metrics

    run.per_layer_metrics = per_layer_metrics
    seed = argv[0] if argv else "3550000011"
    run.main(["--workload", CELL, "--seed", seed, "--seconds", "20", "--trace", "1"])


def check():
    """The kernels against the plain passes on the chip, and their times."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops import latent_kernels as lk
    from paddle_tpu.ops import latent_operands as lo

    batch, positions, heads, nope, v = 1, 16384, 32, 128, 128
    keys = jax.random.split(jax.random.PRNGKey(55), 6)
    q, up, shared = (jax.random.normal(k, (batch, positions) + tail, jnp.bfloat16)
                     for k, tail in zip(keys, ((heads, nope + 64), (heads, nope + v), (64,))))
    cotangents = tuple(jax.random.normal(k, (batch, heads, positions, w), jnp.bfloat16)
                       for k, w in zip(keys[3:], (nope + 64, nope + 64, v)))
    pos = jnp.arange(positions, dtype=jnp.int32)[None]
    for rotated, interleave in ((True, True), (True, False), (False, False)):
        made = []
        for kernels in (False, True):
            p = lo.Passes(nope, 64, 192 ** -0.5, rotated, 1e6, interleave, "q", "k", "s", kernels)
            both = jax.jit(lambda q, up, shared, cts, p=p: (lambda out, back: (*out, *back(cts)))(
                *jax.vjp(lambda q, up, shared: lo.assemble(p, q, up, shared, pos if rotated else None), q, up, shared)))
            made.append([np.asarray(t, "f4") for t in both(q, up, shared, cotangents)])
        print(json.dumps({"info": "kernels_against_plain", "rotated": rotated, "interleave": interleave,
                          "largest_difference": {n: float(np.abs(a - b).max()) for n, a, b in
                                                 zip(("q_hm", "k_hm", "v_hm", "dq", "d_up", "d_shared"), *made)},
                          "differing_share": {n: float(np.mean(a != b)) for n, a, b in
                                              zip(("q_hm", "k_hm", "v_hm", "dq", "d_up", "d_shared"), *made)}}), flush=True)
    cos, sin = lo._tables(lo._pairs(pos, lo.Passes(nope, 64, 1.0, True, 1e6, True, "q", "k", "s", True)))
    q2, up2 = (t.reshape(t.shape[:2] + (-1,)) for t in (q, up))
    # (the wall time of twenty calls: alone a call reads about twice what the same kernel takes inside the cell's step,
    # 1.45 to 1.8 ms against 0.67 to 1.07 in the traced run of PR 55, so read the ORDER here and the ms in `--trace`)
    calls = {"queries": (lambda: lk.queries(q2, cos, sin, heads=heads, nope=nope, scale=0.07, shift=1), 2 * q.nbytes),
             "keys_values": (lambda: lk.keys_values(up2, shared, heads=heads, nope=nope), up.nbytes + q.nbytes + up.nbytes // 2),
             "queries_back": (lambda: lk.queries_back(cotangents[0], cos, sin, heads=heads, nope=nope, scale=0.07, shift=1), 2 * q.nbytes),
             "up_back": (lambda: lk.up_back(cotangents[1], cotangents[2]), 2 * up.nbytes + q.nbytes // 3)}
    for name, (call, moved) in calls.items():
        jax.block_until_ready(call())
        t0 = time.perf_counter()
        for _ in range(20):
            out = call()
        jax.block_until_ready(out)
        ms = (time.perf_counter() - t0) / 20 * 1e3
        print(json.dumps({"info": "kernel_ms", "kernel": name, "ms": round(ms, 3), "MB_moved": round(moved / 1e6, 1),
                          "GB_per_s": round(moved / ms / 1e6, 1)}), flush=True)


def main(argv):
    if argv[:1] == ["--trace"]:
        return traced(argv[1:])
    if argv[:1] == ["--check"]:
        return check()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    devices = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices
    if argv[:1] == ["--layer"]:
        positions = int(argv[1]) if len(argv) > 1 else 2048
        compiled, counted = one_layer_step(devices, positions)
        scope, floor = ANY_LAYER, FLOOR * positions / 16384
    else:
        compiled, counted = cell_step(devices)
        scope, floor = LAYER, FLOOR
    text = compiled.as_text()
    if os.environ.get("KEEP_TEXT"):
        open(os.environ["KEEP_TEXT"], "w").write(text)
    print({k[len("lowering."):]: v for k, v in counted.items() if "latent" in k or "attention_" in k})
    m = compiled.memory_analysis()
    print(f"planned peak {(m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes) / 1e9:.3f} GB")
    print(table(edges(text, scope, floor)))


if __name__ == "__main__":
    main(sys.argv[1:])
