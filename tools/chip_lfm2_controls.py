"""LFM2's cell on the chip, what its comparison can and cannot tell: the
harness's own `benchmark.models.lfm2.compare` / `reference_error` on the
program's check rows against the float32 reference, and then against the SAME
reference with a fault put into its weights, one at a time, so that each
limit that decides `correct` has a reading it must refuse beside the sound one
(PERF.md, section 6, PR 34):

  * `bf16_masters`: every weight rounded to bf16 (the precision below the
    float32 masters the configuration states): `REFERENCE_RTOL`;
  * `dropped_expert`: one held expert of the first sparse layer adds nothing
    (its down matrix is zero): `EXPERTS_RTOL`, `REFERENCE_RTOL`;
  * `zeroed_layer`: the last layer's operator adds nothing (its out-projection
    is zero): `REFERENCE_RTOL`, `LEFT_OUT_LOGIT_MAX`;
  * `router_gain`: every router's matrix 1.25x (a gain the router's input
    lacks or has twice): `LEFT_OUT_MAX`, `ROUTER_RTOL`.

A fault in the reference reads as the same fault in the program would: the
errors are differences.  Then the share of the check rows' choices that fall
on the held experts, layer by layer, under the configuration's `routing_seed`
and others: what the choice of that seed is worth.

    chiprun -- python3 tools/chip_lfm2_controls.py 3900000017      (PERF.md, PR 34)

`DRY=1` rehearses it tiny on the CPU; no number of that means anything.
"""
import contextlib
import io
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

import paddle_tpu as fluid
from benchmark import manifest as mf
from benchmark.models import lfm2

CHECK_ROWS = 8  # as benchmark/runners/train.py
DRY = os.environ.get("DRY") == "1"
TINY = (dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16, intermediate_size=96,
             moe_intermediate_size=32, num_experts=4, num_routed_experts=16, num_experts_per_tok=2, vocab_size=96),
        dict(seq_len=32, batch_per_chip=4, ring=4))
BIAS_SEEDS = (1000, 2000, 3000, 4000, 5000, 6000, 7000)
LIMITS = {"logit_error": "REFERENCE_RTOL", "loss_error": "REFERENCE_RTOL", "logit_error_left_out": "LEFT_OUT_LOGIT_MAX",
          "left_out_share": "LEFT_OUT_MAX", "router_prob_error": "ROUTER_RTOL", "experts_error": "EXPERTS_RTOL",
          "conv_error": "CONV_RTOL", "attention_error": "ATTENTION_RTOL", "qk_error": "QK_RTOL"}


def faults(params: dict, cfg: dict) -> dict:
    """name -> the reference's weights with that fault."""
    sparse = cfg["num_dense_layers"]
    last = len(cfg["layer_types"]) - 1
    out_w = f"lm.l{last}.conv.out.w" if cfg["layer_types"][last] == "conv" else f"lm.l{last}.attn.out.w"
    down = np.array(params[f"lm.l{sparse}.moe.down.w"])
    down[1] = 0.0
    return {
        "sound": params,
        "bf16_masters": {n: lfm2._bf16(np.asarray(v, "f4")) for n, v in params.items()},
        "dropped_expert": {**params, f"lm.l{sparse}.moe.down.w": down},
        "zeroed_layer": {**params, out_w: np.zeros_like(params[out_w])},
        "router_gain": {n: (1.25 * np.asarray(v) if n.endswith(".moe.router.w") else v) for n, v in params.items()},
    }


def main(seed: int):
    cfg = mf.read_json("benchmark/configs/lfm2-8b-a1b.json")
    job = mf.read_json("benchmark/traffic/train-s8192.json")
    if DRY:
        cfg.update(TINY[0])
        job.update(TINY[1])
    program, startup, _, _, check_names = lfm2.build(cfg, job)
    program.random_seed = startup.random_seed = seed
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)
    rows = lfm2.make_batch(np.random.RandomState(seed), cfg, job, CHECK_ROWS)
    test_prog = program.clone(for_test=True)
    got = exe.run(test_prog, feed=rows, fetch_list=list(check_names), scope=scope)
    params = {p.name: np.asarray(scope.find_var(p.name)) for p in program.all_parameters()}
    reference = jax.jit(lambda p, b: lfm2.reference(p, b, cfg, program))
    batch = {k: np.asarray(v) for k, v in rows.items()}
    for name, faulty in faults(params, cfg).items():
        want = [np.asarray(w) for w in reference(faulty, batch)]
        with contextlib.redirect_stdout(io.StringIO()) as said:
            error = lfm2.reference_error(got, want)
        found = json.loads(said.getvalue())
        readings = {k: found[k] for k in LIMITS}
        refused_by = sorted({LIMITS[k] for k in LIMITS if not found[k] <= getattr(lfm2, LIMITS[k])}
                            | ({"a router's choice"} if found["router_choice_differs"] else set())
                            | ({"ROUTING_MARGIN"} if found["routed_differently_above_margin"] else set()))
        print(json.dumps({"control": name, "seed": seed, "correct": bool(error <= lfm2.REFERENCE_RTOL),
                          "refused_by": refused_by, **readings}), flush=True)

    # the held share of the check rows' choices under other biases: the program's own routers, the bias swapped
    sparse = [i for i in range(len(cfg["layer_types"])) if i >= cfg["num_dense_layers"]]
    choices = [check_names[2 + 5 * i] for i in range(len(sparse))]
    biases = [check_names[2 + 5 * i + 4] for i in range(len(sparse))]
    first, count = lfm2.held(cfg)
    for routing_seed in (cfg["routing_seed"],) + BIAS_SEEDS:
        drawn = lfm2.router_biases({}, {**cfg, "routing_seed": routing_seed}, sparse)
        for name, value in zip(biases, drawn):
            scope.set_var(name, jax.device_put(np.asarray(value, "f4")))
        chosen = exe.run(test_prog, feed=rows, fetch_list=choices, scope=scope)
        shares = [float(((np.asarray(c) >= first) & (np.asarray(c) < first + count)).mean()) for c in chosen]
        print(json.dumps({"routing_seed": routing_seed, "seed": seed,
                          "held_rows_share": [round(100.0 * s, 3) for s in shares]}), flush=True)


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 3900000017)
