"""Traffic of kind `train`: one job through `fluid.train_loop`.

The job's parameters come from its traffic file: rows a chip (`batch_per_chip`),
the ring of host batches the loader cycles (`ring`), `max_inflight`,
`log_period`, `warmup_steps`, the optimizer's settings, and for several chips
the mesh (`mesh_shape`, `mesh_axes`).  The loop is the program's own
(`paddle_tpu.pipeline.train_loop` over a `DataLoader.from_generator`); the
benchmark times it from outside:

  * the loader is wrapped, so `next(loader)` is timed and annotated, and
    the wrapper ends the run when the window is over;
  * `on_dispatch(i)` is called after step i - max_inflight has completed
    (the loop drains down to `max_inflight - 1` steps in flight before it
    dispatches), so its time stamps are the completion series that
    `train_samples_per_s` is computed from.

Set-up, in order: build, start-up program on the device (seeded), the
reference check on 8 rows (the `for_test` clone against the plain float32
forward), then `warmup_steps` steps of the same loop, the first of which
compiles.  The window opens at the dispatch after them; nothing drains in
between, so the device is in steady state from the window's first step.
"""
from __future__ import annotations

import itertools

import numpy as np

from benchmark import arith
from benchmark.runners import common

CHECK_ROWS = 8


class TimedLoader:
    """The loader as `train_loop` sees it: an iterator that times each
    `next`, and stops once the window is over."""

    def __init__(self, inner):
        self.inner = iter(inner)
        self.t_end = None          # set when the window opens
        self.wait_s = 0.0
        self.n = 0

    def __iter__(self):
        return self

    def __next__(self):
        t0 = common.now()
        if self.t_end is not None and t0 >= self.t_end:
            raise StopIteration
        with common.annotate("bench.next_loader"):
            item = next(self.inner)
        self.wait_s += common.now() - t0
        self.n += 1
        return item

    def close(self):
        """Release the loader's producer thread (closing its generator sets
        the stop flag) before the process winds the device down."""
        self.inner.close()


def run(run):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    import paddle_tpu as fluid

    from benchmark.run import Outcome, info

    cfg, job, model = run.config, run.traffic, run.model
    chips = len(run.devices)
    batch = job["batch_per_chip"] * chips
    built = model.build(cfg, job)
    main, startup, feeds, loss, check_names = built
    main.random_seed = startup.random_seed = run.seed
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(startup, scope=scope)

    reasons = []
    # -- reference: the for_test clone on 8 seeded rows against the plain
    # float32 forward, parameters by name from the scope
    rng = np.random.RandomState(run.seed)
    rows = model.make_batch(rng, cfg, job, CHECK_ROWS)
    test_prog = main.clone(for_test=True)
    got = exe.run(test_prog, feed=rows, fetch_list=list(check_names), scope=scope)
    params = {p.name: scope.find_var(p.name) for p in main.all_parameters()}
    want = jax.jit(lambda p, b: model.reference(p, b, cfg, main))(
        params, {k: np.asarray(v) for k, v in rows.items()})
    ref_err = model.reference_error(got, [np.asarray(w) for w in want])
    if not ref_err <= model.REFERENCE_RTOL:
        reasons.append(f"reference: error {ref_err:.3e} > {model.REFERENCE_RTOL}")
    info("reference", error=ref_err, tolerance=model.REFERENCE_RTOL, rows=CHECK_ROWS)

    probe = common.ParamProbe(main, scope)
    probe.before()

    # -- the job
    program, sharding = main, None
    if "mesh_shape" in job:
        mesh = fluid.parallel.make_mesh(tuple(job["mesh_shape"]),
                                        tuple(job["mesh_axes"]), run.devices)
        program = fluid.CompiledProgram(main).with_mesh(mesh, batch_axis=job["mesh_axes"][0])
        sharding = NamedSharding(mesh, PartitionSpec(job["mesh_axes"][0]))
    ring = [model.make_batch(rng, cfg, job, batch) for _ in range(job["ring"])]
    feed_vars = [feeds[n] for n in model.FEEDS]
    loader = fluid.DataLoader.from_generator(
        feed_vars, capacity=job["loader_capacity"], sharding=sharding,
        device=None if sharding is not None else fluid.TPUPlace(0).jax_device())
    loader.set_batch_generator(lambda: itertools.cycle(ring))
    timed = TimedLoader(loader)

    warm, inflight = job["warmup_steps"], job["max_inflight"]
    tracer = common.TracedPart(run.trace, run.trace_dir, float("inf"))
    t_dispatch, losses = [], []
    mark = {}

    def on_dispatch(step, feed):
        t = common.now()
        if step == warm:  # the window opens
            mark.update(t0=t, wait0=timed.wait_s, mon0=common.monitor_snapshot())
            timed.t_end = t + run.seconds
            tracer.t_start = timed.t_end - job["trace_seconds"]
        if tracer.due(t):
            tracer.start()
            mark["traced_from"] = step
        t_dispatch.append(t)

    stats = fluid.train_loop(
        exe, program, timed, [loss], scope=scope, max_inflight=inflight,
        log_period=job["log_period"], on_dispatch=on_dispatch,
        on_logged=lambda i, vals: losses.append((i, float(np.asarray(vals[0]).reshape(-1)[0]))))
    t1 = common.now()
    mon1 = common.monitor_snapshot()
    tracer.stop()
    timed.close()

    # -- what the window held.  on_dispatch(i) follows the completion of
    # step i - inflight; the completions inside [t0, t_end] are those
    # stamped after the window's first `inflight` dispatches.
    t0, t_end = mark["t0"], timed.t_end
    done = t_dispatch[warm + inflight:]
    rate = arith.samples_per_s(done, batch, t0, t_end)
    attempted = stats.steps - warm
    bad_loss = [(i, v) for i, v in losses if not np.isfinite(v)]
    if bad_loss:
        reasons.append(f"non-finite loss at steps {bad_loss[:4]}")
    if not losses:
        reasons.append("no loss was resolved")
    moved = probe.after()
    if moved["dead"] or len(moved["still"]) > 0.25 * moved["params"]:
        reasons.append(f"{len(moved['still'])}/{moved['params']} parameters "
                       f"did not move, {len(moved['dead'])} of them without "
                       f"a live moment: {moved['dead'][:4]}")
    executables = common.executables_of(exe, [main])
    info("train", batch=batch, steps_in_window=attempted, **rate,
         loss_first=losses[0][1] if losses else None,
         loss_last=losses[-1][1] if losses else None, n_losses=len(losses),
         params=moved["params"], params_still=len(moved["still"]),
         max_inflight_seen=stats.max_inflight_seen,
         traced_from_step=mark.get("traced_from"))
    return Outcome(
        correct=not reasons, attempted=attempted,
        failed=len(bad_loss) * job["log_period"] if bad_loss else 0,
        end_to_end={"train_samples_per_s": rate["samples_per_s"],
                    "setup_s": t0 - run.t_process},
        stats={"window_s": t_end - t0, "batch": batch, "chips": chips,
               "loader_wait_s": timed.wait_s - mark["wait0"],
               "loop_s": t1 - t0, "steps": attempted, **rate},
        window=(t0, t_end), executables=executables,
        scope_of=common.scopes_of(executables) if run.trace else {},
        monitor_delta={"setup": mark["mon0"],
                       "window": common.delta(mon1, mark["mon0"])},
        reasons=reasons)
