"""One run of a training cell (traffic kind ``train``).

The path is the one a user takes: ``JaxTrainer(...).fit()`` with one worker
reserving every chip the cell asks for, ``make_train_step`` with donated
state, batches through the program's ``DevicePrefetchIterator``,
``train.report`` every step. The window opens after the warm-up steps have
finished on the device and closes with ``block_until_ready``. This process
never touches JAX; all numbers are taken inside the worker.
"""

from __future__ import annotations

import json
import os
import time

from benchmarks import harness
from benchmarks.harness import log, note


def optimizer_shardings(opt, params, shardings, replicated):
    """Shardings for ``opt.init(params)``: each subtree with the parameters'
    structure (Adam's two moments) takes theirs, every other leaf is
    replicated. ``params`` may be arrays or shapes."""
    import jax

    like_params = jax.tree.structure(params)
    is_moment = lambda t: jax.tree.structure(t) == like_params  # noqa: E731
    return jax.tree.map(
        lambda t: shardings if is_moment(t) else replicated,
        jax.eval_shape(opt.init, params), is_leaf=is_moment,
    )


def sharded_train_state(init_params, opt, key, shardings, mesh) -> dict:
    """The program's ``TrainState`` with every leaf placed from the start:
    the parameters by their shardings, each subtree of the optimizer's state
    that has the parameters' structure (Adam's two moments) likewise, the
    rest replicated. ``make_train_state`` is not used: its
    ``jit(optimizer.init)(params)`` leaves the moments whole and uncommitted
    on device 0 (12.4 GB for GPT-2 XL), and the first step then runs out of
    memory slicing them (my chip run, PR 25; PERF.md lists it for the
    program to repair)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    replicated = NamedSharding(mesh, P())
    params = jax.jit(init_params, out_shardings=shardings)(key)
    opt_shardings = optimizer_shardings(opt, params, shardings, replicated)
    opt_state = jax.jit(opt.init, out_shardings=opt_shardings)(params)
    step = jax.device_put(jnp.zeros((), jnp.int32), replicated)
    return {"params": params, "opt_state": opt_state, "step": step}


def _train_loop(run: dict) -> None:
    """Inside the JaxTrainer worker, the process that owns the chips."""
    import itertools

    from benchmarks.serve_replica import (
        CompileEvents, device_report, flightrec_spans, start_trace, stop_trace,
    )

    compiles = CompileEvents()  # before anything compiles
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmarks import traffic_gen
    from benchmarks.reference.common import fold
    from ray_tpu import train
    from ray_tpu.parallel import DEFAULT_RULES, MeshSpec, make_mesh, shardings_from_logical
    from ray_tpu.train.input import DevicePrefetchIterator
    from ray_tpu.train.spmd import (
        compile_train_step, default_optimizer, make_train_step,
    )

    c, job, seed = run["config"], run["job"], run["seed"]
    devices = jax.devices()
    fam = harness.family(c)
    cfg = fam.model_config(c, job)
    mesh = make_mesh(MeshSpec(**job["mesh"]), devices)
    shardings = shardings_from_logical(fam.param_logical_specs(cfg), DEFAULT_RULES, mesh)
    opt_kw = {k: v for k, v in job["optimizer"].items() if k != "name"}
    assert job["optimizer"]["name"] == "adamw"
    opt = default_optimizer(**opt_kw)
    state = sharded_train_state(lambda k: fam.init_params(k, cfg), opt, fold(seed), shardings, mesh)
    batch_sharding = NamedSharding(mesh, P(("dp", "fsdp")))
    step = make_train_step(
        lambda p, b: fam.loss_fn(p, b, cfg, mesh=mesh), opt,
        mesh=mesh, batch_spec=P(("dp", "fsdp")), param_shardings=shardings,
        donate_state=devices[0].platform != "cpu",
    )
    host_batches = traffic_gen.train_batches(job, seed, cfg.vocab_size)
    batches = DevicePrefetchIterator(
        itertools.cycle(host_batches), sharding=batch_sharding, depth=job["prefetch_depth"]
    )
    # The jitted step, not an ahead-of-time executable: only the parameters'
    # shardings are pinned through the update, so XLA is free to lay the
    # optimizer's small leaves out differently on the way out, and the second
    # call compiles once more for that layout. After it the layout is a fixed
    # point; both programs land in the cache, and window_compiles.* watches
    # that no third one appears.
    losses = []
    for _ in range(job["warm_steps"]):
        state, metrics = step(state, next(batches))
        losses.append(metrics["loss"])
    jax.block_until_ready(state)
    compiled, _flops = compile_train_step(step, state, jax.device_put(host_batches[0], batch_sharding))
    mem = compiled.memory_analysis()
    analysis = {
        k: int(getattr(mem, k + "_size_in_bytes", 0))
        for k in ("temp", "argument", "output", "alias")
    } if mem is not None else {}
    mosaic_calls = compiled.as_text().count("tpu_custom_call")
    del compiled
    setup_s = time.time() - run["t_process_start"]

    # A traced run traces the last seconds of the window and stops the
    # profiler after the window is closed: exporting four devices' events
    # takes the profiler about a minute, which must not count as training.
    trace_at = max(0.0, run["seconds"] - job["trace_window"]["seconds"]) if run["trace"] else None
    tracing, anchor_ns, steps = False, None, 0
    t0 = time.time()
    while time.time() - t0 < run["seconds"]:
        if trace_at is not None and not tracing and time.time() - t0 >= trace_at:
            anchor_ns = start_trace(run["trace_dir"])
            tracing = True
        state, metrics = step(state, next(batches))
        losses.append(metrics["loss"])
        train.report({"loss": metrics["loss"]})
        steps += 1
    jax.block_until_ready(state)
    t1 = time.time()
    if tracing:
        stop_trace()
    train.get_context().flush()
    batches.close()
    losses = [float(x) for x in jax.device_get(losses)]
    final_step = int(state["step"])
    spans, dropped = flightrec_spans(("train",))
    report = {
        "pid": os.getpid(),
        "setup_s": setup_s, "window": [t0, t1], "steps": steps,
        "tokens_per_step": job["global_batch"] * job["seq_len"],
        "final_step": final_step, "losses": [losses[0], losses[-1]],
        "finite": all(x == x and abs(x) < 1e9 for x in losses),
        "compile_events": list(compiles.events),
        "spans": spans, "spans_dropped": dropped, "anchor_wall_ns": anchor_ns,
        "memory_analysis": analysis, "mosaic_calls": mosaic_calls,
        "mesh": {k: v for k, v in mesh.shape.items() if v > 1},
        "device": device_report(),
    }
    # The output check, with the state gone: the loss function's loss,
    # gradients and logits against the float32 reference on a seeded batch.
    del state, metrics
    report["check"] = fam.check(c, job, seed, "program", devices)
    report["compile_events_after_check"] = len(compiles.events)
    train.report({"bench": json.dumps(report)})


def run(cell: dict, args, out_dir: str) -> int:
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    config, job = harness.cell_files(cell, args.cpu_rehearsal)
    ray_tpu = harness.start_runtime(cell["chips"], args.cpu_rehearsal)
    trace_dir = os.path.join(out_dir, "trace")
    try:
        log(f"JaxTrainer.fit(): one worker reserving TPU={cell['chips']}")
        result = JaxTrainer(
            _train_loop,
            train_loop_config={
                "config": config, "job": job, "seed": args.seed,
                "seconds": args.seconds, "trace": bool(args.trace),
                "trace_dir": trace_dir, "t_process_start": harness.T_PROCESS_START,
            },
            scaling_config=ScalingConfig(
                num_workers=1, resources_per_worker={"TPU": cell["chips"]}
            ),
            run_config=RunConfig(
                name="bench", storage_path=os.path.join(out_dir, "train_storage")
            ),
        ).fit()
    finally:
        started = harness.run_processes()
        try:
            ray_tpu.shutdown()
        finally:
            harness.wait_until_ended(started, more=harness.run_processes)
    history = result.metrics_history
    rep = json.loads(history[-1]["bench"])
    harness.check_device(rep["device"], cell["chips"], args.cpu_rehearsal)
    t0, t1 = rep["window"]
    rate = rep["steps"] * rep["tokens_per_step"] / (t1 - t0) / rep["device"]["count"]
    log(f"window {t1 - t0:.2f}s, {rep['steps']} steps, {rate:.1f} tokens/s/chip, "
        f"setup {rep['setup_s']:.1f}s")

    limits = harness.load_json(harness.HERE, "limits", cell["config"] + ".json")
    correct = all(
        harness.compared(name, rep["check"][name], limit)
        for name, limit in limits["limits"].items()
    )
    reported = [h["loss"] for h in history if "loss" in h]
    broken = int(not rep["finite"]) + int(len(reported) != rep["steps"]) + int(
        rep["final_step"] != rep["steps"] + job["warm_steps"]
    )
    correct &= harness.compared("steps_lost_or_not_finite", broken, 0)
    note(f"losses of the first and last step {rep['losses']}; check loss (program, reference) {rep['check']['loss']}")
    note(f"allocator peak {rep['device']['memory_peak_bytes']} B of {rep['device']['bytes_limit']} B; "
         f"the compiler's analysis of the step: {rep['memory_analysis']}; "
         f"Mosaic custom calls in the step: {rep['mosaic_calls']}")
    ev = rep["compile_events"]
    note(f"compile cache: {sum(k == 'cache_hit' for _, k in ev)} hits, "
         f"{sum(k == 'cache_miss' for _, k in ev)} misses over step and check")

    reduced = None
    if args.trace:
        reduced = harness.reduce_trace(
            trace_dir, rep["anchor_wall_ns"], rep["spans"], out_dir, args.cpu_rehearsal
        )
    records = {
        "cell": cell, "config": config, "traffic": job, "seconds": args.seconds,
        "setup_s": rep["setup_s"], "window": [t0, t1], "train": rep,
        "spans": rep["spans"], "compile_events": ev, "trace": reduced,
        "peaks": None if args.cpu_rehearsal else harness.peaks_for(rep["device"]["kind"]),
    }
    harness.save(out_dir, "worker.json", {k: v for k, v in rep.items() if k != "spans"})
    metrics = harness.read_metrics(cell["name"], bool(args.trace), records)
    device, breakdown = harness.device_line(rep["device"], reduced)
    harness.result_line(correct, rep["steps"], broken, metrics, device, breakdown)
    return 0
