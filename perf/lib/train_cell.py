"""One run of a training cell: set-up, the first three steps followed for
``correct``, warm-up until the step time has settled, the window of whole
steps, then the reference once the program's state is freed."""

import gc
import time

import numpy as np

from perf.lib import compare, manifest, traffic as traffic_lib


def host_batches(seed, config, traffic):
    """-> (fn(step) -> host batch, or a list of host batches for a feed that
    replays them). The same for the program and for the reference."""
    if traffic["input"] == "tokens":
        return lambda step: traffic_lib.token_batch(
            seed, step, traffic["batch"], config["n_positions"],
            config["vocab_size"])
    if traffic["input"] == "images":
        return traffic_lib.image_batches(
            seed, traffic, config["image_size"], config["num_classes"])
    raise ValueError(f"traffic input {traffic['input']!r}")


def first_batches(source, n):
    if callable(source):
        return [source(i) for i in range(n)]
    return [source[i % len(source)] for i in range(n)]


def settle(step_once, traffic, record):
    """Warm-up after the last compile: at least ``settle_min`` steps, then
    until three consecutive steps agree within ``settle_tolerance``, at most
    ``settle_cap``."""
    lo, cap = traffic.get("settle_min", 3), traffic.get("settle_cap", 12)
    tol = traffic.get("settle_tolerance", 0.01)
    times = []
    while len(times) < cap:
        t = time.perf_counter()
        step_once()
        times.append(time.perf_counter() - t)
        record.add("warmup_step_s", times[-1])
        last = times[-3:]
        if len(times) >= lo and max(last) - min(last) <= tol * min(last):
            break
    return times


def run(ctx):
    import jax

    args, config, traffic, record = ctx.args, ctx.config, ctx.traffic, ctx.record
    ref = manifest.reference(config)
    adapter = manifest.adapter(config)
    ctx.phase("import")
    env = adapter.environment()
    chips = ctx.cell["chips"]
    ctx.phase("environment")

    params = ref.init_params(args.seed, config)
    jax.block_until_ready(params)
    ctx.phase("weights")
    handle = ctx.wrap_trainer(adapter.Trainer(env, config, traffic, params, chips))
    del params
    ctx.phase("trainer")
    source = host_batches(args.seed, config, traffic)
    feed = handle.feed(source)
    spans = ctx.spans
    ctx.phase("inputs")

    # the first three steps, through the window's own call and feed
    p0 = lambda: ref.init_params(args.seed, config)  # noqa: E731
    prog = {"losses": []}
    for i in range(3):
        loss = handle.step(next(feed))
        prog["losses"].append(float(np.asarray(loss).mean()))
        if i == 0:
            prog["grad"] = handle.first_gradient(ref, p0)
    jax.block_until_ready(handle.params())
    prog["delta"] = handle.delta(ref, p0)
    ctx.phase("first_three_steps")

    def one_blocked_step():
        jax.block_until_ready(handle.step(next(feed)))

    warm = settle(one_blocked_step, traffic, record)
    record.note(warmup_steps=len(warm))
    ctx.phase("settle")

    every = traffic["loss_read_back_every"]
    seconds = args.seconds
    if args.trace:
        seconds = min(seconds, traffic.get("trace_seconds", 10))
    gc.collect()
    gc.disable()
    try:
        if args.trace:
            ctx.tracer.start()
        steps, loss, before = 0, None, None
        t_open = time.perf_counter()
        ctx.setup_s = t_open - ctx.t_ready
        while True:
            t0 = time.perf_counter()
            with spans("perf.feed.next"):
                batch = next(feed)
            t1 = time.perf_counter()
            before = loss
            with spans("perf.trainer.step"):
                loss = handle.step(batch)
            t2 = time.perf_counter()
            steps += 1
            if steps % every == 0 and before is not None:
                # the loss of the step before, read while this one runs: the
                # device's queue never drains for the host's sake
                with spans("perf.loss.read_back"):
                    float(np.asarray(before).mean())
                t3 = time.perf_counter()
                record.add("step", t0 - t_open, t1 - t_open, t2 - t_open,
                           t3 - t_open)
            else:
                record.add("step", t0 - t_open, t1 - t_open, t2 - t_open)
            if time.perf_counter() - t_open >= seconds:
                break
        with spans("perf.final.wait"):
            jax.block_until_ready(loss)
        t_close = time.perf_counter()
        if args.trace:
            ctx.tracer.stop()
    finally:
        gc.enable()
    window = t_close - t_open
    final_loss = float(np.asarray(loss).mean())
    handle.close()
    ctx.device = ctx.describe_device()
    items = steps * handle.items_per_step
    ctx.window = {"seconds": window, "steps": steps, "items": items,
                  "items_per_step": handle.items_per_step,
                  "feed_wait_s": sum(s[1] - s[0] for s in record.series["step"])}
    record.note(window_s=window, steps=steps, final_loss=final_loss,
                setup_s=ctx.setup_s)
    ctx.end_to_end = {"train_rate": items / window}
    ctx.attempted, ctx.failed = steps, 0 if np.isfinite(final_loss) else steps

    # the reference, once the window has closed and the program's state is freed
    handle.free()
    del handle, feed
    gc.collect()
    t = time.perf_counter()
    want = ref.train(args.seed, config, traffic, first_batches(source, 3))
    record.note(reference_s=time.perf_counter() - t)
    ctx.checks, notes = compare.training(prog, want, ctx.limits)
    record.note(**notes, losses=prog["losses"], reference_losses=want["losses"])

