"""One run of a serving cell: an open loop at the rate the traffic file
fixes, driven from one thread. Between two ``engine.step()`` calls the loop
submits every request that has come due and times each from its due time;
every request due in the window is measured, and the run drains them after
the window has closed."""

import gc
import time

import numpy as np

from perf.lib import compare, manifest, program_spans, traffic as traffic_lib

WORST_MS = 1e9      # a failed or refused request counts as the worst
# the program's span ring, asked to hold a whole window (15 events a step and
# up to 200 steps a second leave 150,000 in 51 s; the program's own 65,536
# would lose the window's beginning): the operator's setting, serving only
RING_EVENTS = 1 << 19


def percentile(values, q):
    """Nearest-rank percentile of all values (no interpolation, no trimming)."""
    vals = sorted(values)
    if not vals:
        return float("nan")
    k = max(0, min(len(vals) - 1, int(np.ceil(q / 100.0 * len(vals))) - 1))
    return vals[k]


def warm_up(engine, traffic, vocab, record):
    """Compile the prefill, the KV write and the decode step (one shape
    each) and let the step time settle, on requests of the traffic's own
    kind that are thrown away."""
    w = traffic["warm_up"]
    rng = np.random.default_rng(0)
    reqs = [engine.submit(
        rng.integers(0, vocab, size=(w["prompt_tokens"],)).astype(np.int32),
        w["answer_tokens"]) for _ in range(w["requests"])]
    n = 0
    while not all(r.done() for r in reqs):
        t = time.perf_counter()
        engine.step()
        record.add("warmup_step_s", time.perf_counter() - t)
        n += 1
        if n > 10000:
            raise RuntimeError("warm-up requests never finished")
    bad = [r for r in reqs if r.state != "done"]
    if bad:
        raise RuntimeError(f"warm-up request failed: {bad[0].error!r}")


def run(ctx):
    args, config, traffic, record = ctx.args, ctx.config, ctx.traffic, ctx.record
    ref = manifest.reference(config)
    adapter = manifest.adapter(config)
    ctx.phase("import")
    env = adapter.environment(ring_events=RING_EVENTS)
    vocab = config["vocab_size"]
    ctx.phase("environment")

    params = ref.init_params(args.seed, config)
    ctx.phase("weights")
    engine = ctx.wrap_engine(
        adapter.Engine(env, config, traffic, params, ctx.cell["chips"]))
    del params
    ctx.phase("engine")
    warm_up(engine, traffic, vocab, record)
    ctx.phase("warm_up")
    reqs = traffic_lib.requests(args.seed, traffic, args.seconds, vocab)
    ctx.phase("inputs")
    n = len(reqs)
    spans = ctx.spans
    seconds = args.seconds
    trace_from = max(0.0, seconds - traffic.get("trace_seconds", 10)) \
        if args.trace else None

    handles = [None] * n
    seen = [0] * n
    arrivals = [[] for _ in range(n)]
    live = set()
    nxt = done = 0
    traced = [None, None]
    gc.collect()
    gc.disable()
    try:
        t_open = time.perf_counter()
        ctx.setup_s = t_open - ctx.t_ready
        while done < n:
            now = time.perf_counter() - t_open
            if trace_from is not None and traced[0] is None and now >= trace_from:
                ctx.tracer.start(python_tracer=False)
                traced[0] = time.perf_counter() - t_open
                now = traced[0]
            if ctx.tracer is not None and ctx.tracer.on and now >= seconds:
                # stamped before the stop, which takes as long as the events
                # it gathers (over a minute here): the traced window's
                # tokens are counted over the time they were traced in
                traced[1] = now
                ctx.tracer.stop()
                now = time.perf_counter() - t_open
            while nxt < n and reqs[nxt]["due"] <= now:
                r = reqs[nxt]
                try:
                    handles[nxt] = engine.submit(r["prompt"], r["max_new"])
                    live.add(nxt)
                except Exception as e:          # refused: counted, not raised
                    record.add("refused", nxt, repr(e))
                    done += 1
                record.add("submit", nxt, r["due"],
                           time.perf_counter() - t_open)
                nxt += 1
            if not live:
                if nxt < n:
                    time.sleep(max(0.0, min(
                        0.002, reqs[nxt]["due"] - (time.perf_counter() - t_open))))
                continue
            ctx_sum = sum(len(reqs[i]["prompt"]) + seen[i] for i in live)
            t0 = time.perf_counter() - t_open
            with spans("perf.engine.step"):
                inflight = engine.step()
            t1 = time.perf_counter() - t_open
            got = 0
            for i in list(live):
                h = handles[i]
                k = len(h.tokens)
                if k > seen[i]:
                    arrivals[i].extend([t1] * (k - seen[i]))
                    got += k - seen[i]
                    seen[i] = k
                if h.done():
                    live.discard(i)
                    done += 1
            record.add("engine_step", t0, t1, inflight, len(live), ctx_sum, got)
        t_end = time.perf_counter() - t_open
        if ctx.tracer is not None and ctx.tracer.on:
            traced[1] = time.perf_counter() - t_open
            ctx.tracer.stop()
    finally:
        gc.enable()
    for i in range(n):
        record.add("request", i, reqs[i]["due"], len(reqs[i]["prompt"]),
                   reqs[i]["max_new"], arrivals[i])
    ctx.device = ctx.describe_device()

    ttft, gaps, failed = [], [], 0
    for i in range(n):
        h = handles[i]
        ok = h is not None and h.state == "done" \
            and len(h.tokens) == reqs[i]["max_new"]
        if not ok:
            failed += 1
            ttft.append(WORST_MS)
            continue
        ttft.append((arrivals[i][0] - reqs[i]["due"]) * 1e3)
        gaps.extend((b - a) * 1e3 for a, b in zip(arrivals[i], arrivals[i][1:]))
    late = [s[2] - s[1] for s in record.series["submit"]]
    ctx.attempted, ctx.failed = n, failed
    ctx.end_to_end = {"ttft_p50_ms": percentile(ttft, 50),
                      "ttft_p90_ms": percentile(ttft, 90),
                      "itl_p95_ms": percentile(gaps, 95)}
    ctx.window = {"seconds": seconds, "drained_at_s": t_end, "requests": n,
                  "traced": traced, "max_batch": engine.max_batch,
                  "prompts": [len(r["prompt"]) for r in reqs],
                  "arrivals": arrivals, "ttft_ms": ttft,
                  "late_submit_ms_max": max(late) * 1e3 if late else 0.0}
    record.note(setup_s=ctx.setup_s, drained_at_s=t_end,
                late_submit_ms_max=ctx.window["late_submit_ms_max"],
                failure_counters=engine.failure_counters(), **ctx.end_to_end)

    # a sample of the finished requests, drawn from the seed, the longest in it
    finished = [i for i in range(n) if handles[i] is not None
                and handles[i].state == "done"]
    sample = []
    if finished:
        longest = max(finished, key=lambda i: len(reqs[i]["prompt"])
                      + len(handles[i].tokens))
        rng = traffic_lib.rng_for(args.seed, 4)
        others = [i for i in finished if i != longest]
        k = min(len(others), traffic["check_requests"] - 1)
        sample = [longest] + [int(i) for i in rng.choice(others, k, replace=False)]
    sequences = [(reqs[i]["prompt"], np.asarray(handles[i].tokens, np.int32))
                 for i in sample]
    engine.close()
    engine.free()
    del engine, handles
    gc.collect()
    t = time.perf_counter()
    gaps_ref = []
    if sequences:
        params = ref.init_params(args.seed, config)
        gaps_ref = ref.served_gaps(params, config, sequences)
        if ctx.control:             # perf/control.py: the lower precision
            ctx.notes["control_gaps"] = ref.served_gaps(
                params, config, sequences, control=ctx.control)
        del params
    record.note(reference_s=time.perf_counter() - t, checked_requests=sample,
                checked_tokens=int(sum(len(g) for g in gaps_ref)))
    ctx.checks = compare.serving(gaps_ref, ctx.limits)
    # traced or not: what the program's ring saw, into the step record
    program_spans.ring_summary(ctx)
