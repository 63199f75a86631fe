"""Headline benchmark: ResNet-50 data-parallel training step through the framework.

BASELINE.md config 5 ("Caffe ResNet-50 data-parallel Session/Operation graph,
per-layer grad sync"). The reference repo publishes no numbers (BASELINE.md), so the
baseline is self-generated: the same model/batch trained by a single fused raw-JAX jit
(loss+grad+SGD, no framework). vs_baseline = raw_step_time / framework_step_time —
1.0 means the MLSL-style per-layer Start/Wait graph adds zero overhead over the best
monolithic XLA program; >1.0 means we beat it.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
"""

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="tiny shapes (CI/CPU)")
    ap.add_argument("--iters", type=int, default=54)
    ap.add_argument("--warmup", type=int, default=6)
    ap.add_argument("--batch", type=int, default=None,
                    help="global minibatch (default 256 full / 8 quick)")
    args = ap.parse_args()

    # One process for the chip: JAX is imported here and fails loudly if the
    # device cannot be had (no probe child — it would hold the chip). The
    # platform comes from JAX_PLATFORMS, the compile cache from
    # Environment.init (sysinfo.resolve_compile_cache).
    import jax
    import jax.numpy as jnp

    import mlsl_tpu as mlsl
    from mlsl_tpu.models import resnet
    from mlsl_tpu.models.train import DataParallelTrainer

    if args.quick:
        batch, hw, classes = args.batch or 8, 64, 10
    else:
        # Large batch: the MXU wants large batched matmuls (BASELINE.md
        # config 5 is batch 256). A batch that does not fit is an error
        # naming the next size to try, never a silent halving.
        batch, hw, classes = args.batch or 256, 224, 1000

    n_dev = len(jax.devices())
    env = mlsl.Environment.get_env().init()
    dist = env.create_distribution(n_dev, 1)
    sess = env.create_session()
    sess.set_global_minibatch_size(batch)

    params = resnet.init_resnet50(jax.random.PRNGKey(0), num_classes=classes)
    trainer = DataParallelTrainer(
        env, dist, sess, params,
        resnet.loss_fn, resnet.layer_names(params), resnet.layer_subtree,
        lr=0.05,
    )

    rng = np.random.default_rng(0)
    x = rng.normal(size=(batch, hw, hw, 3)).astype(np.float32)
    y = rng.integers(0, classes, size=(batch,)).astype(np.int32)
    fw_batch = trainer.shard_batch(x, y)

    # --- raw-JAX baseline: one fused jit, same math ---
    lr, data_size = 0.05, dist.get_process_count_data()
    mesh = dist.topology.mesh
    from jax.sharding import NamedSharding, PartitionSpec as P

    raw_params = jax.device_put(params, NamedSharding(mesh, P()))
    xb = jax.device_put(
        jnp.asarray(x), NamedSharding(mesh, P(("replica", "data", "seq", "model")))
    )
    yb = jax.device_put(
        jnp.asarray(y), NamedSharding(mesh, P(("replica", "data", "seq", "model")))
    )

    @jax.jit
    def raw_step(p, bx, by):
        loss, grads = jax.value_and_grad(resnet.loss_fn)(p, (bx, by))
        return loss, jax.tree.map(lambda w, g: w - lr * g, p, grads)

    # Every timing block ends in a host read-back of one param element
    # (benchmarks/_common.device_sync); whether block_until_ready would do
    # on this machine is recorded in PERF.md, and the harness is S2's.
    from benchmarks._common import device_sync as _sync

    def run_fw(n):
        for _ in range(n):
            trainer.step(fw_batch)
        _sync(trainer.params)

    def run_raw(n):
        nonlocal raw_params
        for _ in range(n):
            loss, raw_params = raw_step(raw_params, xb, yb)
        _sync(raw_params)

    # Forced per-layer trainer: bypasses the fused shortcut so the Session/
    # Operation Start/Wait machinery (reference loop mlsl_test.cpp:660-698) is
    # itself timed on the chip, not just on the CPU mesh.
    sess_pl = env.create_session()
    sess_pl.set_global_minibatch_size(batch)
    # overlap_compiled=False EXPLICITLY: this row is the HOST Start/Wait
    # engine by definition — an exported MLSL_OVERLAP_COMPILED=1 must not
    # silently reroute it through the compiled engine and collapse the
    # host-vs-compiled comparison into compiled-vs-compiled.
    trainer_pl = DataParallelTrainer(
        env, dist, sess_pl, params,
        resnet.loss_fn, resnet.layer_names(params), resnet.layer_subtree,
        lr=0.05, force_graph_path=True, overlap_compiled=False,
    )

    def run_pl(n):
        for _ in range(n):
            trainer_pl.step(fw_batch)
        _sync(trainer_pl.params)

    # Compiled overlap engine (comm/overlap.py): the same per-layer schedule
    # as trainer_pl but emitted IN-GRAPH as one single-dispatch program —
    # per_layer_compiled_ms / compiled_vs_fused track whether moving the comm
    # schedule into the compiled program beats the host Start/Wait loop
    # (BENCH_r05's per_layer_vs_fused: 1.0 is the number this exists to move).
    trainer_cmp = None
    try:
        sess_cmp = env.create_session()
        sess_cmp.set_global_minibatch_size(batch)
        trainer_cmp = DataParallelTrainer(
            env, dist, sess_cmp, params,
            resnet.loss_fn, resnet.layer_names(params), resnet.layer_subtree,
            lr=0.05, overlap_compiled=True, force_graph_path=True,
        )
        if trainer_cmp._overlap is None:
            trainer_cmp = None
    except Exception as e:
        print(f"bench: compiled overlap trainer skipped ({e})", file=sys.stderr)

    def run_cmp(n):
        for _ in range(n):
            trainer_cmp.step(fw_batch)
        _sync(trainer_cmp.params)

    # warm up all compiled programs, then measure in ALTERNATING blocks so slow
    # machine drift hits all sides equally; medians of per-block means.
    try:
        run_fw(args.warmup)
        run_raw(args.warmup)
        run_pl(args.warmup)
        if trainer_cmp is not None:
            run_cmp(args.warmup)
    except Exception as e:
        if _is_oom(e):
            # no re-exec: this process holds the chip, and a number taken at
            # another batch is another measurement — the caller asks for it
            sys.exit(f"bench: batch {batch} does not fit on this device "
                     f"({e}); rerun with --batch {batch // 2}")
        raise
    # Many short alternating blocks + medians keep a bad draw from skewing
    # any one side.
    n_blocks = min(9, max(1, args.iters))
    per_block = args.iters // n_blocks  # >= 1; at most n_blocks-1 iters truncated
    fw_blocks, raw_blocks, pl_blocks, cmp_blocks = [], [], [], []
    for _ in range(n_blocks):
        t0 = time.perf_counter()
        run_fw(per_block)
        fw_blocks.append((time.perf_counter() - t0) / per_block * 1e3)
        t0 = time.perf_counter()
        run_raw(per_block)
        raw_blocks.append((time.perf_counter() - t0) / per_block * 1e3)
        t0 = time.perf_counter()
        run_pl(per_block)
        pl_blocks.append((time.perf_counter() - t0) / per_block * 1e3)
        if trainer_cmp is not None:
            t0 = time.perf_counter()
            run_cmp(per_block)
            cmp_blocks.append((time.perf_counter() - t0) / per_block * 1e3)
    fw_ms = statistics.median(fw_blocks)
    raw_ms = statistics.median(raw_blocks)
    pl_ms = statistics.median(pl_blocks)
    cmp_ms = statistics.median(cmp_blocks) if cmp_blocks else None
    # the fastest block beside the median (ratios still come from medians of
    # adjacent blocks, which drift cannot skew)
    fw_best = min(fw_blocks)

    # Input-pipeline throughput: the wire-compressed device feed
    # (mlsl_tpu.data: uint8 wire + HBM dataset cache + prefetch) feeding the
    # framework trainer — the steady-state number a real training job sees,
    # input pipeline included. Epoch 0 stages the dataset over the link in
    # uint8 (4x fewer bytes than f32); replays decode straight from HBM, so
    # the timed loop measures compute + decode, not the host link.
    pipe_ms = h2d_mbps = None
    input_stall_ms = wire_mb_per_batch = feed_cache_hits = None
    feed_cache_state = None
    loader = None
    try:
        from mlsl_tpu.core import stats as core_stats
        from mlsl_tpu.data import synthetic_source

        n_data = 8  # distinct batches; the whole "dataset" pins in HBM
        cache_mb = n_data * batch * hw * hw * 3 // (1 << 20) + 64
        loader = trainer.feed(
            lambda: synthetic_source(batch, (hw, hw, 3), classes, seed=1,
                                     steps=n_data),
            wire="uint8", cache_mb=cache_mb, epochs=None, depth=3,
        )
        it = iter(loader)
        # warm: epoch 0 stages + pins every batch, compiles the decode.
        # Sync every other step: on the 8-dev CPU proof mesh the per-layer
        # trainer queues ~54 collectives per step, and the backend wedges
        # past ~dozens in flight (the PR 2 windowed-schedule hazard) — ten
        # unsynced steps reproducibly deadlocked the rendezvous.
        for i in range(n_data + 2):
            trainer.step(next(it))
            if i % 2 == 1:
                _sync(trainer.params)
        _sync(trainer.params)
        f0 = dict(core_stats.FEED_COUNTERS)
        st0 = loader.stats()
        n_pipe = max(6, args.iters // 3)
        t0 = time.perf_counter()
        for _ in range(n_pipe):
            trainer.step(next(it))
        _sync(trainer.params)
        pipe_ms = (time.perf_counter() - t0) / n_pipe * 1e3
        f1 = dict(core_stats.FEED_COUNTERS)
        st1 = loader.stats()
        # stall during the timed window; wire MB/batch over every batch that
        # actually crossed the link (steady state ships ~0 — that is the
        # point; the staged average documents the wire cost when it does)
        input_stall_ms = (st1["stall_ms"] - st0["stall_ms"]) / n_pipe
        wire_mb_per_batch = (
            f1["wire_bytes"] / 1e6 / max(int(f1["batches_staged"]), 1)
        )
        feed_cache_hits = int(f1["cache_hits"] - f0["cache_hits"])
        # Self-describing cache state for the pipeline row: a steady-state
        # (warm-cache) number and a cold staging number differ by the whole
        # h2d wire cost, and BENCH_r05's pipeline_step_ms predates the feed
        # cache entirely — a comparison that doesn't name the state is
        # meaningless (BASELINE.md 'Stale pipeline rows').
        staged = int(f1["batches_staged"])
        feed_cache_state = (
            f"warm(hits={feed_cache_hits},staged={staged})"
            if feed_cache_hits else f"cold(staged={staged})"
        )
        if args.quick:
            print(
                f"bench: pipeline row: pipeline_step_ms="
                f"{pipe_ms:.3f} feed_cache={feed_cache_state}",
                file=sys.stderr,
            )
    except Exception as e:
        print(f"bench: pipeline measurement skipped ({e})", file=sys.stderr)
    finally:
        if loader is not None:
            # the prefetch thread must not keep issuing transfers under the
            # h2d probe and overlap measurements below
            loader.close()

    # h2d bandwidth context: a timed device_put of one batch, AFTER the
    # loader is closed so no prefetch transfer contends for the transport.
    # When pipeline_step_ms >> step time, THIS is the bottleneck.
    try:
        import ml_dtypes

        from mlsl_tpu.data import synthetic_source

        bx, _ = next(iter(synthetic_source(
            batch, (hw, hw, 3), classes, seed=2, dtype=ml_dtypes.bfloat16)))
        h2d_s = float("inf")
        for _ in range(2):  # best-of-2: skip a cold-path draw
            t0 = time.perf_counter()
            _sync(jax.device_put(bx))
            h2d_s = min(h2d_s, time.perf_counter() - t0)
        h2d_mbps = bx.nbytes / 1e6 / h2d_s
    except Exception as e:
        print(f"bench: h2d probe skipped ({e})", file=sys.stderr)

    # Overlap quantification (the point of the async Start/Wait engine —
    # reference eplib newest-first allreduce, eplib/allreduce_pr.c:76-79):
    # isolation-replay each grad collective, then account a few UN-TIMED steps
    # and report the fraction of pure-comm time hidden behind compute. On a
    # single attached chip the gradient group is degenerate (no comm at all,
    # previously emitted null), so the per-layer overlap trajectory is instead
    # tracked on the 8-device CPU proof mesh in a subprocess — same per-layer
    # Start/Test engine, tagged with overlap_backend so rows stay comparable.
    overlap = overlap_backend = overlap_iso = None
    try:
        st = sess_pl.get_stats()
        if not st._isolation_slot_ns:  # MLSL_STATS=1 already replayed at commit
            st.collect_isolation_stats()
        st.reset()  # drop compile/warmup/timed-loop history: account ONLY these steps
        st.start()
        for _ in range(3):
            trainer_pl.step(fw_batch)
        _sync(trainer_pl.params)
        st.stop()
        # isolation-replay overlap (the PR 2 methodology): reported as its
        # own field when the chip's comm groups are live — the method chain
        # below owns the headline overlap_fraction + its method tag
        overlap_iso = st.get_overlap_fraction()
        st.print_()
    except Exception as e:
        print(f"bench: overlap report skipped ({e})", file=sys.stderr)
    # Method chain for the headline number — the tag ALWAYS names the method
    # (a null pair let the BENCH_r05 overlap regression pass unnoticed):
    #   device-trace:      span-derived estimate from THIS device's obs
    #                      wait/dispatch spans (needs live gradient requests)
    #   subprocess-probe:  the 8-dev CPU proof-mesh per-layer schedule
    #   skipped:<reason>:  nothing could produce a number, and why
    try:
        overlap, trace_reason = _overlap_from_trace(trainer_pl, fw_batch, _sync)
        if overlap is not None:
            overlap_backend = "device-trace"
    except Exception as e:
        trace_reason = repr(e)[:120]
    if overlap is None:
        print(f"bench: device-trace overlap unavailable ({trace_reason}); "
              f"falling back to the subprocess probe", file=sys.stderr)
        overlap, overlap_backend = _overlap_probe_cpu_mesh()

    # Telemetry-plane latency distributions (obs/metrics.py): the standard
    # row carries step p50/p99 and dispatch->wait p99 from the SAME
    # histogram registry a production scrape reads, so the bench numbers
    # and the /metrics numbers share one definition. Collected over a short
    # untimed window (the overlap-probe pattern) against a fresh registry;
    # a user-armed MLSL_METRICS registry is restored untouched.
    step_p50 = step_p99 = wait_p99 = None
    try:
        step_p50, step_p99, wait_p99 = _latency_percentiles(
            trainer, trainer_pl, fw_batch, _sync
        )
    except Exception as e:
        print(f"bench: latency percentiles skipped ({e})", file=sys.stderr)

    # Two-tier hierarchical-vs-flat ratio (comm/algos/hier.py): tracked on
    # the synthetic 8-dev two-tier CPU mesh with the DCN bandwidth-delay
    # simulator (benchmarks/hier_bench.py) — a single attached chip has no
    # second tier, so like the overlap probe this keeps the trajectory in
    # the record with an explicit backend tag either way.
    hier_vs_flat, hier_backend = _hier_probe_cpu_mesh()

    # Serving-plane row (mlsl_tpu/serve): offered-load tokens/s and TTFT
    # p50 from benchmarks/serving_bench.py --smoke on the CPU proof mesh,
    # plus the chaos degraded-not-down verdict — same explicit-tag
    # contract as the hier/overlap probes.
    serve_row, serve_backend = _serve_probe_cpu_mesh()

    # Achieved TFLOP/s and MFU for the framework step. FLOPs come from XLA's own
    # cost model on the compiled baseline step (identical math to the framework
    # step); peak from the device kind.
    tflops = mfu = tflops_best = mfu_best = None
    device_kind = jax.devices()[0].device_kind
    # an unlisted TPU kind raises here, outside the guard below; the CPU
    # (--quick) has no peak and reports no MFU
    peak = _peak_tflops(device_kind) if jax.default_backend() == "tpu" else None
    try:
        compiled = raw_step.lower(raw_params, xb, yb).compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        flops = float(ca.get("flops", 0.0))
        if flops > 0:
            tflops = flops / (fw_ms / 1e3) / 1e12
            tflops_best = flops / (fw_best / 1e3) / 1e12
            if peak:
                mfu = tflops / peak
                mfu_best = tflops_best / peak
    except Exception as e:  # cost_analysis unsupported on some backends
        print(f"bench: cost_analysis unavailable ({e})", file=sys.stderr)

    # Secondary evidence: transformer training throughput (tokens/s) through
    # the HybridTrainer on the same chip — the long-context workload family.
    tfm_tok_s = tfm_ms = tfm_mfu_model = None
    if not args.quick:
        try:
            tfm_tok_s, tfm_ms, tfm_mfu_model = _transformer_throughput(env)
        except Exception as e:
            print(f"bench: transformer throughput skipped ({e})", file=sys.stderr)

    result = {
        "metric": "resnet50_dp_train_step_time",
        "value": round(fw_ms, 3),
        "unit": "ms",
        "vs_baseline": round(raw_ms / fw_ms, 4),
        "best_ms": round(fw_best, 3),
        "per_layer_ms": round(pl_ms, 3),
        "per_layer_vs_fused": round(fw_ms / pl_ms, 4),
        "per_layer_compiled_ms": round(cmp_ms, 3) if cmp_ms else None,
        "compiled_vs_fused": round(fw_ms / cmp_ms, 4) if cmp_ms else None,
        "overlap_fraction": round(overlap, 4) if overlap is not None else None,
        "overlap_backend": overlap_backend,
        "overlap_fraction_isolation": (
            round(overlap_iso, 4) if overlap_iso is not None else None
        ),
        "hier_vs_flat": (
            round(hier_vs_flat, 4) if hier_vs_flat is not None else None
        ),
        "hier_backend": hier_backend,
        "step_ms_p50": round(step_p50, 3) if step_p50 is not None else None,
        "step_ms_p99": round(step_p99, 3) if step_p99 is not None else None,
        "dispatch_wait_p99_ms": (
            round(wait_p99, 3) if wait_p99 is not None else None
        ),
        "batch": batch,
        "pipeline_step_ms": round(pipe_ms, 3) if pipe_ms is not None else None,
        "images_per_s": round(batch / (pipe_ms / 1e3)) if pipe_ms else None,
        "pipeline_efficiency": (
            round(fw_ms / pipe_ms, 4) if pipe_ms else None
        ),
        "input_stall_ms": (
            round(input_stall_ms, 3) if input_stall_ms is not None else None
        ),
        "wire_mb_per_batch": (
            round(wire_mb_per_batch, 3) if wire_mb_per_batch is not None
            else None
        ),
        "feed_cache_hits": feed_cache_hits,
        "feed_cache_state": feed_cache_state,
        "h2d_mbps": round(h2d_mbps, 1) if h2d_mbps else None,
        "tflops": round(tflops, 3) if tflops else None,
        "mfu": round(mfu, 4) if mfu else None,
        "tflops_best": round(tflops_best, 3) if tflops_best else None,
        "mfu_best": round(mfu_best, 4) if mfu_best else None,
        "transformer_tok_s": round(tfm_tok_s) if tfm_tok_s else None,
        "transformer_step_ms": round(tfm_ms, 3) if tfm_ms else None,
        "transformer_mfu_model": (round(tfm_mfu_model, 4)
                                  if tfm_mfu_model else None),
        "serve_tokens_per_s": (serve_row or {}).get("tokens_per_s"),
        "serve_ttft_p50_ms": ((serve_row or {}).get("ttft_ms") or {}).get("p50"),
        "serve_chaos_degraded_not_down": (
            (serve_row or {}).get("chaos_degraded_not_down")
        ),
        "serve_backend": serve_backend,
        "device": device_kind,
    }
    print(json.dumps(result))


def _latency_percentiles(trainer, trainer_pl, batch, sync,
                         fw_steps: int = 5, pl_steps: int = 3):
    """-> (step_ms_p50, step_ms_p99, dispatch_wait_p99_ms) from the metrics
    histogram registry over a short live window: ``fw_steps`` standard
    trainer steps feed the step_ms histogram, ``pl_steps`` per-layer steps
    feed the dispatch->wait latency histogram (the standard trainer may ride
    the fused program, which builds no CommRequest). A registry the user
    armed (MLSL_METRICS=1) is swapped out and restored so the bench window
    never pollutes their series."""
    from mlsl_tpu.obs import metrics as obs_metrics

    prev = obs_metrics._registry
    # cadence effectively off: this window wants pure histograms, not
    # loss-readback ticks in the middle of the measurement
    reg = obs_metrics.MetricsRegistry(every=1 << 30)
    obs_metrics._registry = reg
    step_p50 = step_p99 = wait_p99 = None
    try:
        for _ in range(fw_steps):
            trainer.step(batch)
        sync(trainer.params)
        # read the step percentiles BEFORE the per-layer window: trainer_pl
        # steps feed the same step_ms histogram and would skew the standard
        # row's number with the slower host per-layer schedule
        h = reg.find("mlsl_step_ms")
        if h is not None and h.count:
            step_p50, step_p99 = h.percentile(50), h.percentile(99)
        for _ in range(pl_steps):
            trainer_pl.step(batch)
        sync(trainer_pl.params)
    finally:
        obs_metrics._registry = prev
    waits = [s for s in reg.series()
             if s.name == "mlsl_dispatch_wait_ms" and s.count]
    if waits:
        wait_p99 = max(s.percentile(99) for s in waits)
    return step_p50, step_p99, wait_p99


def _overlap_from_trace(trainer, batch, sync, steps: int = 3):
    """-> (overlap fraction or None, reason when None). Device-derived
    overlap estimate from the obs span tracer: run ``steps`` per-layer steps
    with tracing armed and report the mean of
    ``1 - exposed_wait / comm_window`` per step, where exposed_wait is the
    host time blocked inside request wait spans and comm_window spans the
    first request submit to the last wait end (perfectly hidden comm -> wait
    spans ~0 -> fraction ~1; fully exposed comm -> waits fill the window ->
    ~0). Needs live gradient requests: a degenerate single-chip comm group
    emits no wait/dispatch spans, and the caller falls back to the
    subprocess probe."""
    from mlsl_tpu.obs import tracer as obs_tr

    pre_enabled = obs_tr.enabled()
    tr = obs_tr.get_tracer() or obs_tr.enable()
    fracs = []
    try:
        for _ in range(steps):
            # select this step's events by timestamp — never clear() a
            # tracer the user armed (MLSL_TRACE=1): the shared ring holds
            # their whole capture, and the flight-recorder window must
            # survive this probe
            t_mark = tr.now()
            trainer.step(batch)
            sync(trainer.params)
            evs = [ev for ev in tr.snapshot() if ev[3] >= t_mark]
            waits = [(ev[3], ev[4]) for ev in evs
                     if ev[0] == "X" and ev[1] == "wait" and ev[2] == "req"]
            submits = [ev[3] for ev in evs
                       if ev[0] == "i" and ev[1] == "submit"]
            if not waits or not submits:
                return None, "no request spans (degenerate comm group)"
            window = max(ts + d for ts, d in waits) - min(submits)
            if window <= 0:
                continue
            exposed = sum(d for _, d in waits)
            fracs.append(max(0.0, min(1.0, 1.0 - exposed / window)))
    finally:
        if not pre_enabled:
            obs_tr.disable()
    if not fracs:
        return None, "no usable comm windows"
    return sum(fracs) / len(fracs), None


_OVERLAP_PROBE_SRC = """\
import jax
import numpy as np
import mlsl_tpu as mlsl
from mlsl_tpu.models.mlp import LAYERS, get_layer, init, loss_fn
from mlsl_tpu.models.train import DataParallelTrainer
env = mlsl.Environment.get_env().init()
dist = env.create_distribution(8, 1)
sess = env.create_session()
sess.set_global_minibatch_size(32)
t = DataParallelTrainer(env, dist, sess, init(jax.random.PRNGKey(0)), loss_fn,
                        LAYERS, get_layer, lr=0.1, force_graph_path=True,
                        overlap_updates=True)
rng = np.random.default_rng(0)
x = rng.normal(size=(32, 8)).astype(np.float32)
y = rng.integers(0, 4, size=(32,)).astype(np.int32)
b = t.shard_batch(x, y)
st = sess.get_stats()
for _ in range(5):
    t.step(b)
fracs = []
for _ in range(5):
    st.collect_isolation_stats()  # contemporaneous replay: load drift on the
    st.reset()                    # shared box must hit both sides of the ratio
    st.start()
    for _ in range(8):
        t.step(b)
    st.stop()
    f = st.get_overlap_fraction()
    if f is not None:
        fracs.append(f)
# best-of-trials: the schedule's demonstrated hiding capability — one load
# spike zeroes a trial (exposed > iso), the same reason bench.py reports
# fw_best/tflops_best alongside medians (TUNING.md section 0)
import json
print("OVERLAP=" + json.dumps(max(fracs) if fracs else None))
"""


def _overlap_probe_cpu_mesh(timeout: float = 600.0, attempts: int = 2):
    """-> (overlap_fraction or None, backend tag — NEVER None). The per-layer
    comm/compute overlap measured on the 8-device CPU proof mesh in a
    subprocess, via the test-driven per-layer loop (overlap_updates: each
    layer's update runs the moment its collective lands — the schedule the
    reference's canonical loop uses, mlsl_test.cpp:660-698). A CPU-mesh
    figure in the device row: ROADMAP S1 takes it out.

    A probe that cannot produce a number records WHY in the backend tag
    (``skipped:<reason>``) instead of leaving both fields null — a null
    overlap with no tag is indistinguishable from the probe never running,
    which is exactly how the BENCH_r05 overlap regression went unnoticed."""
    import subprocess

    env_vars = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                   + " --xla_force_host_platform_device_count=8").strip(),
    )
    # fault-injection/watchdog config armed for the CHIP run must not leak
    # into the probe's training loop (an armed hang would wedge it to
    # timeout), and the span tracer must not tax a comparative timing probe
    env_vars.pop("MLSL_CHAOS", None)
    env_vars.pop("MLSL_WATCHDOG_TIMEOUT", None)
    env_vars["MLSL_TRACE"] = "0"
    # a chip-run tuner sweep (MLSL_TUNE) must not re-run — or its chip-keyed
    # profile load — inside the CPU-mesh probe (mismatched fingerprint), and
    # a chip-targeted algorithm override must not reroute the probe's
    # baseline collectives either
    env_vars.pop("MLSL_TUNE", None)
    env_vars.pop("MLSL_TUNE_PROFILE", None)
    env_vars.pop("MLSL_ALGO", None)
    # chip-sized feed knobs (wire dtype / HBM cache budget) have no business
    # in the probe's tiny MLP loop
    for k in ("MLSL_FEED_WIRE_DTYPE", "MLSL_FEED_CACHE_MB",
              "MLSL_FEED_DEPTH"):
        env_vars.pop(k, None)
    # the probe measures the HOST per-layer schedule: a chip-armed compiled
    # overlap engine would reroute its trainer through the in-graph path
    for k in ("MLSL_OVERLAP_COMPILED", "MLSL_OVERLAP_STAGES"):
        env_vars.pop(k, None)
    # a chip-armed two-tier split would make the probe's baseline requests
    # eligible for the hier lowering; the probe wants the flat schedule
    for k in ("MLSL_MESH_TIERS", "MLSL_HIER_DCN_CODEC"):
        env_vars.pop(k, None)
    reason = "unknown"
    for attempt in range(attempts):
        try:
            out = subprocess.run(
                [sys.executable, "-c", _OVERLAP_PROBE_SRC],
                capture_output=True, text=True, timeout=timeout, env=env_vars,
                cwd=os.path.dirname(os.path.abspath(__file__)),
            )
            for line in out.stdout.splitlines():
                if line.startswith("OVERLAP="):
                    v = json.loads(line[len("OVERLAP="):])
                    if v is not None:
                        return float(v), "subprocess-probe"
            tail = (out.stderr or "").strip().splitlines()
            reason = (f"no-number rc={out.returncode}"
                      + (f" {tail[-1][:120]}" if tail else ""))
        except subprocess.TimeoutExpired:
            reason = f"timeout {timeout:.0f}s"
        except Exception as e:
            reason = repr(e)[:160]
        print(f"bench: cpu overlap probe attempt {attempt + 1}/{attempts} "
              f"failed ({reason})", file=sys.stderr)
    return None, f"skipped:{reason}"


def _hier_probe_cpu_mesh(timeout: float = 900.0):
    """-> (hier_vs_flat or None, backend tag — NEVER None). Runs
    benchmarks/hier_bench.py --smoke on the synthetic 8-dev two-tier CPU
    mesh (MLSL_MESH_TIERS=2x4, DCN bandwidth-delay simulator armed) and
    parses its summary ratio. Same explicit-tag contract as the overlap
    probe: a probe that cannot produce a number records WHY."""
    import subprocess

    env_vars = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        MLSL_MESH_TIERS="2x4",
        XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                   + " --xla_force_host_platform_device_count=8").strip(),
    )
    for k in ("MLSL_CHAOS", "MLSL_WATCHDOG_TIMEOUT", "MLSL_TRACE",
              "MLSL_TUNE", "MLSL_TUNE_PROFILE", "MLSL_ALGO",
              "MLSL_HIER_DCN_CODEC"):
        env_vars.pop(k, None)
    here = os.path.dirname(os.path.abspath(__file__))
    reason = "unknown"
    try:
        out = subprocess.run(
            [sys.executable, os.path.join(here, "benchmarks", "hier_bench.py"),
             "--smoke"],
            capture_output=True, text=True, timeout=timeout, env=env_vars,
            cwd=here,
        )
        for line in out.stdout.splitlines():
            try:
                row = json.loads(line)
            except ValueError:
                continue
            if row.get("metric") == "hier_vs_flat":
                v = row.get("value")
                if v is not None:
                    return float(v), "cpu-mesh-sim"
                reason = row.get("reason", "no value")
        tail = (out.stderr or "").strip().splitlines()
        if reason == "unknown":
            reason = (f"no-row rc={out.returncode}"
                      + (f" {tail[-1][:120]}" if tail else ""))
    except subprocess.TimeoutExpired:
        reason = f"timeout {timeout:.0f}s"
    except Exception as e:
        reason = repr(e)[:160]
    print(f"bench: hier probe failed ({reason})", file=sys.stderr)
    return None, f"skipped:{reason}"


def _serve_probe_cpu_mesh(timeout: float = 900.0):
    """-> (serving row dict or None, backend tag — NEVER None). Runs
    benchmarks/serving_bench.py --smoke on the 8-dev CPU proof mesh and
    merges its load row with the parity row's chaos verdict. Same
    explicit-tag contract as the hier probe: a probe that cannot produce
    numbers records WHY."""
    import subprocess

    env_vars = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                   + " --xla_force_host_platform_device_count=8").strip(),
    )
    for k in ("MLSL_CHAOS", "MLSL_WATCHDOG_TIMEOUT", "MLSL_TRACE",
              "MLSL_TUNE", "MLSL_TUNE_PROFILE", "MLSL_ALGO",
              "MLSL_MESH_TIERS"):
        env_vars.pop(k, None)
    here = os.path.dirname(os.path.abspath(__file__))
    reason = "unknown"
    try:
        out = subprocess.run(
            [sys.executable,
             os.path.join(here, "benchmarks", "serving_bench.py"), "--smoke"],
            capture_output=True, text=True, timeout=timeout, env=env_vars,
            cwd=here,
        )
        row = parity = None
        for line in out.stdout.splitlines():
            try:
                r = json.loads(line)
            except ValueError:
                continue
            if r.get("metric") == "serving_bench":
                row = r
            elif r.get("metric") == "serving_bench_parity":
                parity = r
        if row is not None:
            if parity is not None:
                row["chaos_degraded_not_down"] = parity.get(
                    "chaos_degraded_not_down")
            return row, "cpu-mesh-sim"
        tail = (out.stderr or "").strip().splitlines()
        reason = (f"no-row rc={out.returncode}"
                  + (f" {tail[-1][:120]}" if tail else ""))
    except subprocess.TimeoutExpired:
        reason = f"timeout {timeout:.0f}s"
    except Exception as e:
        reason = repr(e)[:160]
    print(f"bench: serve probe failed ({reason})", file=sys.stderr)
    return None, f"skipped:{reason}"


def _is_oom(e: BaseException) -> bool:
    s = str(e)
    return ("RESOURCE_EXHAUSTED" in s or "Out of memory" in s
            or "out of memory" in s or "OOM" in s)


def _transformer_throughput(env):
    """Tokens/s for a d512 x 8-block transformer train step (batch 32, seq 512)
    on the attached device, via the HybridTrainer on ONE device (dp=sp=tp=1 and
    devices pinned to the first chip, so multi-device hosts don't trip the
    replica-count check)."""
    import numpy as np

    from mlsl_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(
        vocab=32768, d_model=512, n_heads=8, head_dim=64, n_blocks=8,
        seq_len=512,
    )
    batch = 32
    trainer = tfm.HybridTrainer(
        env, cfg, 1, 1, 1, batch=batch, lr=0.1, devices=env.devices[:1]
    )
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab, size=(batch, cfg.seq_len)).astype(np.int32)
    labels = np.roll(toks, -1, axis=1)
    tb, lb = trainer.shard_tokens(toks, labels)

    from benchmarks._common import timed

    ms = timed(lambda: trainer.step(tb, lb), iters=36, warmup=4, blocks=6)
    from benchmarks._common import model_flops

    peak = _peak_tflops(env.devices[0].device_kind)
    mfu_model = model_flops(cfg, batch) / (ms / 1e3) / 1e12 / peak
    return batch * cfg.seq_len / (ms / 1e3), ms, mfu_model


#: dense bf16 peak TFLOP/s per chip by ``device_kind`` substring (Google
#: Cloud TPU documentation, system architecture pages)
_PEAK_TFLOPS = (
    ("v5 lite", 197.0),   # v5e
    ("v5e", 197.0),
    ("v5p", 459.0),
    ("v6 lite", 918.0),   # v6e, Trillium
    ("v6e", 918.0),
    ("v4", 275.0),
    ("v3", 123.0),
    ("v2", 45.0),
)


def _peak_tflops(device_kind: str) -> float:
    """Dense bf16 peak TFLOP/s for a TPU device kind (the MXU's native rate,
    so fp32 models report a conservative MFU). A kind that is not in the
    table raises: an MFU against a guessed peak is a wrong number under a
    right name."""
    kind = device_kind.lower()
    for key, peak in _PEAK_TFLOPS:
        if key in kind:
            return peak
    raise ValueError(
        f"no bf16 peak on record for device kind {device_kind!r}; add it to "
        "bench._PEAK_TFLOPS with its source"
    )


if __name__ == "__main__":
    main()
