"""Serving bench: offered-load throughput, TTFT/TPOT tails, chaos soak.

The acceptance instrument for the serving engine (mlsl_tpu/serve/):

- **load row**: requests submitted at a fixed offered rate against one
  engine on the CPU proof mesh — tokens/s, TTFT p50/p99 and TPOT p50/p99
  (per-step wall time over steps that had in-flight work), plus the
  429-rejection count. Two routes ("short"/"long") exercise the per-route
  metric labels.
- **chaos row**: the same load with a ``serve.decode`` hang armed — the
  degraded-not-down proof. A hang is a slow step, not an exception: the
  TPOT window breaches, the SLA ladder sheds, the queue drains, and every
  request still completes with zero unhandled exceptions; idle ticks after
  the drain show the ladder recovering.
- **parity rows**: every token the paged decode serves within a logit
  tolerance of the unpaged full-context oracle's best (float32; the gap and
  its tolerance are in the row, as chip_smoke.py P3 walks it), and the
  int8-paged variant in near-total token agreement with it (the exit code;
  timing never gates).

Off-TPU the numbers are CPU-mesh proof numbers, tagged ``backend: cpu`` —
scheduling behaviour and parity are real; absolute tokens/s is not measured
on the chip yet (ROADMAP S1/S7).

Usage: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
       python benchmarks/serving_bench.py [--smoke]

--smoke trims the request count for the tier-1 wiring (tests/test_serve.py,
the ``bench_smoke`` marker). The full grid belongs to the capture run.
"""

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def _pct(vals, p):
    if not vals:
        return None
    vals = sorted(vals)
    return round(vals[min(len(vals) - 1, int(p * len(vals)))], 3)


def _drive(eng, prompts, max_new, rps):
    """Submit ``prompts`` at ``rps`` offered load while single-threaded
    stepping the engine; returns (reqs, rejected, tpot_samples, wall_s)."""
    import numpy as np

    from mlsl_tpu import serve

    reqs, tpots, rejected = [], [], 0
    t0 = time.monotonic()
    i = 0
    while True:
        now = time.monotonic()
        while i < len(prompts) and now - t0 >= i / rps:
            p = prompts[i]
            try:
                reqs.append(eng.submit(
                    np.asarray(p, np.int32), max_new,
                    route="long" if len(p) > 12 else "short"))
            except serve.ServeOverloadError:
                rejected += 1
            i += 1
        ts = time.monotonic()
        n = eng.step()
        if n > 0:
            tpots.append((time.monotonic() - ts) * 1e3)
        if i >= len(prompts) and n == 0 and not eng._pending:
            break
    return reqs, rejected, tpots, time.monotonic() - t0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=0)
    ap.add_argument("--max-new", type=int, default=0)
    ap.add_argument("--rps", type=float, default=0.0)
    args = ap.parse_args()

    from mlsl_tpu import sysinfo

    import numpy as np
    import jax

    if not sysinfo.on_tpu():
        os.environ.setdefault("MLSL_PALLAS_INTERPRET", "1")

    from mlsl_tpu import chaos, serve
    from mlsl_tpu.core import stats
    from mlsl_tpu.core.environment import Environment
    from mlsl_tpu.models.transformer import TransformerConfig
    from mlsl_tpu.serve.engine import oracle_generate, oracle_logit_gap

    backend = "tpu" if sysinfo.on_tpu() else "cpu"
    n_req = args.requests or (6 if args.smoke else 32)
    max_new = args.max_new or (4 if args.smoke else 12)
    rps = args.rps or (50.0 if args.smoke else 100.0)

    env = Environment.get_env()
    env.init()
    cfg = TransformerConfig(vocab=128, d_model=64, n_heads=8, head_dim=8,
                            n_blocks=2, seq_len=64, dtype="float32")
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab, size=int(rng.integers(4, 25)))
               for _ in range(n_req)]

    # -- load row -----------------------------------------------------------
    eng = serve.InferenceEngine(env, cfg, tp=1, seed=0)
    reqs, rejected, tpots, wall = _drive(eng, prompts, max_new, rps)
    done = [r for r in reqs if r.state == "done"]
    ttfts = [r.ttft_ms for r in reqs if r.ttft_ms is not None]
    tokens = sum(len(r.tokens) for r in reqs)
    print(json.dumps({
        "metric": "serving_bench", "backend": backend,
        "devices": jax.device_count(), "requests": n_req,
        "offered_rps": rps, "max_new": max_new,
        "completed": len(done), "rejected": rejected,
        "tokens_per_s": round(tokens / wall, 1) if wall > 0 else None,
        "ttft_ms": {"p50": _pct(ttfts, 0.5), "p99": _pct(ttfts, 0.99)},
        "tpot_ms": {"p50": _pct(tpots, 0.5), "p99": _pct(tpots, 0.99)},
    }), flush=True)
    eng.close()

    # -- chaos soak row: a wedged decode degrades, never dies ---------------
    stats.reset_serve_counters()
    serve.reset()
    eng = serve.InferenceEngine(env, cfg, tp=1, seed=0,
                                tpot_p99_ms=5.0 if backend == "tpu" else 200.0)
    hang_s = 0.05 if args.smoke else 0.3
    chaos.plan("serve.decode", "hang", seconds=hang_s,
               times=3 if args.smoke else 8)
    unhandled = 0
    try:
        reqs, rejected, _, _ = _drive(eng, prompts, max_new, rps)
    except Exception:
        unhandled = 1
        reqs = []
    for _ in range(40):   # idle ticks: let the ladder climb back down
        eng.step()
    chaos.clear()
    sheds = stats.SERVE_COUNTERS["shed_batch"] \
        + stats.SERVE_COUNTERS["shed_precision"] \
        + stats.SERVE_COUNTERS["shed_admission"]
    completed = sum(1 for r in reqs if r.state == "done")
    failed = sum(1 for r in reqs if r.state == "failed")
    degraded_not_down = bool(unhandled == 0 and failed == 0
                             and completed + rejected == n_req
                             and not eng._pending and not eng._active)
    print(json.dumps({
        "metric": "serving_bench_chaos", "backend": backend,
        "hang_s": hang_s, "completed": completed, "rejected": rejected,
        "failed": failed, "unhandled": unhandled, "sheds": int(sheds),
        "recoveries": int(stats.SERVE_COUNTERS["recoveries"]),
        "final_rung": serve.status()["state"],
        "degraded_not_down": degraded_not_down,
    }), flush=True)
    eng.close()

    # -- parity acceptance rows ---------------------------------------------
    serve.reset()
    eng = serve.InferenceEngine(env, cfg, tp=1, seed=0)
    probe = prompts[0]
    r = eng.submit(np.asarray(probe, np.int32), max_new)
    eng.run()
    # the decode step sums over the live pages, the oracle's prefill over
    # its padded context: logits agree to rounding, so a near-tie may fall
    # the other way. Four bf16 roundings of the largest logit, as
    # chip_smoke.py P3 allows on the chip
    served = r.result()
    paged_gap, top = oracle_logit_gap(eng, probe, served)
    paged_tol = 2 ** -6 * top
    paged_ok = len(served) == max_new and paged_gap <= paged_tol
    eng.close()

    serve.reset()
    qcfg = dataclasses.replace(env.config, serve_kv_quant=True)
    qeng = serve.InferenceEngine(env, cfg, tp=1, seed=0, config=qcfg)
    rq = qeng.submit(np.asarray(probe, np.int32), max_new)
    qeng.run()
    oracle = oracle_generate(qeng, probe, max_new)
    got = rq.result()
    # int8 KV: greedy argmax usually survives the quantization noise on
    # this tiny model; the hard gate is prefix agreement on the first token
    quant_ok = got[0] == oracle[0]
    quant_agree = sum(1 for a, b in zip(got, oracle) if a == b) / len(oracle)
    qeng.close()

    print(json.dumps({
        "metric": "serving_bench_parity", "backend": backend,
        "paged_logit_gap_vs_unpaged": round(paged_gap, 6),
        "paged_logit_gap_tolerance": round(paged_tol, 6),
        "quant_first_token_exact": bool(quant_ok),
        "quant_token_agreement": round(quant_agree, 3),
        "chaos_degraded_not_down": degraded_not_down,
    }), flush=True)
    return 0 if paged_ok and quant_ok and degraded_not_down else 1


if __name__ == "__main__":
    sys.exit(main())
