"""Statistics: online comm/compute accounting and the isolation benchmark.

Mirrors the reference Statistics engine (include/mlsl.hpp:651-726,
src/mlsl_impl_stats.cpp):

- Online accounting: every Start/Wait/Test on any entity emits an event pair; the time
  since the previous event is attributed to *compute* on the pre-event and to *comm* on
  the post-event, and bytes are attributed on Start (reference UpdateStats
  :564-668). "Cycles" are reported as nanoseconds (TPU has no rdtsc visible to the
  host; the unit is documented).

- Isolation benchmark at Commit: every registered comm request is replayed
  ISOLATION_ITERS times (first ISOLATION_SKIP discarded) with compute off, using zero
  buffers, giving the pure-communication time per iteration (reference
  CollectIsolationStats :387-562, iters/skip hardcoded :48-49).

- Table printer to mlsl_stats.log (reference :226-363).
"""

from __future__ import annotations

import collections
import contextlib
import os
import time
from typing import Deque, Dict, List, Optional, Tuple

import numpy as np
import jax

from mlsl_tpu.log import log_warning
from mlsl_tpu.obs import tracer as obs
from mlsl_tpu.types import jnp_dtype

ISOLATION_ITERS = 10
ISOLATION_SKIP = 4
STATS_OUTPUT_FILE = "mlsl_stats.log"


def stats_path(name: str = STATS_OUTPUT_FILE) -> str:
    """Where the stats log lands: ``MLSL_STATS_DIR`` (default CWD, the
    reference's behavior). Read per call, not at import — tests route it to a
    tmp dir and long-lived processes may re-point it between phases."""
    d = os.environ.get("MLSL_STATS_DIR")
    return os.path.join(d, name) if d else name

# Watchdog event record: every request the watchdog declared stuck, with its
# descriptor and how long it had been in flight. Process-wide (the watchdog
# fires from the request layer, which has no Session handle); bounded so a
# recurrently flaky interconnect cannot grow memory across recoveries — the
# full history lives in STATS_OUTPUT_FILE, appended per event below.
WATCHDOG_EVENTS: Deque[dict] = collections.deque(maxlen=256)


def record_watchdog_event(descriptor: str, phase: str, waited_s: float) -> None:
    """Called by CommRequest._watchdog_trip just before it raises
    MLSLTimeoutError."""
    evt = {
        "descriptor": descriptor,
        "phase": phase,
        "waited_s": waited_s,
        "at": time.time(),
    }
    WATCHDOG_EVENTS.append(evt)
    log_warning(
        "watchdog: request stuck in %s for %.2fs: %s", phase, waited_s, descriptor
    )
    if obs._tracer is not None:
        # flight recorder: dump the trailing window of spans around the stall
        # (the stuck epoch plus margin) so the timeout report carries the
        # timeline that led to it — the stuck request's own watchdog.trip
        # instant is already in the ring (CommRequest._watchdog_trip)
        from mlsl_tpu.obs import export as obs_export

        path = obs_export.flight_record(
            window_s=max(2 * waited_s, 30.0),
            reason=f"watchdog {phase}: {descriptor}",
        )
        if path:
            evt["flight_record"] = path
            log_warning("watchdog flight record written: %s", path)
    prof = _profile_on_trip(descriptor)
    if prof:
        evt["device_profile"] = prof
    try:
        with open(stats_path(), "a") as f:
            f.write(
                f"{'WATCHDOG':<16} {phase:<8} waited {waited_s:>10.2f} s  "
                f"{descriptor}\n"
            )
    except OSError:
        pass


#: how long the on-trip device profile samples the wedged state (seconds):
#: long enough for the profiler to catch the in-flight executable / idle
#: devices, short enough that the trip still raises promptly
PROFILE_ON_TRIP_WINDOW_S = 0.25


def _profile_on_trip(reason: str) -> Optional[str]:
    """``MLSL_PROFILE_ON_TRIP=1``: capture a short jax.profiler device trace
    of the wedged state, next to the flight record — the host timeline says
    WHERE the wait stuck, the device profile says what (if anything) the
    chips were doing under it. Best-effort by contract: a profiler failure
    (already active, unsupported backend) must never replace the
    MLSLTimeoutError the watchdog exists to raise."""
    v = (os.environ.get("MLSL_PROFILE_ON_TRIP") or "").strip().lower()
    if v in ("", "0", "false", "no", "off"):
        return None
    out_dir = os.path.join(
        obs.trace_dir(), f"profile-trip-{time.time_ns() // 1_000_000}"
    )
    try:
        jax.profiler.start_trace(out_dir)
        try:
            time.sleep(PROFILE_ON_TRIP_WINDOW_S)
        finally:
            jax.profiler.stop_trace()
    except Exception as e:  # profiler busy/unsupported: keep the trip primary
        log_warning(
            "MLSL_PROFILE_ON_TRIP capture failed (%s: %s); continuing with "
            "the host flight record only (%s)", type(e).__name__, e, reason,
        )
        return None
    log_warning("watchdog device profile written: %s", out_dir)
    return out_dir


# Bucket-round accounting (core/bucketing.py): process-wide like the watchdog
# record — buckets fire from the request layer with no Session handle. The
# aggregate counters are the tracked signal (printed by Statistics.print_ into
# STATS_OUTPUT_FILE); the bounded event ring keeps the recent per-round detail
# for diagnosis without growing memory on a long run.
BUCKET_EVENTS: Deque[dict] = collections.deque(maxlen=256)
BUCKET_COUNTERS: Dict[str, int] = {
    "rounds_dispatched": 0,   # full rounds served by one coalesced dispatch
    "rounds_fallback": 0,     # early-Wait rounds degraded to individual reqs
    "member_abandons": 0,     # members restarted mid-flight (ran individually)
    "bytes_coalesced": 0,     # member payload bytes carried by bucket rounds
    "wire_bytes_saved": 0,    # est. wire bytes compression saved vs f32 rounds
}


def record_bucket_round(
    event: str, kind: str, members: int = 0, coalesced: int = 0,
    wire_saved: int = 0,
) -> None:
    """Called by GradBucket at every round transition (dispatch / early-Wait
    fallback / member-restart abandon)."""
    if event == "dispatched":
        BUCKET_COUNTERS["rounds_dispatched"] += 1
        BUCKET_COUNTERS["bytes_coalesced"] += coalesced
        BUCKET_COUNTERS["wire_bytes_saved"] += wire_saved
    elif event == "fallback":
        BUCKET_COUNTERS["rounds_fallback"] += 1
    else:  # abandon
        BUCKET_COUNTERS["member_abandons"] += max(members, 1)
    BUCKET_EVENTS.append(
        {"event": event, "kind": kind, "members": members, "at": time.time()}
    )
    if obs._tracer is not None:
        # round transitions on the comm timeline (the dispatched round's
        # pack+Start duration is recorded by GradBucket itself)
        obs._tracer.instant(f"bucket.{event}", "bucket", kind=kind,
                            members=members)


def reset_bucket_counters() -> None:
    for k in BUCKET_COUNTERS:
        BUCKET_COUNTERS[k] = 0
    BUCKET_EVENTS.clear()


# Degradation-ladder accounting (mlsl_tpu.supervisor): breaker transitions,
# degraded dispatches, comm retries, and supervised recoveries — process-wide
# like the watchdog record (breakers fire from the request layer with no
# Session handle). Breaker transitions append a DEGRADE line to
# STATS_OUTPUT_FILE immediately (cold path — trips are rare by construction);
# per-dispatch fallbacks and retries only bump counters + the obs timeline
# (an OPEN breaker degrades every dispatch, and a file append per layer per
# step would be the new bottleneck). Statistics.print_ renders the counter
# totals as the DEGRADE summary line.
DEGRADE_EVENTS: Deque[dict] = collections.deque(maxlen=256)
DEGRADE_COUNTERS: Dict[str, int] = {
    "breaker_trips": 0,     # closed/half_open -> open transitions
    "breaker_probes": 0,    # open -> half_open probe admissions
    "breaker_resets": 0,    # half_open -> closed (healthy path re-engaged)
    "comm_retries": 0,      # rung-2 transient retries (dispatch + wait)
    "recoveries": 0,        # rung-4 supervised checkpoint restarts
}
#: degraded dispatches per subsystem (quant->plain, bucket->individual,
#: algo->lax, tracer->no-op)
DEGRADE_FALLBACKS: Dict[str, int] = {}


def record_degrade(subsystem: str, event: str, detail: str = "") -> None:
    """One ladder event: ``event`` is a breaker transition ('trip' /
    'probe' / 'reset'), a degraded dispatch ('fallback'), or a supervised
    restart ('recover'). Called by supervisor.CircuitBreaker and the
    degraded call sites."""
    if event == "trip":
        DEGRADE_COUNTERS["breaker_trips"] += 1
    elif event == "probe":
        DEGRADE_COUNTERS["breaker_probes"] += 1
    elif event == "reset":
        DEGRADE_COUNTERS["breaker_resets"] += 1
    elif event == "recover":
        DEGRADE_COUNTERS["recoveries"] += 1
    elif event == "codec_demote":
        # guardrail demotion (mlsl_tpu.codecs): counted in its own family
        # (CODEC_COUNTERS, via record_codec_demotion) — here it only joins
        # the event deque + DEGRADE file line, not the fallback counter
        pass
    else:  # fallback: one dispatch served by the degraded path
        DEGRADE_FALLBACKS[subsystem] = DEGRADE_FALLBACKS.get(subsystem, 0) + 1
    DEGRADE_EVENTS.append(
        {"subsystem": subsystem, "event": event, "detail": detail,
         "at": time.time()}
    )
    if obs._tracer is not None:
        # trip/reset instants bracket the degraded interval on the timeline;
        # fallback instants attribute each degraded dispatch
        name = f"breaker.{event}" if event != "fallback" else "degrade.fallback"
        obs._tracer.instant(name, "degrade", subsystem=subsystem,
                            detail=detail or None)
    if event in ("trip", "probe", "reset", "recover", "codec_demote"):
        try:
            with open(stats_path(), "a") as f:
                f.write(
                    f"{'DEGRADE':<16} {event.upper():<8} {subsystem:<10} "
                    f"{detail}\n"
                )
        except OSError:
            pass


# Integrity-sentinel accounting (mlsl_tpu.sentinel): gate screens/fires and
# consistency audits — process-wide like the degrade counters (the sentinel
# fires from the trainer with no Session handle). Statistics.print_ renders
# the totals as the SENTINEL line in mlsl_stats.log; gate fires and audit
# mismatches also land on the obs timeline as integrity.* instants (emitted
# by the sentinel itself, which owns the step/reason context).
SENTINEL_COUNTERS: Dict[str, int] = {
    "screened": 0,        # steps the quality gate inspected
    "gate_warn": 0,       # gate fired with response 'warn' (run continued)
    "gate_skip": 0,       # gate fired with response 'skip_step'
    "gate_rollback": 0,   # gate fired with response 'rollback' (raised)
    "audits": 0,          # cross-replica consistency audits run
    "audit_mismatch": 0,  # audits that found replica divergence
    "verified_saves": 0,  # checkpoints saved with a passing fingerprint
    "reaudits": 0,        # post-restore re-audits (rollback verification)
}


def record_sentinel(event: str) -> None:
    """One sentinel event: 'screened', 'gate_<response>', 'audits',
    'audit_mismatch', 'verified_saves', or 'reaudits'."""
    SENTINEL_COUNTERS[event] += 1


def reset_sentinel_counters() -> None:
    for k in SENTINEL_COUNTERS:
        SENTINEL_COUNTERS[k] = 0


# Codec-lab accounting (mlsl_tpu.codecs): per-codec wire bytes (compressed
# image of each started round's payload — the codec-comparable bandwidth
# signal) and the calibration/guardrail event counters. Process-wide like the
# degrade counters: the guardrail fires from the sentinel with no Session
# handle. Demotions additionally keep a bounded attribution list (which
# request, which codec, why) — the post-mortem answer to "who turned my VQ
# off", mirrored into supervisor.status()["codecs"].
CODEC_WIRE_BYTES: Dict[str, int] = {}
CODEC_COUNTERS: Dict[str, int] = {
    "calibrations": 0,     # calibration passes run (Session.commit)
    "assignments": 0,      # ParameterSets routed to a calibrated codec
    "guard_breaches": 0,   # sentinel loss z-score breaches while guarded
    "demotions": 0,        # guardrail demotions to int8
}
CODEC_DEMOTIONS: List[str] = []
_CODEC_DEMOTIONS_MAX = 64


def record_codec(event: str) -> None:
    """One codec-lab event: a key of CODEC_COUNTERS."""
    CODEC_COUNTERS[event] += 1


def record_codec_wire(codec: str, nbytes: int) -> None:
    """One started compressed round: ``nbytes`` of wire image under
    ``codec`` (called from CommRequest.start — one dict upsert)."""
    CODEC_WIRE_BYTES[codec] = CODEC_WIRE_BYTES.get(codec, 0) + int(nbytes)


def record_codec_demotion(request: str, codec: str, reason: str) -> None:
    """Guardrail demotion attribution: bump the counter, keep the bounded
    attribution row, and cut the DEGRADE ladder line (codec_demote)."""
    CODEC_COUNTERS["demotions"] += 1
    if len(CODEC_DEMOTIONS) < _CODEC_DEMOTIONS_MAX:
        CODEC_DEMOTIONS.append(f"{request}: {codec} -> int8 ({reason})")
    record_degrade("quant", "codec_demote", f"{request} {codec}->int8 {reason}")


def reset_codec_counters() -> None:
    for k in CODEC_COUNTERS:
        CODEC_COUNTERS[k] = 0
    CODEC_WIRE_BYTES.clear()
    CODEC_DEMOTIONS.clear()


# Elastic-mesh accounting (mlsl_tpu.elastic): device losses routed to the
# reshard rung, shrink/grow cycles, and the re-admission audit verdicts —
# process-wide like the degrade counters (the coordinator outlives every
# Environment rebuild it performs). Cold events (a reshard is rarer than a
# breaker trip) append an immediate ELASTIC line to mlsl_stats.log, the same
# contract as DEGRADE transitions; Statistics.print_ renders the totals.
ELASTIC_COUNTERS: Dict[str, int] = {
    "device_losses": 0,     # DEVICE_LOSS faults reaching the coordinator
    "shrinks": 0,           # successful shrink reshard cycles
    "grows": 0,             # successful grow (re-admission) cycles
    "grow_abandons": 0,     # grows abandoned on persistent divergence
    "admits": 0,            # replicas admitted on a passing fingerprint audit
    "admit_rejects": 0,     # admission audits that found divergence
    "resyncs": 0,           # rejected copies re-broadcast from survivors
    "reshard_buffers": 0,   # ZeRO-1 state buffers moved by reshard plans
    "restart_fallbacks": 0,  # losses escalated to checkpoint restart
}


def record_elastic(event: str, detail: str = "", n: int = 1) -> None:
    """One elastic-mesh event (see ELASTIC_COUNTERS keys). Events that mark
    a topology change or an admission verdict get an immediate ELASTIC line
    in mlsl_stats.log; per-buffer accounting only bumps the counter."""
    ELASTIC_COUNTERS[event] += n
    # every event is cold (topology change / admission verdict) except the
    # per-buffer accounting — state the exception so a new counter cannot
    # silently fall out of the immediate-line contract
    if event != "reshard_buffers":
        try:
            with open(stats_path(), "a") as f:
                f.write(
                    f"{'ELASTIC':<16} {event.upper():<16} {detail}\n"
                )
        except OSError:
            pass


def reset_elastic_counters() -> None:
    for k in ELASTIC_COUNTERS:
        ELASTIC_COUNTERS[k] = 0


# Buffer-checker accounting (mlsl_tpu.checker): how many buffers CHKP
# inspected, how many violated the contract, and how many device syncs the
# batched CHKP_VALUES finiteness path actually paid (the point of batching:
# value_checks >> value_syncs on a multi-request round).
CHKP_COUNTERS: Dict[str, int] = {
    "checks": 0,        # buffers validated (shape/dtype/sharding tier)
    "violations": 0,    # checks that raised (any tier)
    "value_checks": 0,  # finiteness verdicts queued (CHKP_VALUES)
    "value_syncs": 0,   # device syncs paid to resolve queued verdicts
}


def record_chkp(event: str, n: int = 1) -> None:
    CHKP_COUNTERS[event] += n


def reset_chkp_counters() -> None:
    for k in CHKP_COUNTERS:
        CHKP_COUNTERS[k] = 0


# Static-analysis accounting (mlsl_tpu.analysis): verifier/linter runs and
# their finding counts. Process-wide like the other event families (the
# verifier fires from Session.commit, which may run for several sessions in
# one process); each run also appends an immediate ANALYSIS line below.
ANALYSIS_COUNTERS: Dict[str, int] = {
    "runs": 0,       # verify/lint passes completed
    "errors": 0,     # error-severity findings across all runs
    "warnings": 0,   # warn-severity findings across all runs
}


def record_analysis(kind: str, errors: int, warnings: int,
                    codes: List[str], duration_s: float = 0.0) -> None:
    """One finished static-analysis pass (called by analysis.diagnostics
    .record): counters plus an immediate ANALYSIS line in the stats log —
    the verifier's verdict belongs next to the DEGRADE/WATCHDOG history it
    exists to prevent."""
    ANALYSIS_COUNTERS["runs"] += 1
    ANALYSIS_COUNTERS["errors"] += int(errors)
    ANALYSIS_COUNTERS["warnings"] += int(warnings)
    verdict = "FAIL" if errors else "PASS"
    try:
        with open(stats_path(), "a") as f:
            f.write(
                f"{'ANALYSIS':<16} {kind:<8} {verdict:<5} "
                f"errors={errors} warnings={warnings} "
                f"dt={duration_s * 1e3:.2f}ms"
                + (f"  codes={','.join(codes)}" if codes else "") + "\n"
            )
    except OSError:
        pass


def reset_analysis_counters() -> None:
    for k in ANALYSIS_COUNTERS:
        ANALYSIS_COUNTERS[k] = 0


# Straggler-sentinel accounting (mlsl_tpu.obs.straggler): cross-replica
# skew audits, confirmed-straggler flags, and elastic sheds — process-wide
# like the degrade counters (the sentinel is fed from the trainer with no
# Session handle). Flags and sheds are cold (a confirmed straggler is rarer
# than a breaker trip) and append an immediate STRAGGLER line, the DEGRADE
# transition contract; per-audit bookkeeping only bumps the counter.
STRAGGLER_COUNTERS: Dict[str, int] = {
    "audits": 0,          # cross-replica comparisons run
    "flags": 0,           # confirmed stragglers (sustained skew) flagged
    "sheds": 0,           # flagged replicas handed to the elastic coordinator
    "shed_fallbacks": 0,  # shed handoffs the coordinator refused/failed
}


def record_straggler(event: str, detail: str = "") -> None:
    """One straggler-sentinel event (see STRAGGLER_COUNTERS keys)."""
    STRAGGLER_COUNTERS[event] += 1
    if event != "audits":  # audits are the per-interval heartbeat, not news
        try:
            with open(stats_path(), "a") as f:
                f.write(f"{'STRAGGLER':<16} {event.upper():<8} {detail}\n")
        except OSError:
            pass


def reset_straggler_counters() -> None:
    for k in STRAGGLER_COUNTERS:
        STRAGGLER_COUNTERS[k] = 0


# Pod-control-plane accounting (mlsl_tpu.control): heartbeat traffic,
# membership detection/commit, election, and drain coordination —
# process-wide like the other families (pod membership outlives every
# Environment rebuild). Heartbeat traffic is the hot path (every interval x
# every peer) and only bumps counters; everything else is a cold membership
# event and appends an immediate CONTROL line, the DEGRADE transition
# contract — the acceptance story ("who noticed the death, who committed
# the epoch, who ordered the drain") must be readable from mlsl_stats.log.
CONTROL_COUNTERS: Dict[str, int] = {
    "heartbeats_sent": 0,   # frames sent (hot: counter only)
    "heartbeats_recv": 0,   # frames received (hot: counter only)
    "send_failures": 0,     # control-channel sends that failed (hot)
    "deaths_detected": 0,   # peers locally declared dead (miss budget)
    "epochs_committed": 0,  # membership/drain epochs applied (fenced)
    "stale_rejected": 0,    # stale-epoch / deposed-leader orders rejected
    "elections": 0,         # leadership changes observed
    "notices": 0,           # preemption notices submitted locally
    "drain_decisions": 0,   # pod-wide drain verdicts made (leader only)
    "drains_executed": 0,   # local drain executions completed
    "evicted": 0,           # this rank declared dead by the pod (partition)
}

_CONTROL_HOT = ("heartbeats_sent", "heartbeats_recv", "send_failures")


def record_control(event: str, detail: str = "", line: bool = True,
                   count: bool = True) -> None:
    """One control-plane event (see CONTROL_COUNTERS keys)."""
    if count:
        CONTROL_COUNTERS[event] += 1
    if line and event not in _CONTROL_HOT:
        try:
            with open(stats_path(), "a") as f:
                f.write(f"{'CONTROL':<16} {event.upper():<16} {detail}\n")
        except OSError:
            pass


def reset_control_counters() -> None:
    for k in CONTROL_COUNTERS:
        CONTROL_COUNTERS[k] = 0


# Runtime lock-witness accounting (mlsl_tpu.analysis.witness,
# MLSL_LOCK_WITNESS=1): the dynamic half of the A21x concurrency suite.
# Acquisitions are the hot path (every witnessed critical section) and only
# bump the counter; edges/cycles/over-budget holds are cold findings and
# append an immediate LOCKWITNESS line — a witnessed order cycle must be
# readable from mlsl_stats.log next to the CONTROL story it would deadlock.
LOCKWITNESS_COUNTERS: Dict[str, int] = {
    "acquisitions": 0,       # witnessed acquisitions (hot: counter only)
    "edges_observed": 0,     # distinct acquisition-order edges seen
    "cycles_detected": 0,    # runtime lock-order cycles (potential deadlock)
    "over_budget_holds": 0,  # holds past MLSL_LOCK_WITNESS_BUDGET_MS
}

_LOCKWITNESS_HOT = ("acquisitions",)


def record_lock_witness(event: str, detail: str = "") -> None:
    """One lock-witness event (see LOCKWITNESS_COUNTERS keys)."""
    LOCKWITNESS_COUNTERS[event] += 1
    if event not in _LOCKWITNESS_HOT:
        try:
            with open(stats_path(), "a") as f:
                f.write(f"{'LOCKWITNESS':<16} {event.upper():<16} {detail}\n")
        except OSError:
            pass


def reset_lock_witness_counters() -> None:
    for k in LOCKWITNESS_COUNTERS:
        LOCKWITNESS_COUNTERS[k] = 0


def record_comm_retry(phase: str, request: str, error: BaseException,
                      attempt: int, delay_s: float) -> None:
    """One rung-2 retry of a transient dispatch/wait failure (called by
    CommRequest before it backs off)."""
    DEGRADE_COUNTERS["comm_retries"] += 1
    if obs._tracer is not None:
        obs._tracer.instant(f"{phase}.retry", "degrade", request=request,
                            attempt=attempt, delay_s=round(delay_s, 4),
                            error=repr(error))


def reset_degrade_counters() -> None:
    for k in DEGRADE_COUNTERS:
        DEGRADE_COUNTERS[k] = 0
    DEGRADE_FALLBACKS.clear()
    DEGRADE_EVENTS.clear()


# Feed-pipeline accounting (mlsl_tpu.data): process-wide like the bucket
# counters — the feed stages batches from a loader worker thread with no
# Session handle. Wire bytes are what actually crossed the h2d link;
# bytes_saved is the full-width f32 baseline minus that; stall_ms is time the
# TRAINING LOOP blocked on an empty prefetch queue (the number the pipeline
# exists to drive to zero); producer_wait_ms is healthy backpressure (the
# worker waiting for a free slot). Statistics.print_ renders the totals as
# the FEED line in mlsl_stats.log.
FEED_COUNTERS: Dict[str, float] = {
    "batches_staged": 0,     # batches that crossed the h2d link
    "wire_bytes": 0,         # bytes actually shipped (payload + scales)
    "bytes_saved": 0,        # f32-baseline bytes minus wire bytes
    "cache_hits": 0,         # batches served from the HBM cache (no h2d)
    "cache_misses": 0,
    "cache_rejects": 0,      # batches the cache budget refused to pin
    "stall_ms": 0.0,         # consumer blocked on an empty prefetch queue
    "producer_wait_ms": 0.0,  # worker blocked on a full queue (backpressure)
    "retries": 0,            # TRANSIENT source-read retries (rung 2)
}


def record_feed_stage(wire_bytes: int, full_bytes: int) -> None:
    """One batch staged over the wire (called by FeedCodec.stage; the
    h2d.transfer span is recorded there too)."""
    FEED_COUNTERS["batches_staged"] += 1
    FEED_COUNTERS["wire_bytes"] += wire_bytes
    FEED_COUNTERS["bytes_saved"] += max(0, full_bytes - wire_bytes)


def record_feed_cache(event: str) -> None:
    """One cache lookup outcome: 'hit' / 'miss' / 'reject'."""
    key = "cache_misses" if event == "miss" else f"cache_{event}s"
    FEED_COUNTERS[key] += 1


def record_feed_stall(ms: float) -> None:
    """Consumer blocked on the prefetch queue for ``ms`` (AsyncLoader)."""
    FEED_COUNTERS["stall_ms"] += ms


def record_feed_wait(ms: float) -> None:
    """Producer backpressure wait (AsyncLoader worker, full queue)."""
    FEED_COUNTERS["producer_wait_ms"] += ms


def record_feed_retry() -> None:
    """One TRANSIENT source-read retry (MLSL_FEED_RETRIES)."""
    FEED_COUNTERS["retries"] += 1


def reset_feed_counters() -> None:
    for k in FEED_COUNTERS:
        FEED_COUNTERS[k] = 0 if isinstance(FEED_COUNTERS[k], int) else 0.0


# Serving-engine accounting (mlsl_tpu.serve): process-wide like the feed
# counters — the engine admits requests from caller threads with no Session
# handle. Admission outcomes, decode progress, KV paging churn, and SLA
# ladder transitions; Statistics.print_ renders the totals as the SERVE line
# in mlsl_stats.log, and obs/metrics.sample_families snapshots them onto
# /metrics as mlsl_serve_* gauges.
SERVE_COUNTERS: Dict[str, float] = {
    "admitted": 0,        # requests accepted into the admission queue
    "rejected": 0,        # 429-style admission rejections (ladder rung 3)
    "completed": 0,       # sequences that finished (eos or max_tokens)
    "failed": 0,          # sequences abandoned by a non-retryable fault
    "prefills": 0,        # prompts prefilled (first tokens produced)
    "prefill_chunks": 0,  # chunk programs launched (chunked prefill)
    "decode_steps": 0,    # iteration-level decode steps over the batch
    "tokens_out": 0,      # total generated tokens across all sequences
    "retries": 0,         # TRANSIENT decode-step retries (rung 2)
    "kv_pages_alloc": 0,  # KV pages taken off the free-list
    "kv_pages_freed": 0,  # KV pages returned on retirement
    "kv_evictions": 0,    # sequences evicted to reclaim pages under pressure
    "kv_rejects": 0,      # admissions refused for want of KV pages
    "shed_batch": 0,      # SLA ladder: batch-size sheds (rung 1)
    "shed_precision": 0,  # SLA ladder: KV-precision sheds (rung 2)
    "shed_admission": 0,  # SLA ladder: admission-shedding entries (rung 3)
    "recoveries": 0,      # ladder steps back toward healthy
}


def record_serve(event: str, n: int = 1) -> None:
    """One serving-engine event (see SERVE_COUNTERS keys)."""
    SERVE_COUNTERS[event] += n


def record_serve_shed(rung: str, detail: str = "") -> None:
    """One SLA-ladder transition ('batch' / 'precision' / 'admission' /
    'recovery'): counted, and appended as an immediate SERVE line — the
    degraded-not-down story must be readable from mlsl_stats.log."""
    key = "recoveries" if rung == "recovery" else f"shed_{rung}"
    SERVE_COUNTERS[key] += 1
    try:
        with open(stats_path(), "a") as f:
            f.write(f"{'SERVE':<16} {rung.upper():<10} {detail}\n")
    except OSError:
        pass


def reset_serve_counters() -> None:
    for k in SERVE_COUNTERS:
        SERVE_COUNTERS[k] = 0 if isinstance(SERVE_COUNTERS[k], int) else 0.0


# Per-algorithm dispatch accounting (comm/algos): process-wide like the
# bucket counters — dispatch fires at the request layer with no Session
# handle. Key = (kind, algorithm name); value = launches. The point: traces
# and stats must attribute wire time to the ALGORITHM that ran, or a tuned
# profile's effect is invisible in the logs it was tuned from.
ALGO_COUNTERS: Dict[Tuple[str, str], int] = {}


def record_algo_dispatch(kind: str, algo: str) -> None:
    """One collective launch under ``algo`` (called by CommRequest._dispatch
    on the hot path: a dict upsert, no allocation beyond the first key)."""
    key = (kind, algo)
    ALGO_COUNTERS[key] = ALGO_COUNTERS.get(key, 0) + 1


def reset_algo_counters() -> None:
    ALGO_COUNTERS.clear()


# Compiled-overlap engine accounting (comm/overlap.py): the in-graph rounds
# never construct a CommRequest, so their attribution lands here (and, per
# algorithm, in ALGO_COUNTERS — the ALGO line covers host AND in-graph
# launches). Process-wide like the other dispatch-layer counters.
OVERLAP_COUNTERS: Dict[str, int] = {
    "steps": 0,          # compiled-overlap steps dispatched
    "split_steps": 0,    # of which ran the two-program (sentinel-gated) split
    "units": 0,          # in-graph reduction units dispatched (cumulative)
    "rounds": 0,         # in-graph collective phases (ppermute rounds etc.)
    "bytes": 0,          # logical gradient bytes reduced in-graph
}


def record_overlap_step(units: int, rounds: int, nbytes: int, *,
                        split: bool = False,
                        breakdown: Optional[Dict[Tuple[str, str], int]] = None
                        ) -> None:
    """One compiled-overlap step: bulk attribution for all of its in-graph
    rounds (a handful of dict upserts per STEP, not per layer — the
    dispatch-floor budget the engine exists to protect). ``breakdown`` maps
    (kind, algo) -> unit count and feeds the shared ALGO table."""
    OVERLAP_COUNTERS["steps"] += 1
    if split:
        OVERLAP_COUNTERS["split_steps"] += 1
    OVERLAP_COUNTERS["units"] += units
    OVERLAP_COUNTERS["rounds"] += rounds
    OVERLAP_COUNTERS["bytes"] += nbytes
    if breakdown:
        for key, n in breakdown.items():
            ALGO_COUNTERS[key] = ALGO_COUNTERS.get(key, 0) + n


def reset_overlap_counters() -> None:
    for k in OVERLAP_COUNTERS:
        OVERLAP_COUNTERS[k] = 0


#: jax monitoring event fired once per XLA backend compilation — the
#: compile-count probe behind the MLSL_PRECOMPILE acceptance check.
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@contextlib.contextmanager
def count_backend_compiles():
    """Count XLA backend compilations inside the block: yields a one-element
    list whose [0] is the running count. Used to verify AOT precompilation
    (Session.precompile_collectives / MLSL_PRECOMPILE) actually removed
    compile stalls from the timed path — a warmed step must count 0.

    Cleanup is unconditional (the ``finally`` runs on exception paths too) and
    VERIFIED: a listener left behind by a failing test body would keep
    counting other tests' compiles forever, so if jax's private unregister
    hook has moved we excise the callback from the registry list directly and
    warn rather than silently leaking."""
    from jax._src import monitoring

    n = [0]

    def _listener(event, duration=0.0, **kw):  # noqa: ARG001
        if event == BACKEND_COMPILE_EVENT:
            n[0] += 1

    monitoring.register_event_duration_secs_listener(_listener)
    try:
        yield n
    finally:
        _remove_duration_listener(monitoring, _listener)


def _remove_duration_listener(monitoring, listener) -> None:
    """Best-effort unregister via the jax API, then verify against the
    registry itself and fall back to direct excision — never leave the
    listener installed."""
    try:
        monitoring._unregister_event_duration_listener_by_callback(listener)
    except Exception:  # mlsl-lint: disable=A205 -- jax internals moved;
        pass           # the verify below still runs
    for attr in (
        "_event_duration_secs_listeners",  # current jax registry list
        "event_duration_secs_listeners",
    ):
        reg = getattr(monitoring, attr, None)
        if isinstance(reg, list) and listener in reg:
            try:
                reg.remove(listener)
            except ValueError:
                pass
    for reg in (
        getattr(monitoring, "_event_duration_secs_listeners", None),
        getattr(monitoring, "event_duration_secs_listeners", None),
    ):
        if isinstance(reg, list) and listener in reg:  # pragma: no cover
            log_warning(
                "count_backend_compiles could not unregister its jax "
                "monitoring listener; later compile counts will be inflated"
            )


class _Slot:
    __slots__ = ("bytes", "comm_ns", "comp_ns", "events", "starts")

    def __init__(self):
        self.bytes = 0
        self.comm_ns = 0
        self.comp_ns = 0
        self.events = 0
        self.starts = 0


def _entity_key(entity, is_param: bool, is_increment: bool) -> Tuple:
    if is_param:
        kind = "INC" if is_increment else "GRAD"
        return (kind, entity.param_index)
    kind = "IA" if entity.is_input else "OA"
    return (kind, entity.act_index)


class Statistics:
    def __init__(self, session):
        self.session = session
        self._started = False
        self._last_event_ns: Optional[int] = None
        self._slots: Dict[Tuple[int, Tuple], _Slot] = {}
        self._isolation_ns: Dict[int, int] = {}   # op_idx -> per-iteration comm ns
        self._isolation_bytes: Dict[int, int] = {}
        # (op_idx, entity_key) -> per-iteration comm ns, for the overlap report
        self._isolation_slot_ns: Dict[Tuple[int, Tuple], int] = {}

    # -- lifecycle ---------------------------------------------------------

    def is_enabled(self) -> bool:
        cfg = self.session.env.config
        return bool(cfg and cfg.enable_stats)

    def is_started(self) -> bool:
        return self._started

    def initialize(self) -> None:
        self._slots.clear()
        if self.is_enabled():
            self._started = True
            self._last_event_ns = time.perf_counter_ns()

    def start(self) -> None:
        self._started = True
        self._last_event_ns = time.perf_counter_ns()

    def stop(self) -> None:
        self._started = False

    def reset(self) -> None:
        self._slots.clear()
        self._last_event_ns = time.perf_counter_ns()

    # -- online accounting -------------------------------------------------

    def _slot(self, op_idx: int, key: Tuple) -> _Slot:
        s = self._slots.get((op_idx, key))
        if s is None:
            s = _Slot()
            self._slots[(op_idx, key)] = s
        return s

    def update(self, entity, action: str, is_param: bool, is_increment: bool) -> None:
        """Pre-events ('start','wait','test') attribute elapsed time to compute;
        post-events ('*_done') attribute it to comm; bytes counted on start.

        Peer-op redirection (reference UpdateStats src/mlsl_impl_stats.cpp:564-668):
        WaitComm on an activation completes the PEER's transfer, so its comm time is
        charged to the peer's (op, entity) slot."""
        if not self._started:
            return
        now = time.perf_counter_ns()
        delta = now - (self._last_event_ns or now)
        self._last_event_ns = now
        target = entity
        if (
            not is_param
            and action in ("wait", "wait_done")
            and getattr(entity, "peer_act", None) is not None
        ):
            target = entity.peer_act
        op_idx = target.op.op_idx
        slot = self._slot(op_idx, _entity_key(target, is_param, is_increment))
        if action.endswith("_done"):
            slot.comm_ns += delta
        else:
            slot.comp_ns += delta
        if action == "start":
            slot.starts += 1
            req = _entity_request(entity, is_param, is_increment)
            if req is not None:
                slot.bytes += req.desc.payload_bytes()
        slot.events += 1

    # -- isolation benchmark ----------------------------------------------

    def collect_isolation_stats(self) -> None:
        """Replay every registered comm with compute off (reference :387-562)."""
        for op in self.session.operations:
            total_ns = 0
            total_bytes = 0
            for key, req in _op_request_slots(op):
                ns, nbytes = isolation_time_request(req)
                total_ns += ns
                total_bytes += nbytes
                self._isolation_slot_ns[(op.op_idx, key)] = ns
            self._isolation_ns[op.op_idx] = total_ns
            self._isolation_bytes[op.op_idx] = total_bytes

    # -- overlap quantification --------------------------------------------

    def overlap_report(self) -> dict:
        """Hidden vs exposed communication time — how much comm actually hides
        behind compute, the entire point of the async Start/Wait engine
        (reference: eplib's newest-first allreduce exists to maximize this,
        eplib/allreduce_pr.c:76-79; the comp/comm attribution intent is
        src/mlsl_impl_stats.cpp:564-668).

        Per (op, entity) slot that was replayed in isolation AND started online:
          true comm time  = isolation ns/iter x observed Start count
          exposed time    = online comm ns (host blocked inside Start/Wait/Test)
          hidden time     = max(0, true - exposed)
          overlap_fraction = hidden / true
        Requires collect_isolation_stats() (run at Commit when stats are enabled,
        or callable explicitly) plus at least one accounted step."""
        ops: Dict[str, dict] = {}
        tot_iso = tot_exposed = 0
        for op_idx, iso, exposed in self._overlap_slots():
            name = self.session.operations[op_idx].name
            ent = ops.setdefault(name, {"iso_ns": 0, "exposed_ns": 0})
            ent["iso_ns"] += iso
            ent["exposed_ns"] += exposed
            tot_iso += iso
            tot_exposed += exposed
        for ent in ops.values():
            ent["hidden_ns"] = max(0, ent["iso_ns"] - ent["exposed_ns"])
            ent["overlap_fraction"] = ent["hidden_ns"] / ent["iso_ns"]
        total = {
            "iso_ns": tot_iso,
            "exposed_ns": tot_exposed,
            "hidden_ns": max(0, tot_iso - tot_exposed),
            "overlap_fraction": (
                max(0, tot_iso - tot_exposed) / tot_iso if tot_iso > 0 else None
            ),
        }
        rep = {"ops": ops, "total": total}
        tr = obs._tracer
        if tr is not None:
            # span-derived attribution (tracing on): per-op p50/p95 wait-stall
            # from the tracer's 'wait' spans — requests are named '<op>/...'
            # (core/parameter_set.py), so overlap loss maps to specific ops
            # instead of one aggregate number
            stalls = tr.wait_stall_durations()
            all_durs: List[int] = []
            for name, ent in ops.items():
                durs: List[int] = []
                for key, d in stalls.items():
                    if key.startswith(name + "/"):
                        durs.extend(d)
                if durs:
                    durs.sort()
                    ent["wait_spans"] = len(durs)
                    ent["wait_stall_p50_ms"] = (
                        obs._percentile(durs, 50) / 1e6
                    )
                    ent["wait_stall_p95_ms"] = (
                        obs._percentile(durs, 95) / 1e6
                    )
                all_durs.extend(durs)
            if all_durs:
                all_durs.sort()
                total["wait_spans"] = len(all_durs)
                total["wait_stall_p50_ms"] = obs._percentile(all_durs, 50) / 1e6
                total["wait_stall_p95_ms"] = obs._percentile(all_durs, 95) / 1e6
        return rep

    def _overlap_slots(self):
        """(op_idx, true_comm_ns, exposed_ns) per qualifying slot — the ONE
        copy of the overlap accounting rules, shared by overlap_report and
        get_overlap_fraction so the printed table and the C API agree."""
        for (oi, key), iso_per_iter in self._isolation_slot_ns.items():
            slot = self._slots.get((oi, key))
            if slot is None or slot.starts == 0 or iso_per_iter <= 0:
                continue
            yield oi, iso_per_iter * slot.starts, slot.comm_ns

    def get_overlap_fraction(self, op_idx: Optional[int] = None) -> Optional[float]:
        """Fraction of pure-comm time hidden behind compute — session total, or
        one operation's with ``op_idx`` (keyed by index, robust to duplicate op
        names). None until isolation stats and an accounted step exist, or for
        an op with no replayed comm."""
        iso = exposed = 0
        for oi, slot_iso, slot_exposed in self._overlap_slots():
            if op_idx is not None and oi != op_idx:
                continue
            iso += slot_iso
            exposed += slot_exposed
        return None if iso == 0 else max(0, iso - exposed) / iso

    # -- queries (reference include/mlsl.hpp:680-725) ----------------------

    def get_isolation_comm_cycles(self, op_idx: int) -> int:
        return self._isolation_ns.get(op_idx, 0)

    def get_comm_size(self, op_idx: int) -> int:
        return sum(s.bytes for (oi, _), s in self._slots.items() if oi == op_idx)

    def get_comm_cycles(self, op_idx: int) -> int:
        return sum(s.comm_ns for (oi, _), s in self._slots.items() if oi == op_idx)

    def get_compute_cycles(self, op_idx: int) -> int:
        return sum(s.comp_ns for (oi, _), s in self._slots.items() if oi == op_idx)

    def get_total_isolation_comm_cycles(self) -> int:
        return sum(self._isolation_ns.values())

    def get_total_comm_size(self) -> int:
        return sum(s.bytes for s in self._slots.values())

    def get_total_comm_cycles(self) -> int:
        return sum(s.comm_ns for s in self._slots.values())

    def get_total_compute_cycles(self) -> int:
        return sum(s.comp_ns for s in self._slots.values())

    # -- printer (reference :226-363) --------------------------------------

    def print_(self, path: Optional[str] = None) -> str:
        if path is None:
            path = stats_path()  # MLSL_STATS_DIR routing, resolved per call
        lines = []
        mb = max(self.session.global_minibatch_size, 1)
        lines.append(
            f"{'op':<16} {'entity':<8} {'KB':>12} {'comm Kns/img':>14} "
            f"{'comp Kns/img':>14} {'events':>8}"
        )
        for (op_idx, key), slot in sorted(self._slots.items()):
            op = self.session.operations[op_idx]
            lines.append(
                f"{op.name:<16} {key[0] + str(key[1]):<8} "
                f"{slot.bytes / 1024.0:>12.1f} {slot.comm_ns / 1e3 / mb:>14.2f} "
                f"{slot.comp_ns / 1e3 / mb:>14.2f} {slot.events:>8}"
            )
        for op_idx, ns in sorted(self._isolation_ns.items()):
            op = self.session.operations[op_idx]
            lines.append(
                f"{op.name:<16} {'ISOLATE':<8} "
                f"{self._isolation_bytes.get(op_idx, 0) / 1024.0:>12.1f} "
                f"{ns / 1e3 / mb:>14.2f} {'-':>14} {'-':>8}"
            )
        rep = self.overlap_report()
        if rep["total"]["overlap_fraction"] is not None:
            lines.append(
                f"{'OVERLAP':<16} {'TOTAL':<8} hidden "
                f"{rep['total']['hidden_ns'] / 1e3:>10.1f} Kns / iso "
                f"{rep['total']['iso_ns'] / 1e3:>10.1f} Kns = "
                f"{rep['total']['overlap_fraction']:.3f}"
            )
            for name, ent in sorted(rep["ops"].items()):
                lines.append(
                    f"{name:<16} {'OVERLAP':<8} hidden "
                    f"{ent['hidden_ns'] / 1e3:>10.1f} Kns / iso "
                    f"{ent['iso_ns'] / 1e3:>10.1f} Kns = "
                    f"{ent['overlap_fraction']:.3f}"
                )
        c = BUCKET_COUNTERS
        if c["rounds_dispatched"] or c["rounds_fallback"] or c["member_abandons"]:
            bucket_line = (
                f"{'BUCKET':<16} {'ROUNDS':<8} dispatched {c['rounds_dispatched']} "
                f"fallback {c['rounds_fallback']} abandoned {c['member_abandons']} "
                f"coalesced {c['bytes_coalesced'] / 1024.0:.1f} KB "
                f"wire_saved {c['wire_bytes_saved'] / 1024.0:.1f} KB"
            )
            tr = obs._tracer
            if tr is not None:
                # span-derived: wait-stall distribution over the bucket
                # requests' 'wait' spans (named 'bucket-<kind>[NxM]')
                durs = [
                    d
                    for key, ds in tr.wait_stall_durations().items()
                    if key.startswith("bucket-")
                    for d in ds
                ]
                if durs:
                    durs.sort()
                    bucket_line += (
                        f" wait_p50 {obs._percentile(durs, 50) / 1e6:.2f} ms"
                        f" wait_p95 {obs._percentile(durs, 95) / 1e6:.2f} ms"
                    )
            lines.append(bucket_line)
        fc = FEED_COUNTERS
        if (fc["batches_staged"] or fc["cache_hits"] or fc["cache_misses"]
                or fc["stall_ms"] or fc["retries"]):
            # stall/retries alone must also surface the line: a plain
            # AsyncLoader (no wire path) that stalled the training loop is
            # exactly the input-bound run this line exists to expose
            # the feed line: how many bytes the wire codecs + HBM cache kept
            # off the h2d link, and whether the training loop ever waited on
            # its input (stall) — one grep ('FEED') answers "is this run
            # input-bound"
            staged = max(int(fc["batches_staged"]), 1)
            lines.append(
                f"{'FEED':<16} {'PIPELINE':<8} "
                f"staged {int(fc['batches_staged'])} "
                f"wire {fc['wire_bytes'] / 1e6:.1f} MB "
                f"({fc['wire_bytes'] / 1e6 / staged:.2f} MB/batch) "
                f"saved {fc['bytes_saved'] / 1e6:.1f} MB "
                f"cache {int(fc['cache_hits'])}h/{int(fc['cache_misses'])}m/"
                f"{int(fc['cache_rejects'])}r "
                f"stall {fc['stall_ms']:.1f} ms "
                f"bp_wait {fc['producer_wait_ms']:.1f} ms "
                f"retries {int(fc['retries'])}"
            )
        if ALGO_COUNTERS:
            # per-algorithm dispatch attribution (comm/algos): which program
            # family actually carried each collective kind this run
            parts = [
                f"{kind}:{algo}={n}"
                for (kind, algo), n in sorted(ALGO_COUNTERS.items())
            ]
            lines.append(
                f"{'ALGO':<16} {'DISPATCH':<8} " + " ".join(parts)
            )
        oc = OVERLAP_COUNTERS
        if oc["steps"]:
            # the compiled-overlap story: how many steps rode the in-graph
            # schedule, how many of those split for the sentinel gate, and
            # the in-graph round/byte volume — one grep ('OVERLAP ENGINE')
            # answers "did the compiled path actually carry this run"
            lines.append(
                f"{'OVERLAP':<16} {'ENGINE':<8} "
                f"steps {oc['steps']} (split {oc['split_steps']}) "
                f"units {oc['units']} rounds {oc['rounds']} "
                f"bytes {oc['bytes'] / 1e6:.1f} MB"
            )
        sc = SENTINEL_COUNTERS
        if any(sc.values()):
            # the integrity story: how many steps the gate screened, what it
            # fired, and whether the consistency audit ever saw replicas
            # diverge — one grep ('SENTINEL') answers "did this run's state
            # stay trustworthy"
            lines.append(
                f"{'SENTINEL':<16} {'GATE':<8} "
                f"screened {sc['screened']} "
                f"warn {sc['gate_warn']} skip {sc['gate_skip']} "
                f"rollback {sc['gate_rollback']} audits {sc['audits']} "
                f"mismatch {sc['audit_mismatch']} "
                f"verified_saves {sc['verified_saves']} "
                f"reaudits {sc['reaudits']}"
            )
        ec = ELASTIC_COUNTERS
        if any(ec.values()):
            # the elastic story: how many device losses the run absorbed by
            # rescaling instead of restarting, and whether every returning
            # replica passed its admission audit — one grep ('ELASTIC')
            # answers "did capacity churn cost this run a restart"
            lines.append(
                f"{'ELASTIC':<16} {'MESH':<8} "
                f"losses {ec['device_losses']} "
                f"shrinks {ec['shrinks']} grows {ec['grows']} "
                f"abandons {ec['grow_abandons']} "
                f"admits {ec['admits']} rejects {ec['admit_rejects']} "
                f"resyncs {ec['resyncs']} "
                f"reshard_buffers {ec['reshard_buffers']} "
                f"restart_fallbacks {ec['restart_fallbacks']}"
            )
        gc = STRAGGLER_COUNTERS
        if any(gc.values()):
            # the straggler story: how many skew audits ran, which replicas
            # were confirmed slow, and whether any were shed — one grep
            # ('STRAGGLER') answers "did one replica tax this run"
            lines.append(
                f"{'STRAGGLER':<16} {'SKEW':<8} "
                f"audits {gc['audits']} flags {gc['flags']} "
                f"sheds {gc['sheds']} "
                f"shed_fallbacks {gc['shed_fallbacks']}"
            )
        cc = CONTROL_COUNTERS
        if any(cc.values()):
            # the pod story: detection -> one fenced epoch -> drain — one
            # grep ('CONTROL') answers "did the pod agree on what happened"
            lines.append(
                f"{'CONTROL':<16} {'POD':<8} "
                f"hb_sent {cc['heartbeats_sent']} "
                f"hb_recv {cc['heartbeats_recv']} "
                f"send_failures {cc['send_failures']} "
                f"deaths {cc['deaths_detected']} "
                f"epochs {cc['epochs_committed']} "
                f"stale_rejected {cc['stale_rejected']} "
                f"elections {cc['elections']} notices {cc['notices']} "
                f"drain_decisions {cc['drain_decisions']} "
                f"drains {cc['drains_executed']} evicted {cc['evicted']}"
            )
        vc = SERVE_COUNTERS
        if any(vc.values()):
            # the serving story: admission vs rejection, decode progress,
            # KV paging churn, and every SLA shed — one grep ('SERVE')
            # answers "did this engine stay inside its SLO, and at what cost"
            lines.append(
                f"{'SERVE':<16} {'ENGINE':<10} "
                f"admitted {int(vc['admitted'])} "
                f"rejected {int(vc['rejected'])} "
                f"completed {int(vc['completed'])} "
                f"failed {int(vc['failed'])} "
                f"tokens {int(vc['tokens_out'])} "
                f"steps {int(vc['decode_steps'])} "
                f"retries {int(vc['retries'])} "
                f"kv {int(vc['kv_pages_alloc'])}a/{int(vc['kv_pages_freed'])}f/"
                f"{int(vc['kv_evictions'])}e/{int(vc['kv_rejects'])}r "
                f"sheds {int(vc['shed_batch'])}b/{int(vc['shed_precision'])}p/"
                f"{int(vc['shed_admission'])}a "
                f"recoveries {int(vc['recoveries'])}"
            )
        kc = CHKP_COUNTERS
        if any(kc.values()):
            lines.append(
                f"{'CHKP':<16} {'BUFFERS':<8} checks {kc['checks']} "
                f"violations {kc['violations']} "
                f"value_checks {kc['value_checks']} "
                f"value_syncs {kc['value_syncs']}"
            )
        xc = CODEC_COUNTERS
        if any(xc.values()) or CODEC_WIRE_BYTES:
            # the codec-lab story: which codecs carried how many compressed
            # bytes, whether a calibration ran, and every guardrail demotion
            # — one grep ('CODEC') answers "what was on the wire, and did
            # the autotuner's choice survive the sentinel"
            wire = " ".join(
                f"{name}={n}" for name, n in sorted(CODEC_WIRE_BYTES.items())
            )
            lines.append(
                f"{'CODEC':<16} {'LAB':<8} "
                f"calibrations {xc['calibrations']} "
                f"assignments {xc['assignments']} "
                f"breaches {xc['guard_breaches']} "
                f"demotions {xc['demotions']}"
                + (f" wire_bytes {wire}" if wire else "")
            )
            for row in CODEC_DEMOTIONS:
                lines.append(f"{'CODEC':<16} {'DEMOTE':<8} {row}")
        dc = DEGRADE_COUNTERS
        if any(dc.values()) or DEGRADE_FALLBACKS:
            # the ladder summary: every trip/probe/reset, retry, degraded
            # dispatch, and supervised recovery of this run, plus the live
            # breaker states — one grep ('DEGRADE') answers "did this run
            # ever leave the healthy path, and is it back on it"
            from mlsl_tpu import supervisor  # lazy: supervisor imports stats

            states = " ".join(
                f"{name}:{st['state']}"
                for name, st in supervisor.status().items()
                # 'analysis' is verdict-shaped, not breaker-shaped — it has
                # its own ANALYSIS line above, so the ladder summary skips it
                if "state" in st
                and (st["state"] == "tripped" if name == "sentinel"
                     # elastic's healthy vocabulary is 'full', which never
                     # equals CLOSED — list it only when actually shrunk
                     else st["state"] == "shrunk" if name == "elastic"
                     # straggler's healthy vocabulary is 'off'/'watching'
                     # (the elastic lesson): list only when flagged
                     else st["state"] == "flagged" if name == "straggler"
                     # control's healthy vocabulary is 'off'/'member'/
                     # 'leader': list only when the pod actually lost
                     # members (or this rank was evicted by it)
                     else bool(st.get("dead")) or st.get("evicted")
                     if name == "control"
                     # serve's healthy vocabulary is 'off'/'healthy': list
                     # only when the SLA ladder actually shed a rung
                     else st["state"] not in ("off", "healthy")
                     if name == "serve"
                     else st.get("trips") or st["state"] != supervisor.CLOSED)
            )
            fb = " ".join(
                f"{name}={n}" for name, n in sorted(DEGRADE_FALLBACKS.items())
            )
            lines.append(
                f"{'DEGRADE':<16} {'LADDER':<8} retries {dc['comm_retries']} "
                f"trips {dc['breaker_trips']} probes {dc['breaker_probes']} "
                f"resets {dc['breaker_resets']} "
                f"recoveries {dc['recoveries']}"
                + (f" fallbacks {fb}" if fb else "")
                + (f" breakers {states}" if states else "")
            )
        text = "\n".join(lines) + "\n"
        try:
            with open(path, "a") as f:
                f.write(text)
        except OSError:
            pass
        return text

    def trace(self, log_dir: str):
        """Device-level profiler trace context (the jax.profiler complement to the
        host-side byte/time accounting; view in TensorBoard/Perfetto). Usage:

            with session.get_stats().trace("/tmp/trace"):
                trainer.step(batch)
        """
        import jax

        return jax.profiler.trace(log_dir)

    # PascalCase parity aliases
    Start = start
    Stop = stop
    Reset = reset
    IsStarted = is_started
    IsEnabled = is_enabled
    Print = print_
    GetIsolationCommCycles = get_isolation_comm_cycles
    GetCommSize = get_comm_size
    GetCommCycles = get_comm_cycles
    GetComputeCycles = get_compute_cycles
    GetTotalIsolationCommCycles = get_total_isolation_comm_cycles
    GetTotalCommSize = get_total_comm_size
    GetTotalCommCycles = get_total_comm_cycles
    GetTotalComputeCycles = get_total_compute_cycles
    OverlapReport = overlap_report
    GetOverlapFraction = get_overlap_fraction


# -- helpers -----------------------------------------------------------------


def _entity_request(entity, is_param: bool, is_increment: bool):
    if is_param:
        return entity.inc_req if is_increment else entity.grad_req
    return entity.comm_req


def _op_request_slots(op) -> List[Tuple[Tuple, object]]:
    """(entity_key, request) pairs for every registered comm of one operation,
    keyed the same way as the online-accounting slots so the isolation replay
    and the live Start/Wait attribution line up per entity."""
    out = []
    for act in op.inputs + op.outputs:
        if act.comm_req is not None:
            out.append((("IA" if act.is_input else "OA", act.act_index), act.comm_req))
    for ps in op.parameter_sets:
        if ps.grad_req is not None:
            out.append((("GRAD", ps.param_index), ps.grad_req))
        if ps.inc_req is not None:
            out.append((("INC", ps.param_index), ps.inc_req))
    return out


def isolation_time_request(req) -> Tuple[int, int]:
    """(per-iteration ns, payload bytes) for one request, measured in isolation."""
    d = req.desc
    topo = d.group.topology
    buf = topo.shard_buffer(
        np.zeros((*topo.grid_shape, d.count), dtype=jnp_dtype(d.data_type))
    )
    times = []
    for i in range(ISOLATION_ITERS):
        t0 = time.perf_counter_ns()
        req.start(buf)
        req.wait()
        times.append(time.perf_counter_ns() - t0)
    good = times[ISOLATION_SKIP:]
    return int(sum(good) / max(len(good), 1)), d.payload_bytes()
