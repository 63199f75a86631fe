"""The program's own spans, read by the metrics that are measured inside the
program (the serving engine's span tree: ``serve.step`` and its children,
``serve.request``; PERF.md section 3 names each with its reader).

Three things happen here, each checked on a small recorded run in tests/perf:

- **the window**: the ring's clock is ``time.perf_counter_ns()``, the
  benchmark's is ``time.perf_counter()``; the window opened at ``t_ready +
  setup_s`` on it, so the spans of the window and of its traced part are
  chosen without any trace, and warm-up's fall before it and are left out;
- **the clock join**: every ``engine.step()`` of the traced part runs directly
  inside the benchmark's ``perf.engine.step`` annotation, which the profiler
  stamps on the trace's clock. The k-th ``serve.step`` is paired with the k-th
  annotation; the median of (trace start - ring start) is the offset, the
  95th percentile of the deviations from it the residual. Above
  ``RESIDUAL_LIMIT_NS`` nothing is mapped;
- **idle time by innermost span**: the device's idle intervals, cut exactly at
  the mapped spans' edges, each piece booked to the innermost span that
  covers it (a parent's own time under the parent's name), ``(no span)`` for
  what lies between two steps.

A program without these spans (an older commit, ``MLSL_TRACE=0``) gives
``None`` everywhere, and says why in ``run.notes``."""

import bisect
import statistics
import sys

from perf.lib import manifest, trace as trace_lib

CAT = "serve"
STEP, REQUEST, ADMIT = "serve.step", "serve.request", "serve.admit"
WAIT, FIRST, DECODE = "serve.decode.wait", "serve.first_token", "serve.decode"
DISPATCH = "serve.decode.dispatch"
PREPARE, SAMPLE, KV_WRITE = ("serve.decode.prepare", "serve.decode.sample",
                             "serve.kv_write")
SUMMARY_SPANS = (STEP, PREPARE, DISPATCH, WAIT, SAMPLE, ADMIT, KV_WRITE)
ENGINE_STEP = "perf.engine.step"
NO_SPAN = "(no span)"
RESIDUAL_LIMIT_NS = 200_000
SUBMIT_AGREE_NS = 1_000_000
CLOSE_MS = 1.0


def _rank95(sorted_vals):
    """Nearest-rank 95th percentile of a sorted, non-empty list."""
    return sorted_vals[max(0, -(-95 * len(sorted_vals) // 100) - 1)]


def clock_join(ring_starts, trace_starts):
    """-> (offset_ns, residual_ns, pairs): trace clock = ring clock + offset.
    Where the two sides differ in number (a step cut by the profiler's start
    or stop), the heads and the tails are tried and the closer fit kept."""
    n = min(len(ring_starts), len(trace_starts))
    if n == 0:
        return None
    best = None
    ends = [(ring_starts[:n], trace_starts[:n])]
    if len(ring_starts) != len(trace_starts):
        ends.append((ring_starts[-n:], trace_starts[-n:]))
    for ring, there in ends:
        deltas = [t - r for r, t in zip(ring, there)]
        offset = int(statistics.median(deltas))
        dev = sorted(abs(d - offset) for d in deltas)
        residual = _rank95(dev)
        if best is None or residual < best[1]:
            best = (offset, residual, n)
    return best


def flatten(spans):
    """Nested ``[name, start, end]`` spans -> disjoint ``[start, end, name]``
    segments in order, each named by the innermost span that covers it. A
    child that overruns its parent is cut at the parent's end."""
    out, stack, at = [], [], None

    def emit(upto, name):
        if upto > at:
            out.append([at, upto, name])

    for name, start, end in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][1] <= start:
            top, top_end = stack.pop()
            emit(top_end, top)
            at = max(at, top_end)
        if stack:
            emit(start, stack[-1][0])
            end = min(end, stack[-1][1])
        stack.append([name, end])
        at = start
    while stack:
        top, top_end = stack.pop()
        emit(top_end, top)
        at = max(at, top_end)
    return out


def book(idle, segments):
    """Idle ``[start, end)`` intervals against ``flatten``'s segments, both
    sorted and disjoint -> {name: ns}; what no segment covers goes to
    ``(no span)``, so the values sum to the idle time exactly."""
    by, j = {}, 0
    for s, e in idle:
        covered = 0
        while j < len(segments) and segments[j][1] <= s:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < e:
            a, b, name = segments[k]
            piece = min(b, e) - max(a, s)
            if piece > 0:
                by[name] = by.get(name, 0) + piece
                covered += piece
            k += 1
        if e - s > covered:
            by[NO_SPAN] = by.get(NO_SPAN, 0) + (e - s) - covered
    return by


class Program:
    """The serving spans of one run's window, on the ring's clock."""

    def __init__(self, events, t_open_ns, seconds, traced=(None, None),
                 capacity=None):
        self.ring_full = capacity is not None and len(events) >= capacity
        self.t_open = t_open_ns
        self.t_close = t_open_ns + int(seconds * 1e9)
        self.traced = tuple(None if t is None else t_open_ns + int(t * 1e9)
                            for t in traced)
        spans = [e for e in events if e[0] == "X" and e[2] == CAT
                 and "step" in e[6] and e[3] >= t_open_ns]
        self.tree = [e for e in spans if e[5] is None]
        self.steps = {e[6]["step"]: e for e in self.tree if e[1] == STEP}
        self.requests = sorted((e for e in spans if e[1] == REQUEST),
                               key=lambda e: e[6]["req"])
        self._kids = {}
        for e in self.tree:
            if e[1] != STEP:
                self._kids.setdefault(e[6]["step"], []).append(e)

    def named(self, name, step=None):
        if step is not None:
            return [e for e in self._kids.get(step, []) if e[1] == name]
        return [e for e in self.tree if e[1] == name]

    def window_steps(self):
        """The steps that started while the window was open."""
        return [e for _, e in sorted(self.steps.items()) if e[3] < self.t_close]

    def traced_steps(self):
        lo, hi = self.traced
        if lo is None or hi is None:
            return []
        return [e for _, e in sorted(self.steps.items()) if lo <= e[3] < hi]


def from_events(run, armed, events, capacity=None):
    if not armed:
        run.notes["program_spans"] = "the program's span ring is not armed"
        return None
    prog = Program(events, int(round((run.t_ready + run.setup_s) * 1e9)),
                   run.window["seconds"], run.window.get("traced") or (None, None),
                   capacity)
    if not prog.steps:
        run.notes["program_spans"] = "the ring holds no serve.step of the window"
        return None
    return prog


def load(run):
    """The run's ``Program``, read from the ring once; ``None`` where the
    program has no such spans."""
    if not hasattr(run, "_program_spans"):
        adapter = manifest.load_module(
            manifest.PERF / "adapters" / "program_trace.py")
        run._program_spans = from_events(run, *adapter.snapshot(),
                                         capacity=adapter.capacity())
    return run._program_spans


# -- per request: the parts of a first token's time ------------------------

def ttft_parts(run):
    """-> one dict a finished request of the benchmark (``late_ms``,
    ``queue_wait_ms``, ``admit_ms``, ``step_tail_ms``, ``gap_ms``: the
    benchmark's TTFT less their sum), or ``None``. A benchmark request is
    matched to its ``req`` by order of acceptance, checked by the two submit
    stamps; an evicted and resumed request's parts are its first
    admission's."""
    if hasattr(run, "_ttft_parts"):
        return run._ttft_parts
    run._ttft_parts = None
    prog = load(run)
    if prog is None:
        return None
    series = run.record.series
    refused = {r[0] for r in series.get("refused", [])}
    accepted = [s for s in series.get("submit", []) if s[0] not in refused]
    if len(accepted) != len(prog.requests):
        run.notes["ttft_parts"] = (
            f"{len(accepted)} accepted requests against "
            f"{len(prog.requests)} serve.request spans")
        return None
    first_admit = {}
    for e in prog.named(ADMIT):
        if not e[6].get("resumed") and not e[6].get("error"):
            first_admit.setdefault(e[6]["req"], e)
    parts, worst_stamp = [], 0
    for (i, due, submitted), span in zip(accepted, prog.requests):
        worst_stamp = max(worst_stamp, abs(
            prog.t_open + int(submitted * 1e9) - span[3]))
        admit = first_admit.get(span[6]["req"])
        ttft_ms = run.window["ttft_ms"][i]
        if admit is None or admit[6]["step"] not in prog.steps \
                or span[6].get("outcome") != "done" or ttft_ms >= 1e9:
            continue
        step = prog.steps[admit[6]["step"]]
        p = {"request": i, "req": span[6]["req"],
             "late_ms": (submitted - due) * 1e3,
             "queue_wait_ms": admit[6]["queue_wait_ns"] / 1e6,
             "admit_ms": admit[4] / 1e6,
             "step_tail_ms": (step[3] + step[4] - admit[3] - admit[4]) / 1e6}
        p["gap_ms"] = ttft_ms - (p["late_ms"] + p["queue_wait_ms"]
                                 + p["admit_ms"] + p["step_tail_ms"])
        parts.append(p)
    if worst_stamp > SUBMIT_AGREE_NS:
        run.notes["ttft_parts"] = (
            f"submit stamps disagree by {worst_stamp / 1e6:.3f} ms: the "
            "requests were not matched")
        return None
    if not parts:
        return None
    gaps = [abs(p["gap_ms"]) for p in parts]
    closing = {"requests": len(parts), "gap_max_ms": max(gaps),
               "within_1ms_share": sum(g <= CLOSE_MS for g in gaps) / len(gaps),
               "submit_stamp_gap_max_ms": worst_stamp / 1e6,
               "late_median_ms": statistics.median(p["late_ms"] for p in parts)}
    run.notes["ttft_parts"] = closing
    run.record.note(ttft_parts=closing)
    run._ttft_parts = parts
    return parts


def ttft_part_median(run, key):
    parts = ttft_parts(run)
    return None if not parts else statistics.median(p[key] for p in parts)


# -- per step ---------------------------------------------------------------

def step_host_ms(run):
    """Mean over the window's steps that decoded of ``serve.step`` less its
    ``serve.decode.wait`` and ``serve.first_token`` spans: the host work of a
    step that is serial with the device."""
    prog = load(run)
    if prog is None:
        return None
    host = []
    for e in prog.window_steps():
        n = e[6]["step"]
        waits = prog.named(WAIT, n)
        if waits:
            blocked = sum(w[4] for w in waits + prog.named(FIRST, n))
            host.append((e[4] - blocked) / 1e6)
    return statistics.fmean(host) if host else None


def decode_counts(run):
    """The ``serve.decode`` spans of the window's steps that carry the page
    counts."""
    prog = load(run)
    if prog is None:
        return []
    in_window = {e[6]["step"] for e in prog.window_steps()}
    return [e[6] for e in prog.named(DECODE)
            if e[6]["step"] in in_window and e[6].get("pages_gathered")
            and e[6].get("pool_pages")]


# -- what the ring saw of the window, traced or not ---------------------------

def _ms_stats(ns):
    """-> {"n", "median_ms", "p95_ms"} (nearest rank) of durations in ns."""
    vals = sorted(ns)
    if not vals:
        return {"n": 0}
    return {"n": len(vals), "median_ms": statistics.median(vals) / 1e6,
            "p95_ms": _rank95(vals) / 1e6}


def ring_summary(run):
    """What the program's ring saw of the window's steps, for the step record
    of every serving run, so that a run that reads slow can be opened without
    a trace: median and 95th percentile of ``SUMMARY_SPANS``; the steps
    counted, and ``serve.step`` timed, by the admissions they made; the
    medians again over the window's first and second half (the host's slow
    speed lasts tens of seconds and shows as a step between the halves of
    ``prepare`` and ``dispatch``, which cost the same at any load; those of
    ``serve.step`` and ``wait`` also follow the load, so they compare only
    where the traffic's arrival order puts the same load into both halves);
    and where the 95th percentile of the gaps between tokens stands: every
    sequence a step decodes ends one gap, of length nought for a sequence
    the step has just admitted, so ``gap_share_admitting`` is the share of
    the other gaps that end in a step which admitted. ``ring_full`` says that the
    ring had wrapped and the window's first steps may be lost. ``None``
    where the program has no such spans."""
    prog = load(run)
    if prog is None:
        return None
    steps = prog.window_steps()
    mid = (prog.t_open + prog.t_close) // 2
    spans = {name: [] for name in SUMMARY_SPANS}
    halves = [{name: [] for name in SUMMARY_SPANS} for _ in range(2)]
    by_admissions, gaps, gaps_admitting = {}, 0, 0
    for e in steps:
        n = e[6]["step"]
        kids = [e] + prog._kids.get(n, [])
        half = halves[e[3] >= mid]
        admitted = 0
        for k in kids:
            if k[1] in spans:
                spans[k[1]].append(k[4])
                half[k[1]].append(k[4])
            admitted += k[1] == ADMIT and not k[6].get("error")
        by_admissions.setdefault(admitted, []).append(e[4])
        decoded = sum(k[6].get("inflight", 0) for k in kids if k[1] == DECODE)
        gaps += max(0, decoded - admitted)
        if admitted:
            gaps_admitting += max(0, decoded - admitted)
    out = {
        "steps": len(steps), "ring_full": prog.ring_full,
        "spans": {name: _ms_stats(v) for name, v in spans.items()},
        "steps_by_admissions": {str(k): _ms_stats(v)
                                for k, v in sorted(by_admissions.items())},
        "halves_median_ms": [
            {name: statistics.median(v) / 1e6 for name, v in h.items() if v}
            for h in halves],
        "gaps": gaps, "gap_share_admitting": gaps_admitting / gaps if gaps else None}
    run.record.note(ring_summary=out)
    return out


# -- the clock join and the idle table -----------------------------------------

def idle_table(run):
    """-> {"offset_ns", "residual_ns", "steps", "idle_s", "attributed_share",
    "by_span": [[name, seconds], ...]} for the traced window, or ``None``
    (no device trace, no spans, or a residual over the limit; ``run.notes``
    says which). Written to the step record and to standard error."""
    if hasattr(run, "_idle_table"):
        return run._idle_table
    run._idle_table = None
    prog = load(run)
    if run.trace is None or prog is None:
        return None
    tr = run.trace
    there = [e for e in tr.data["host"] if e[0] == ENGINE_STEP
             and tr.lo <= e[1] < tr.hi]
    here = prog.traced_steps()
    join = clock_join([e[3] for e in here], [e[1] for e in there])
    if join is None:
        run.notes["clock_join"] = (
            f"{len(here)} serve.step spans against {len(there)} "
            f"{ENGINE_STEP} annotations: nothing to pair")
        return None
    offset, residual, pairs = join
    note = {"offset_ns": offset, "residual_ns": residual, "pairs": pairs,
            "program_steps": len(here), "benchmark_spans": len(there)}
    run.notes["clock_join"] = note
    run.record.note(clock_join=note)
    print(f"clock join: offset {offset} ns, residual {residual} ns over "
          f"{pairs} steps", file=sys.stderr)
    if residual > RESIDUAL_LIMIT_NS:
        note["refused"] = (f"residual {residual} ns over the limit of "
                           f"{RESIDUAL_LIMIT_NS} ns: no span was mapped")
        return None
    mapped = [[e[1], e[3] + offset, e[3] + e[4] + offset] for e in prog.tree
              if e[3] + e[4] + offset > tr.lo and e[3] + offset < tr.hi]
    dev = tr.devices[0]
    busy = tr.busy_intervals(dev)
    idle = trace_lib.subtract([[tr.lo, tr.hi]], busy)
    # how far the trace's device clock can be trusted against its host clock:
    # in a step that admits nothing the device has nothing to do until the
    # decode program is dispatched, so its first operation cannot start
    # before `serve.decode.dispatch` does; a negative lead is the skew
    starts, leads = [s for s, _ in busy], []
    for e in here:
        n = e[6]["step"]
        dispatch = prog.named(DISPATCH, n)
        i = bisect.bisect_left(starts, e[3] + offset)
        if dispatch and not prog.named(ADMIT, n) and i < len(starts):
            leads.append(starts[i] - dispatch[0][3] - offset)
    if leads:
        note["device_start_after_dispatch_ns"] = int(statistics.median(leads))
    by = book(idle, flatten(mapped))
    total = sum(by.values())
    table = {**note, "idle_s": total / 1e9,
             "attributed_share": 1.0 - by.get(NO_SPAN, 0) / total if total else None,
             "by_span": [[k, v / 1e9] for k, v in
                         sorted(by.items(), key=lambda kv: -kv[1])]}
    run.record.note(idle_by_span=table)
    for name, seconds in table["by_span"]:
        print(f"idle {name}: {seconds:.6f} s "
              f"({100 * seconds * 1e9 / total:.1f}%)", file=sys.stderr)
    run._idle_table = table
    return table
