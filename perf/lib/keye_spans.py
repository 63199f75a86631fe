"""What the readers of the ``keye-vl2-30b-a3b-serve-longctx`` cell share: the
program's counters of the traced steps (``serve.decode``'s ``ctx_tokens``,
``selected_tokens``, ``experts_hit``, ``expert_tokens``; ``serve.prefill.
chunk``'s ``offset``, ``tokens``, ``last`` and expert counts), and the device
time of the walks inside the decode and chunk programs.

A program without these spans or counters (an older commit) gives ``None``
or an empty list everywhere, and nothing raises."""

import re

from perf.lib import program_spans, trace as trace_lib

CHUNK, DECODE = "serve.prefill.chunk", "serve.decode"
DECODE_PROGRAM, CHUNK_PROGRAM = r"decode_body", r"chunk_body"


def _in_steps(prog, name, steps):
    wanted = {e[6]["step"] for e in steps}
    return [e for e in prog.named(name) if e[6]["step"] in wanted]


def decodes(run, traced=True):
    """The ``serve.decode`` spans' arguments that carry the selection and
    expert counters, of the traced steps (or of the whole window)."""
    prog = program_spans.load(run)
    if prog is None:
        return []
    steps = prog.traced_steps() if traced else prog.window_steps()
    return [e[6] for e in _in_steps(prog, DECODE, steps)
            if e[6].get("ctx_tokens") and "experts_hit" in e[6]]


def chunks(run, traced=True):
    """The ``serve.prefill.chunk`` spans (whole events) of the traced steps
    (or of the whole window)."""
    prog = program_spans.load(run)
    if prog is None:
        return []
    steps = prog.traced_steps() if traced else prog.window_steps()
    return [e for e in _in_steps(prog, CHUNK, steps) if "offset" in e[6]]


# -- the walks inside a program's run, from the device trace -------------------
#
# The decode program runs, layer by layer and in this order, two loops that
# the trace shows as ``while`` operations: the counting passes of the exact
# top-k (``select``) and the walk over the tiles of the experts that got a
# token (``experts``). Between the end of one layer's ``experts`` and the
# start of the next ``select`` the device projects q, k, v and the indexer's
# queries, writes the pools, gathers the slots' index keys and scores them;
# between ``select`` and ``experts`` it compacts the selection, gathers the
# selected rows, attends, projects back, norms, routes and sorts. The chunk
# program runs three loops a layer at the top level (``index``: the index
# scores over the context; ``attend``: the masked walk over the context;
# ``experts``) and, inside a ``conditional`` between the first two, the
# counting passes when the context is longer than ``topk``.

DECODE_WALKS = ("select", "experts")
CHUNK_WALKS = ("index", "attend", "experts")


def program_runs(run, program):
    """-> [[start_ns, end_ns], ...] of the program's runs that start inside
    the traced window, on the first device."""
    tr = run.trace
    dev = tr.devices[0]
    rx = re.compile(program)
    return [[e[1], e[1] + e[2]] for e in tr.data["devices"][dev]["modules"]
            if rx.search(e[0]) and tr.lo <= e[1] < tr.hi]


def walks(run, program, kinds):
    """The device time of each run of ``program`` in the traced window, cut
    at its top-level loops: -> list of runs, each a list of layers, each
    {kind: ns of that loop, "before_<kind>": ns between the loop before it
    (or the layer's start) and it}, plus the run's ``tail`` after the last
    loop and its ``total``; or None where the trace does not show ``len(kinds)`` loops a
    layer (another program, a compiler that unrolled one)."""
    tr = run.trace
    if tr is None:
        return None
    dev = tr.devices[0]
    ops = tr.data["devices"][dev]["ops"]
    layers = run.config["num_hidden_layers"]
    out = []
    for lo, hi in program_runs(run, program):
        inside = [e for e in ops if lo <= e[1] < hi]
        outer = [e for e in inside
                 if trace_lib.op_family(e[0]) in ("while", "conditional")]
        # top level: not inside another loop or conditional
        top, end = [], lo
        for e in sorted(outer, key=lambda e: e[1]):
            if e[1] >= end:
                top.append(e)
                end = e[1] + e[2]
        loops = [e for e in top if trace_lib.op_family(e[0]) == "while"]
        if len(loops) != layers * len(kinds):
            return None
        at, rows = lo, []
        for i in range(layers):
            row = {}
            for kind, e in zip(kinds, loops[i * len(kinds):]):
                row["before_" + kind] = e[1] - at
                row[kind] = e[2]
                at = e[1] + e[2]
            rows.append(row)
        out.append({"layers": rows, "tail": hi - at, "total": hi - lo})
    return out or None


def decode_share(run, seconds_of, least_of=None):
    """A share of the decode program's device time in the traced window, for
    the readers of its walks. ``seconds_of(layer)``: the ns of a layer
    (``walks``) that the metric times. Without ``least_of``: that time over
    the runs' whole time. With ``least_of(counters, layers)`` -> the least
    seconds the step's work needs, from the ``serve.decode`` counters of the
    span that dispatched the run: a roofline share, least over timed. -> a
    percentage, or None where the runs and the spans do not pair up (another
    program, a run cut by the trace's edge, a compiler that unrolled a loop)
    or the program carries no counters."""
    spans = decodes(run)
    runs = walks(run, DECODE_PROGRAM, DECODE_WALKS) if spans else None
    if not runs or len(runs) != len(spans):
        return None
    timed = sum(seconds_of(layer) for r in runs for layer in r["layers"])
    if least_of is None:
        return 100.0 * timed / sum(r["total"] for r in runs)
    least = sum(least_of(d, len(r["layers"])) for r, d in zip(runs, spans))
    return 100.0 * least / (timed / 1e9) if timed else None
