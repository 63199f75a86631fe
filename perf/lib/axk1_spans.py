"""What the readers of the ``ax-k1-ep16-pp10-serve-docreason`` cell share:
the program's counters of the traced steps (``serve.decode``'s and
``serve.prefill.chunk``'s ``latent_rows``, ``experts_hit``,
``expert_tokens``, ``shared_tokens``), the device time of the absorbed
decode kernel (found by its name, ``latent_decode_attention``), and the
device time of the prefill kernel that walks a chunk over its context
(``latent_prefill_attention``).

A program without these spans, counters or kernel (an older commit) gives
``None`` or an empty list everywhere, and nothing raises."""

import re

from perf.lib import keye_spans, program_spans

DECODE_PROGRAM, CHUNK_PROGRAM = keye_spans.DECODE_PROGRAM, keye_spans.CHUNK_PROGRAM
KERNEL = re.compile(r"^latent_decode_attention")
PREFILL_KERNEL = re.compile(r"^latent_prefill_attention")


def _spans(run, name, traced):
    prog = program_spans.load(run)
    if prog is None:
        return []
    steps = prog.traced_steps() if traced else prog.window_steps()
    wanted = {e[6]["step"] for e in steps}
    return [e for e in prog.named(name)
            if e[6]["step"] in wanted and "latent_rows" in e[6]]


def decodes(run, traced=True):
    """The ``serve.decode`` spans' arguments of the traced steps (or of the
    whole window) that carry the latent counters."""
    return [e[6] for e in _spans(run, keye_spans.DECODE, traced)]


def chunks(run, traced=True):
    """The ``serve.prefill.chunk`` spans' arguments likewise."""
    return [e[6] for e in _spans(run, keye_spans.CHUNK, traced)]


def paired(run, program, spans):
    """The program's runs in the traced window ([start_ns, end_ns] on the
    first device) beside the spans that dispatched them, in order. A run
    that started before the first traced step's span and a span whose run
    started after the trace's end are the window's edges and are dropped.
    -> list of (run, span); empty without a trace."""
    if run.trace is None or not spans:
        return []
    runs = keye_spans.program_runs(run, program)
    if len(runs) > len(spans):
        runs = runs[len(runs) - len(spans):]
    return list(zip(runs, spans[:len(runs)]))


def _kernel_events(run, rx, lo, hi):
    dev = run.trace.devices[0]
    return [e for e in run.trace.data["devices"][dev]["ops"]
            if lo <= e[1] < hi and rx.search(e[0])]


def kernel_ns(run, lo, hi):
    """Device time of the absorbed decode kernel inside [lo, hi)."""
    return sum(e[2] for e in _kernel_events(run, KERNEL, lo, hi))


def prefill_kernel_ns(run, lo, hi):
    """Device time of the prefill kernel in one run of the chunk program,
    [lo, hi): the sum of its events, one a layer. None where the run does
    not show exactly ``num_hidden_layers`` of them."""
    events = _kernel_events(run, PREFILL_KERNEL, lo, hi)
    if len(events) != run.config["num_hidden_layers"]:
        return None
    return sum(e[2] for e in events)
