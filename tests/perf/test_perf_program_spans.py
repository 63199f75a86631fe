"""The metrics measured inside the program: the window on the ring's clock,
the clock join, idle time by innermost span and the parts of a first token's
time, on a small hand-made run (small_program_spans.json, whose times are
round milliseconds so that every number here is a hand sum); and the readers
in a tiny run on the CPU. No chip, nothing at import."""

import json
import pathlib
import sys

import pytest

from perf_helpers import ROOT, tiny_run

if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from perf.lib import manifest, program_spans, trace  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
MS = 1_000_000
OFFSET = 5_000_000_000_000
SPAN_ONLY = ["serve_ttft_queue_wait_ms", "serve_ttft_admit_ms",
             "serve_ttft_step_tail_ms", "serve_step_host_ms",
             "serve_kv_gather_useful_share", "serve_kv_pool_peak_share"]


class Record:
    def __init__(self, series):
        self.series, self.meta = series, {}

    def note(self, **kw):
        self.meta.update(kw)


class Run:
    """What a reader is handed, filled from the fixture."""

    def __init__(self, doc, with_trace=True):
        self.t_ready, self.setup_s = doc["t_ready"], doc["setup_s"]
        self.window = doc["window"]
        self.record = Record(doc["series"])
        self.notes = {}
        self.trace = trace.Trace(doc["trace"]) if with_trace else None
        self._program_spans = program_spans.from_events(self, True, doc["events"])


@pytest.fixture()
def doc():
    return json.loads((HERE / "small_program_spans.json").read_text())


def _read(name, run):
    return manifest.metric_reader(name)(run)


def test_the_window_is_chosen_on_the_rings_clock(doc):
    prog = Run(doc)._program_spans
    assert prog.t_open == 1000 * MS and prog.t_close == 1400 * MS
    # warm-up (step 0) falls before the opening, the drain (step 4) after
    assert [e[6]["step"] for e in prog.window_steps()] == [1, 2, 3]
    # step 1 began before the profiler did
    assert [e[6]["step"] for e in prog.traced_steps()] == [2, 3]
    assert [e[6]["req"] for e in prog.requests] == [1, 2]
    assert all(e[1] != "kv.evict" and e[5] is None for e in prog.tree)


def test_the_clock_join_recovers_a_planted_offset_and_its_residual(doc):
    run = Run(doc)
    table = program_spans.idle_table(run)
    assert table["offset_ns"] == OFFSET and table["residual_ns"] == 20_000
    assert table["pairs"] == 2
    assert run.notes["clock_join"]["offset_ns"] == OFFSET
    assert run.record.meta["clock_join"]["residual_ns"] == 20_000
    # twenty steps of uneven length, one of them 90 us off and one 300 us:
    # the 95th percentile of the deviations is the 19th of 20
    ring = [(i * 100 + i * i % 7) * MS for i in range(20)]
    there = [r + 7 * MS for r in ring]
    there[3] += 90_000
    there[11] += 300_000
    assert program_spans.clock_join(ring, there) == (7 * MS, 90_000, 20)
    # a step the profiler cut off one end: the closer of heads and tails
    assert program_spans.clock_join(ring, there[1:])[:2] == (7 * MS, 300_000)
    assert program_spans.clock_join(ring[:-1], there)[:2] == (7 * MS, 300_000)
    assert program_spans.clock_join(ring[:-1], there)[2] == 19
    assert program_spans.clock_join([], there) is None


def test_a_residual_over_the_limit_maps_nothing(doc, capsys):
    for span, jitter in zip(doc["trace"]["host"][1:], (-250_000, 250_000)):
        span[1] += jitter
    run = Run(doc)
    assert program_spans.idle_table(run) is None
    assert _read("serve_idle_attributed_share", run) is None
    assert run.notes["clock_join"]["residual_ns"] == 270_000
    assert "over the limit" in run.notes["clock_join"]["refused"]
    assert "idle_by_span" not in run.record.meta
    assert "residual 270000 ns" in capsys.readouterr().err


def test_idle_time_goes_to_the_innermost_span_and_sums_to_the_traces(doc, capsys):
    run = Run(doc)
    table = program_spans.idle_table(run)
    by = {k: round(v * 1e3, 6) for k, v in table["by_span"]}
    assert by == {
        "(no span)": 110, "serve.decode.sample": 14, "serve.decode.wait": 4,
        "serve.decode": 4, "serve.retire": 4, "serve.step": 4,
        "serve.decode.prepare": 4, "serve.decode.dispatch": 3,
        "serve.prefill": 3, "serve.kv_write": 3, "serve.schedule": 2,
        "serve.capacity": 2, "serve.first_token": 2, "serve.admit": 1}
    idle_s = run.trace.window_s - run.trace.busy_s("0")
    assert idle_s == pytest.approx(0.160)
    assert sum(v for _, v in table["by_span"]) == pytest.approx(idle_s, abs=1e-12)
    assert table["idle_s"] == pytest.approx(idle_s, abs=1e-12)
    assert _read("serve_idle_attributed_share", run) == pytest.approx(31.25)
    # step 2 admits nothing: its program starts on the device at 1125.5 ms,
    # 1.5 ms after serve.decode.dispatch began (step 3 admits: not judged)
    assert table["device_start_after_dispatch_ns"] == 1_500_000
    assert run.record.meta["idle_by_span"]["by_span"][0][0] == "(no span)"
    assert "idle serve.decode.sample: 0.014000 s (8.8%)" in capsys.readouterr().err


def _result(run, kind):
    """perf/run.py's last line of a traced run, for the hand-made run in a
    cell of traffic ``kind`` that lists no metric."""
    import argparse

    from perf import run as run_lib

    run.args = argparse.Namespace(trace=1, tiny=False, workload="cell")
    run.manifest = {"per_layer": [], "end_to_end": []}
    run.traffic = {"kind": kind}
    run.device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    run.checks, run.attempted, run.failed = [], 0, 0
    return run_lib.result(run)


def test_the_breakdown_books_serving_idle_time_to_the_programs_spans(doc):
    """Where the clock join holds, ``idle_gaps`` is the top ten of the idle
    table by innermost span, the same one the reader of
    ``serve_idle_attributed_share`` made; it sums to no more than the
    table's idle time."""
    run = Run(doc)
    assert _read("serve_idle_attributed_share", run) == pytest.approx(31.25)
    gaps = _result(run, "serve")["breakdown"]["idle_gaps"]
    table = program_spans.idle_table(run)
    assert gaps == table["by_span"][:10] and len(table["by_span"]) == 14
    assert gaps[:2] == [["(no span)", pytest.approx(0.110)],
                        ["serve.decode.sample", pytest.approx(0.014)]]
    assert sum(v for _, v in gaps) <= table["idle_s"]


def test_the_breakdown_keeps_the_benchmarks_spans_without_the_join(doc):
    """A residual over the limit (nothing mapped), or a training cell: the
    trace's own host spans, the benchmark's ``perf.*``."""
    for span, jitter in zip(doc["trace"]["host"][1:], (-250_000, 250_000)):
        span[1] += jitter
    run = Run(doc)
    gaps = _result(run, "serve")["breakdown"]["idle_gaps"]
    assert gaps == run.trace.idle_gaps(10)
    assert {name for name, _ in gaps} <= {"perf.engine.step", "(no span)"}
    run = Run(json.loads((HERE / "small_program_spans.json").read_text()))
    gaps = _result(run, "train")["breakdown"]["idle_gaps"]
    assert gaps == run.trace.idle_gaps(10) and not hasattr(run, "_idle_table")


def test_flatten_and_book():
    segs = program_spans.flatten([["a", 0, 10], ["b", 2, 5], ["c", 3, 4],
                                  ["d", 12, 14], ["e", 6, 11]])
    # e overruns its parent a and is cut at a's end
    assert segs == [[0, 2, "a"], [2, 3, "b"], [3, 4, "c"], [4, 5, "b"],
                    [5, 6, "a"], [6, 10, "e"], [12, 14, "d"]]
    assert program_spans.book([[1, 4], [9, 13]], segs) == {
        "a": 1, "b": 1, "c": 1, "e": 1, "(no span)": 2, "d": 1}
    assert program_spans.book([[20, 25]], segs) == {"(no span)": 5}


def test_the_parts_of_a_first_tokens_time_close_on_the_benchmarks(doc):
    run = Run(doc, with_trace=False)
    parts = program_spans.ttft_parts(run)
    # benchmark request 1 was refused: request 2 is the program's req 2
    assert [(p["request"], p["req"]) for p in parts] == [(0, 1), (2, 2)]
    first, second = parts
    assert first["late_ms"] == pytest.approx(2.0)
    assert first["queue_wait_ms"] == pytest.approx(9.01)
    assert first["admit_ms"] == 40 and first["step_tail_ms"] == 39
    assert first["gap_ms"] == pytest.approx(0.01)
    assert second["late_ms"] == pytest.approx(62.0)
    assert second["queue_wait_ms"] == pytest.approx(9.02)
    assert second["step_tail_ms"] == 69
    assert second["gap_ms"] == pytest.approx(0.01)
    closing = run.notes["ttft_parts"]
    assert closing["within_1ms_share"] == 1.0
    assert closing["gap_max_ms"] == pytest.approx(0.01)
    assert closing["submit_stamp_gap_max_ms"] == pytest.approx(0.02)
    assert run.record.meta["ttft_parts"] == closing
    assert _read("serve_ttft_queue_wait_ms", run) == pytest.approx(9.015)
    assert _read("serve_ttft_admit_ms", run) == 40
    assert _read("serve_ttft_step_tail_ms", run) == 54
    # steps 1-3: 80 - 28 - 30, 80 - 64, 110 - 54 - 30
    assert _read("serve_step_host_ms", run) == pytest.approx((22 + 16 + 26) / 3)
    # pages held 10, 12, 30 of 128 gathered and of a pool of 40; the drain's
    # 40 of 40 came after the window closed
    assert _read("serve_kv_gather_useful_share", run) \
        == pytest.approx(100 * (10 + 12 + 30) / 3 / 128)
    assert _read("serve_kv_pool_peak_share", run) == pytest.approx(75.0)
    assert _read("serve_idle_attributed_share", run) is None


def test_ring_summary_of_the_windows_steps_by_hand(doc):
    """Steps 1-3 lie in the window (1,000 to 1,400 ms, its middle at 1,200):
    steps 1 and 2 in the first half, step 3 in the second; 1 and 3 admit."""
    for e in doc["events"]:
        if e[1] == "serve.decode" and e[6]["step"] == 3:
            e[6]["inflight"] = 3
    run = Run(doc, with_trace=False)
    got = program_spans.ring_summary(run)
    assert run.record.meta["ring_summary"] == got
    assert got["steps"] == 3 and got["ring_full"] is False
    spans = got["spans"]
    assert spans["serve.step"] == {"n": 3, "median_ms": 80, "p95_ms": 110}
    assert spans["serve.decode.prepare"] == {"n": 3, "median_ms": 2, "p95_ms": 2}
    assert spans["serve.decode.dispatch"] == {"n": 3, "median_ms": 2, "p95_ms": 2}
    assert spans["serve.decode.wait"] == {"n": 3, "median_ms": 54, "p95_ms": 64}
    assert spans["serve.decode.sample"] == {"n": 3, "median_ms": 5, "p95_ms": 6}
    assert spans["serve.admit"] == {"n": 2, "median_ms": 40, "p95_ms": 40}
    assert spans["serve.kv_write"] == {"n": 2, "median_ms": 5, "p95_ms": 5}
    assert set(spans) == set(program_spans.SUMMARY_SPANS)
    # step 2 admits nothing; steps 1 and 3 one each: 80 and 110 ms
    assert got["steps_by_admissions"] == {
        "0": {"n": 1, "median_ms": 80, "p95_ms": 80},
        "1": {"n": 2, "median_ms": 95, "p95_ms": 110}}
    first, second = got["halves_median_ms"]
    assert first == {"serve.step": 80, "serve.decode.prepare": 2,
                     "serve.decode.dispatch": 2, "serve.decode.wait": 46,
                     "serve.decode.sample": 4.5, "serve.admit": 40,
                     "serve.kv_write": 5}
    assert second == {"serve.step": 110, "serve.decode.prepare": 2,
                      "serve.decode.dispatch": 2, "serve.decode.wait": 54,
                      "serve.decode.sample": 5, "serve.admit": 40,
                      "serve.kv_write": 5}
    # a decoded sequence ends one gap, of length nought where the step has
    # just admitted it: 1 - 1, 1 and 3 - 1 gaps; step 3's two end in a step
    # that admitted
    assert got["gaps"] == 3
    assert got["gap_share_admitting"] == pytest.approx(2 / 3)


def test_ring_summary_says_when_the_ring_had_wrapped(doc):
    run = Run(doc, with_trace=False)
    run._program_spans = program_spans.from_events(
        run, True, doc["events"], capacity=len(doc["events"]))
    assert program_spans.ring_summary(run)["ring_full"] is True
    run._program_spans = None
    assert program_spans.ring_summary(run) is None
    assert "ring_summary" not in Run(doc).record.meta


def test_requests_that_cannot_be_matched_are_not_guessed(doc):
    doc["series"]["refused"] = []
    run = Run(doc, with_trace=False)
    assert program_spans.ttft_parts(run) is None
    assert "3 accepted requests against 2" in run.notes["ttft_parts"]
    assert _read("serve_ttft_admit_ms", run) is None
    doc["series"]["refused"] = [[1, "refused"]]
    doc["series"]["submit"][2][2] += 0.002
    run = Run(doc, with_trace=False)
    assert program_spans.ttft_parts(run) is None
    assert "disagree" in run.notes["ttft_parts"]


@pytest.mark.parametrize("armed,events", [(False, []), (True, []), (True, [
    ["X", "serve.prefill", "serve", 1100 * MS, 5 * MS, None, {"seq": 1, "tokens": 9}],
    ["X", "serve.decode", "serve", 1110 * MS, 70 * MS, None, {"inflight": 1}]])],
    ids=["disarmed", "empty", "the_parents_two_spans"])
def test_a_program_without_the_span_tree_reads_as_nothing(doc, armed, events):
    """What the parent commit gives: no ring unless MLSL_TRACE=1, and then
    two spans with no ``step``. Every reader returns None and none raises."""
    run = Run(doc)
    run._program_spans = program_spans.from_events(run, armed, events)
    assert run._program_spans is None and run.notes["program_spans"]
    for name in SPAN_ONLY + ["serve_idle_attributed_share"]:
        assert _read(name, run) is None


def test_a_tiny_traced_run_reads_the_six_span_metrics(capsys):
    out, last, err = tiny_run(capsys, "gpt2-medium-serve-chat", seed=2**31 + 29,
                              seconds=1.5, trace=1)
    assert last["correct"] is True and last["metrics"] == {}
    dry = last["dry_run"]
    for name in SPAN_ONLY:
        assert dry[name] >= 0, name
    assert "serve_idle_attributed_share" not in dry
    assert 0 < dry["serve_kv_gather_useful_share"] <= 100
    assert 0 < dry["serve_kv_pool_peak_share"] <= 100
    assert dry["serve_ttft_admit_ms"] > 0 and dry["serve_step_host_ms"] > 0
    records = sorted((ROOT / "perf" / "out").glob(
        f"gpt2-medium-serve-chat.seed{2**31 + 29}.trace1.*.json"),
        key=lambda p: p.stat().st_mtime)
    closing = json.loads(records[-1].read_text())["meta"]["ttft_parts"]
    assert closing["requests"] == last["attempted"]
    # on the CPU, next to other tests, a stamp can be a millisecond late;
    # what the chip reads is in PERF.md
    assert closing["within_1ms_share"] >= 0.5
