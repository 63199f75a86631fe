"""``prefill_chunk_p90_ms`` (PR 33): the reader on the hand-made run of the
long-context cell (small_keye_run.json: three chunks in the window, 25, 40 and
20 ms), on a program without the spans, and its entry in the manifest. A file
of its own: test_perf_keye.py's ``HAND`` table is as PR 29 left it."""

import json
import pathlib
import sys

import pytest

from perf_helpers import ROOT

if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from perf.lib import manifest, program_spans  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
NAME = "prefill_chunk_p90_ms"
CELL = "keye-vl2-30b-a3b-serve-longctx"


class Run:
    """What the reader is handed, filled from the fixture: the window and
    the program's ring (no device trace: the metric is a ``program_span``)."""

    def __init__(self, doc, events=None):
        self.t_ready, self.setup_s = doc["t_ready"], doc["setup_s"]
        self.window, self.config = doc["window"], doc["config"]
        self.notes, self.trace = {}, None
        self._program_spans = program_spans.from_events(
            self, True, doc["events"] if events is None else events)


@pytest.fixture()
def doc():
    return json.loads((HERE / "small_keye_run.json").read_text())


def read(run):
    return manifest.metric_reader(NAME)(run)


def test_the_reader_gives_the_hand_worked_percentile(doc):
    """Nearest rank, as every tail of the benchmark: of 20, 25 and 40 ms the
    third (ceil(0.9 x 3) = 3), where the median reader gives 25."""
    assert read(Run(doc)) == pytest.approx(40.0, rel=1e-9)
    assert manifest.metric_reader("prefill_chunk_ms")(Run(doc)) \
        == pytest.approx(25.0, rel=1e-9)


@pytest.mark.parametrize("chunks,want", [
    ([30], 30.0), ([10] * 9 + [70], 10.0), ([10] * 8 + [70, 70], 70.0),
    (list(range(1, 21)), 18.0)])
def test_the_percentile_is_the_nearest_rank(doc, chunks, want):
    """The window's chunks replaced by ``chunks`` (ms), one a step."""
    first = next(e for e in doc["events"] if e[1] == "serve.prefill.chunk")
    events = [e for e in doc["events"] if e[1] != "serve.prefill.chunk"]
    events += [[*first[:4], ms * 1_000_000, first[5], dict(first[6])]
               for ms in chunks]
    assert read(Run(doc, events=events)) == pytest.approx(want, rel=1e-9)


def test_the_reader_finds_nothing_in_a_program_without_the_spans(doc):
    """A program that does not prefill by chunks (GPT-2's cell, an older
    commit): ``None``, never 0, and nothing raises."""
    old = [e for e in doc["events"] if e[1] != "serve.prefill.chunk"]
    assert read(Run(doc, events=old)) is None


def test_the_p90_entry_matches_the_median_entry():
    """Found by name, wherever it stands among the entries: the same layer,
    source, unit, cells and end-to-end metric as the median's."""
    man = manifest.load()
    entry = next(m for m in man["per_layer"] if m["name"] == NAME)
    median = next(m for m in man["per_layer"] if m["name"] == "prefill_chunk_ms")
    assert entry == {**median, "name": NAME}
    assert CELL in entry["workloads"] and CELL in median["workloads"]
    assert entry["moves"] == "ttft_p50_ms"
