"""The cell ``keye-vl2-30b-a3b-serve-longctx``: its configuration against the
catalog's numbers, its counts by hand, each of its readers on a small
hand-made run (small_keye_run.json, times in round milliseconds), its traffic,
and perf/run.py --tiny of it with the control and the two planted faults. No
chip, nothing at import."""

import json
import pathlib
import sys

import numpy as np
import pytest

from perf_helpers import RESULT_KEYS, ROOT, tiny_run

if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from perf.lib import (counts_keye as counts, keye_spans, manifest,  # noqa: E402
                      program_spans, trace, traffic)

HERE = pathlib.Path(__file__).resolve().parent
CELL = "keye-vl2-30b-a3b-serve-longctx"
MANIFEST = manifest.load()
CONFIG = manifest.config_file(MANIFEST, "keye-vl2-30b-a3b-pp8")
TRAFFIC = manifest.traffic_file("longctx-open-loop")


# -- the configuration ----------------------------------------------------------

def test_the_configuration_keeps_the_published_widths():
    """Every number of the catalog row's config under the same key; the
    depth alone is cut, as one stage of eight."""
    published = {
        "hidden_size": 2048, "num_attention_heads": 32,
        "num_key_value_heads": 4, "head_dim": 128, "num_experts": 128,
        "num_local_experts": 128, "num_experts_per_tok": 8,
        "moe_intermediate_size": 768, "intermediate_size": 6144,
        "vocab_size": 151936, "rope_theta": 10000000, "rms_norm_eps": 1e-06,
        "max_position_embeddings": 262144, "norm_topk_prob": True,
        "tie_word_embeddings": False, "decoder_sparse_step": 1,
        "mlp_only_layers": [], "attention_bias": False}
    for key, value in published.items():
        assert CONFIG[key] == value, key
    assert CONFIG["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512,
        "topk": 2048}
    assert CONFIG["rope_scaling"]["mrope_section"] == [16, 24, 24]
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    assert CONFIG["num_hidden_layers"] == 6
    assert CONFIG["published"] == {"num_hidden_layers": 48}
    assert "eight pipeline stages of six layers" in CONFIG["deployment"]
    assert CONFIG["weights_dtype"] == CONFIG["kv_dtype"] == "bfloat16"
    entry = manifest.config_entry(MANIFEST, "keye-vl2-30b-a3b-pp8")
    assert entry["reduced"] == ["num_hidden_layers"]
    for needed in ("vision_tower", "qk_norm", "indexer", "initializer_range",
                   "q_chunk_size_kv_chunk_size", "embedding_and_head_here"):
        assert CONFIG["assumed"][needed]


def test_the_issues_arithmetic_of_parameters_and_cache():
    """625.4 M parameters a layer, 4,375 M on this chip; a token's cache."""
    per_layer = counts.dense_params_per_layer(CONFIG) \
        + CONFIG["num_experts"] * counts.expert_params(CONFIG)
    assert counts.expert_params(CONFIG) * 128 == 603_979_776
    assert round(per_layer / 1e6, 1) == 625.4
    assert round(counts.param_count(CONFIG) / 1e6) == 4375
    s = counts.dims(CONFIG)
    token = s["layers"] * (2 * s["g"] * s["dh"] + s["di"]) * counts.BF16
    assert token == 13056


# -- the counts, on a toy whose sums a reader can follow -------------------------

@pytest.fixture()
def doc():
    return json.loads((HERE / "small_keye_run.json").read_text())


def test_counts_match_hand_sums(doc):
    cfg = doc["config"]
    assert counts.dense_params_per_layer(cfg) == 192 + 64 + 32
    assert counts.expert_params(cfg) == 72
    assert counts.token_flat_ops(cfg) == 2 * (576 + 288) + 160
    assert counts.token_flat_ops(cfg, head=False) == 1728
    assert counts.index_ops(cfg, 11) == 16 * 11
    assert counts.selected_attention_ops(cfg, 8) == 64 * 8
    # positions 3, 4, 5 read 4, 5, 6 positions and keep 4 of them each
    assert counts.span_sums(3, 6, 4) == (15, 12)
    assert counts.span_sums(0, 6, 4) == (21, 1 + 2 + 3 + 4 + 4 + 4)
    assert counts.span_sums(0, 3, 4) == (6, 6)
    assert counts.prefill_ops(cfg, 3, 6, True) == 3 * 1728 + 160 + 240 + 768
    assert counts.prefill_ops(cfg, 0, 3, False) == 3 * 1728 + 96 + 384
    assert counts.decode_ops(cfg, 2, 11, 8) == 2 * 1888 + 176 + 512
    assert counts.decode_step_bytes(cfg, 2, 11, 8, 6) == 1312 + 864 + 88 + 256
    assert counts.expert_walk(cfg, 6, 8) == (1152, 864)
    assert counts.index_select(cfg, 11) == (88, 44)
    assert counts.selected_rows(cfg, 8) == (256, 128)


# -- each reader on the hand-made run ----------------------------------------------

class Record:
    def __init__(self, series):
        self.series, self.meta = series, {}

    def note(self, **kw):
        self.meta.update(kw)


class Run:
    """What a reader is handed, filled from the fixture."""

    def __init__(self, doc, with_trace=True, events=None):
        self.t_ready, self.setup_s = doc["t_ready"], doc["setup_s"]
        self.window, self.config = doc["window"], doc["config"]
        self.record, self.notes = Record(doc["series"]), {}
        self.trace = trace.Trace(doc["trace"]) if with_trace else None
        self._program_spans = program_spans.from_events(
            self, True, doc["events"] if events is None else events)
        self._peaks = doc["peaks"]

    def peaks(self):
        return self._peaks


def _read(name, run):
    return manifest.metric_reader(name)(run)


HAND = {
    # two decode steps (4,464 and 4,496 operations) and the chunk at offset 3
    # (6,352) in 0.3 s at 1e6 operations a second
    "serve_mfu_model_active": 100 * (4464 + 4496 + 6352) / 0.3 / 1e6,
    # 2,520 + 2,392 bytes at 1e6 B/s against two runs of 40 ms
    "sparse_decode_step_roofline": 100 * 4.912 / 80,
    # 1,152 operations a step bound it (864 and 720 bytes do not); the walks
    # take 10 + 12 ms a run
    "expert_product_roofline": 100 * 2.304 / 44,
    "expert_share_of_step": 100 * 44 / 80,
    # two layers of 88 and of 104 operations against (2 + 1) ms a layer
    "indexer_select_roofline": 100 * (0.176 + 0.208) / 12,
    "indexer_select_share_of_step": 100 * 12 / 80,
    # two layers of 256 operations a step against 5 + 4 ms a run
    "sparse_attn_roofline": 100 * 1.024 / 18,
    # the window's three decode steps: 8 + 8 + 4 of 11 + 13 + 9 positions
    "sparse_selected_share": 100 * 20 / 33,
    "expert_distinct_per_step": (6 + 5 + 3) / 3 / 2,
    "prefill_chunk_ms": 25.0,
    # request 7: 1,011 ms to the end of its last chunk at 1,101; request 8
    # has not finished its prompt
    "serve_ttft_prefill_ms": 90.0,
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_a_reader_gives_the_hand_sum(doc, name):
    assert _read(name, Run(doc)) == pytest.approx(HAND[name], rel=1e-9)
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert CELL in entry["workloads"]


@pytest.mark.parametrize("name", sorted(HAND))
def test_a_reader_finds_nothing_in_a_program_without_the_counters(doc, name):
    """The parent's program (no ``serve.prefill.chunk``, no selection or
    expert counters on ``serve.decode``) and a run without a trace: ``None``,
    never 0, and nothing raises."""
    old = [e for e in doc["events"] if e[1] != "serve.prefill.chunk"]
    for e in old:
        for key in ("ctx_tokens", "selected_tokens", "experts_hit",
                    "expert_tokens"):
            e[6].pop(key, None)
    assert _read(name, Run(doc, events=old)) is None
    from_trace = next(m for m in MANIFEST["per_layer"]
                      if m["name"] == name)["source"] == "device_trace"
    if from_trace:
        assert _read(name, Run(doc, with_trace=False)) is None


def test_the_walks_are_cut_at_the_top_level_loops(doc):
    run = Run(doc)
    runs = keye_spans.walks(run, keye_spans.DECODE_PROGRAM,
                            keye_spans.DECODE_WALKS)
    assert len(runs) == 2 and runs[0]["total"] == 40e6 and runs[0]["tail"] == 3e6
    assert runs[0]["layers"] == [
        {"before_select": 2e6, "select": 1e6, "before_experts": 5e6,
         "experts": 10e6},
        {"before_select": 2e6, "select": 1e6, "before_experts": 4e6,
         "experts": 12e6}]
    # a compiler that unrolled a loop leaves another number of them: no guess
    ops = doc["trace"]["devices"]["0"]["ops"]
    doc["trace"]["devices"]["0"]["ops"] = [e for e in ops if e[0] != "while.3"]
    assert keye_spans.walks(Run(doc), keye_spans.DECODE_PROGRAM,
                            keye_spans.DECODE_WALKS) is None
    assert _read("expert_share_of_step", Run(doc)) is None


# -- the traffic ------------------------------------------------------------------

def _offered_cv(order, bins=6, seconds=51):
    reqs = traffic.requests(1, {**TRAFFIC, "arrival_order_seed": order},
                            seconds, 1000)
    prompt, answer = np.zeros(bins), np.zeros(bins)
    for r in reqs:
        b = min(bins - 1, int(r["due"] / seconds * bins))
        prompt[b] += len(r["prompt"])
        answer[b] += r["max_new"]
    return max(prompt.std() / prompt.mean(), answer.std() / answer.mean())


def test_the_arrival_order_is_the_most_even_of_its_candidates():
    """PR 28's rule, on this file's rate and lengths."""
    cvs = {order: _offered_cv(order) for order in range(1, 33)}
    assert min(cvs, key=cvs.get) == TRAFFIC["arrival_order_seed"]


def test_the_traffic_is_the_issues():
    t = TRAFFIC
    assert t["kind"] == "serve" and t["rate_from"] and t["seed_role"]
    assert t["prompt_tokens"] == {"median": 8192, "sigma": 0.7, "min": 2048,
                                  "max": 32768}
    assert t["answer_tokens"] == {"median": 192, "sigma": 0.55, "min": 64,
                                  "max": 512}
    assert (t["max_total_tokens"], t["max_batch"], t["prefill_chunk_tokens"],
            t["queue_depth"]) == (33280, 16, 2048, 256)
    assert t["warm_up"] == {"requests": 2, "prompt_tokens": 4096,
                            "answer_tokens": 8}
    assert t["check_requests"] >= 4 and 3 <= t["trace_seconds"] <= 5
    reqs = traffic.requests(3, t, 51, CONFIG["vocab_size"])
    assert len(reqs) == traffic.request_count(t, 51)
    assert all(2048 <= len(r["prompt"]) <= 32768 and 64 <= r["max_new"] <= 512
               and len(r["prompt"]) + r["max_new"] <= 33280 for r in reqs)
    # every context is longer than topk: the selection is at work on each
    assert min(len(r["prompt"]) for r in reqs) >= CONFIG["sa_config"]["topk"]
    # the pool: whole pages of three bfloat16 pools, index keys on 128 lanes
    page = t["kv_page_tokens"] * 6 * (2 * 4 * 128 + 128) * 2
    assert page == 884736 and t["max_total_tokens"] % t["kv_page_tokens"] == 0
    assert t["prefill_chunk_tokens"] % t["kv_page_tokens"] == 0


# -- perf/run.py --tiny --------------------------------------------------------------

def test_tiny_run_of_the_cell_and_its_record(capsys):
    seed = 2**31 + 29
    out, last, err = tiny_run(capsys, CELL, seed=seed, seconds=2.0)
    assert RESULT_KEYS <= set(last) and list(last)[-1] == "checks"
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] == traffic.request_count(
        {**TRAFFIC, **TRAFFIC["tiny"]}, 2.0)
    assert last["metrics"] == {} and last["device"]["platform"] == "cpu"
    assert set(last["dry_run"]) == {"setup_s", "ttft_p50_ms", "itl_p95_ms"}
    assert "check served_logit_gap_max:" in err
    records = sorted((ROOT / "perf" / "out").glob(
        f"{CELL}.seed{seed}.trace0.*.json"), key=lambda p: p.stat().st_mtime)
    record = json.loads(records[-1].read_text())
    assert record["series"]["engine_step"] and record["meta"]["correct"] is True
    ring = record["meta"]["ring_summary"]
    # an admission is the step of a request's first chunk: one a request
    assert ring["spans"]["serve.admit"]["n"] <= last["attempted"]
    assert ring["spans"]["serve.kv_write"]["n"] == 0    # the chunk writes
    assert record["meta"]["checked_requests"] and not record["meta"].get(
        "failure_counters")


def test_tiny_traced_run_reports_the_counter_and_span_metrics(capsys):
    out, last, _ = tiny_run(capsys, CELL, seed=12, seconds=2.0, trace=1)
    named = {m["name"] for m in manifest.metrics_of(MANIFEST, CELL, "per_layer")}
    assert set(last["dry_run"]) <= named and last["metrics"] == {}
    for name in ("sparse_selected_share", "expert_distinct_per_step",
                 "prefill_chunk_ms", "serve_ttft_prefill_ms",
                 "decode_batch_occupancy", "serve_kv_pool_peak_share",
                 "serve_ttft_queue_wait_ms"):
        assert last["dry_run"][name] > 0, name
    # the toy's contexts are several times its topk of 16
    assert last["dry_run"]["sparse_selected_share"] < 60
    assert last["dry_run"]["expert_distinct_per_step"] <= 8
    assert last["correct"] is True


@pytest.mark.parametrize("fault", ["recent_window", "experts_top7"])
def test_a_planted_fault_comes_out_not_correct(capsys, fault):
    from perf import keye_faults

    try:
        out, last, err = tiny_run(capsys, CELL, seed=5, seconds=2.0,
                                  prepare=keye_faults.planting(fault))
    finally:
        keye_faults.restore()
    value, limit = last["checks"]["served_logit_gap_max"]
    assert last["correct"] is False and last["failed"] == 0 and value > limit
    assert err.strip().splitlines()[-1] == "correct: False"


def test_the_float8_control_comes_out_not_correct(capsys):
    from perf import control

    readings = control.main(["--workload", CELL, "--seed", "5",
                             "--seconds", "2", "--tiny"])
    lines = [json.loads(line) for line in
             capsys.readouterr().out.strip().splitlines()]
    assert [line["in_place"] for line in lines] == ["program", "control_fp8"]
    assert lines[0]["correct"] is True and lines[1]["correct"] is False
    assert readings["control_fp8"]["correct"] is False
