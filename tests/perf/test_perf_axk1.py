"""The cell ``ax-k1-ep16-pp10-serve-docreason``: its configuration against the
catalog's numbers and the stated cut, its counts by hand, each of its new
readers on a small hand-made run (times in round milliseconds), its traffic,
and perf/run.py --tiny of it with the control and the three planted faults.
No chip, nothing at import."""

import copy
import json
import os

import numpy as np
import pytest

from perf_helpers import RESULT_KEYS, ROOT, tiny_run

import sys

if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from perf.lib import (axk1_spans, counts_axk1 as counts, manifest,  # noqa: E402
                      program_spans, trace, traffic)

CELL = "ax-k1-ep16-pp10-serve-docreason"
MANIFEST = manifest.load()
CONFIG = manifest.config_file(MANIFEST, "ax-k1-ep16-pp10")
TRAFFIC = manifest.traffic_file("docreason-open-loop")
NEW = ("latent_decode_roofline", "latent_decode_share_of_step",
       "latent_prefill_roofline", "serve_mfu_latent_active")


# -- the configuration ----------------------------------------------------------

def test_the_configuration_keeps_every_published_number_but_the_cut():
    """Every number of the catalog row's config under the same key; the
    depth, the experts held and the vocabulary's slice are cut, each named
    in ``reduced`` with its published value beside it."""
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "hidden_act": "silu", "hidden_size": 7168, "intermediate_size": 18432,
        "kv_lora_rank": 512, "max_position_embeddings": 131072,
        "model_type": "axk1", "moe_intermediate_size": 2048,
        "moe_layer_freq": 1, "n_group": 8, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 64,
        "num_experts_per_tok": 8, "num_key_value_heads": 64,
        "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "seq_aux": True, "tie_word_embeddings": False, "topk_group": 4,
        "topk_method": "none", "v_head_dim": 128}
    for key, value in published.items():
        assert CONFIG[key] == value, key
    assert CONFIG["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    reduced = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert CONFIG["reduced"] == reduced
    assert manifest.config_entry(MANIFEST, "ax-k1-ep16-pp10")["reduced"] \
        == reduced
    assert (CONFIG["num_hidden_layers"], CONFIG["n_routed_experts"],
            CONFIG["vocab_size"]) == (7, 12, 20480)
    assert CONFIG["published"] == {"num_hidden_layers": 61,
                                   "n_routed_experts": 192,
                                   "vocab_size": 163840}
    assert "16 chips share each layer" in CONFIG["deployment"]
    assert "1/16" in CONFIG["expert_load"] and CONFIG["held_from"] == 0
    for needed in ("correction_bias", "group_score", "rotary", "held_experts",
                   "embedding_and_head_here", "initializer_range", "mtp"):
        assert CONFIG["assumed"][needed]


def test_the_arithmetic_of_parameters_and_pool():
    """101.1 M parameters of attention a layer, 396.4 M in the dense layer,
    44.0 M an expert, 4,841 M on this chip (9.68 GB in bfloat16); a page of
    64 latent rows in 7 layers 573,440 B, 5,617 of them in 3 GiB."""
    assert round(counts.attention_params(CONFIG) / 1e6, 1) == 101.1
    assert round(counts.dense_mlp_params(CONFIG) / 1e6, 1) == 396.4
    assert round(counts.expert_params(CONFIG) / 1e6, 1) == 44.0
    assert round(counts.param_count(CONFIG) / 1e6) == 4841
    assert counts.row_lanes(CONFIG) == 640
    page = TRAFFIC["kv_page_tokens"] * 7 * 640 * counts.BF16
    assert page == 573440
    assert (TRAFFIC["kv_cache_mb"] << 20) // page == 5617


# -- the counts, on a toy whose sums a reader can follow -------------------------

TOY = {"hidden_size": 8, "num_attention_heads": 2, "q_lora_rank": 4,
       "kv_lora_rank": 4, "qk_nope_head_dim": 2, "qk_rope_head_dim": 2,
       "v_head_dim": 2, "intermediate_size": 6, "moe_intermediate_size": 3,
       "n_routed_experts": 2, "published": {"n_routed_experts": 4},
       "n_shared_experts": 1, "first_k_dense_replace": 1, "vocab_size": 10,
       "num_hidden_layers": 3}


def test_counts_match_hand_sums():
    # q_a 32, q_b 32, kv_a 48, kv_b 32, o 32
    assert counts.attention_params(TOY) == 176
    assert counts.expert_params(TOY) == 72
    assert counts.dense_mlp_params(TOY) == 144
    assert counts.moe_flat_params(TOY) == 32 + 72
    assert counts.param_count(TOY) == 3 * 176 + 144 + 2 * (104 + 144) + 160
    assert counts.row_lanes(TOY) == 128
    assert counts.token_flat_ops(TOY) == 2 * (528 + 144 + 208) + 160
    assert counts.token_flat_ops(TOY, head=False) == 1760
    # a row: 2 heads x 2 x (4 + 2 + 4)
    assert counts.latent_decode(TOY, 15) == (600, 15 * 256)
    assert counts.span_contexts(4, 6) == 5 + 6
    # 3 layers x 11 context pairs x 2 heads x 2 x (2 + 2 + 2)
    assert counts.latent_prefill(TOY, 4, 6) == (3 * 11 * 24, 3 * 6 * 256)
    assert counts.decode_ops(TOY, 2, 3, 15) == 2 * 1920 + 432 + 600
    assert counts.prefill_ops(TOY, 4, 6, True, 2) \
        == 2 * 1760 + 160 + 288 + 792


# -- each reader on a hand-made run ----------------------------------------------

def _doc():
    """A run with the window open at 1.0 s for 0.4 s, traced from 1.05 s to
    1.35 s: a decode before the traced part; a chunk (offset 0, 4 tokens)
    and a decode of one sequence; the prompt's last chunk (offset 4, 2
    tokens) and a decode of two."""
    ms = 1_000_000

    def span(name, t, dur, **kw):
        return ["X", name, "serve", t * ms, dur * ms, None, kw]

    events = [
        span("serve.step", 1010, 30, step=0),
        span("serve.decode", 1012, 20, step=0, inflight=1, tokens_live=4,
             pages_held=1, pages_gathered=8, pool_pages=10, ctx_tokens=4,
             experts_hit=1, expert_tokens=2, shared_tokens=1, latent_rows=12),
        span("serve.step", 1060, 100, step=1),
        span("serve.prefill.chunk", 1061, 32, step=1, req=7, chunk=0,
             offset=0, tokens=4, last=False, experts_hit=2, expert_tokens=3,
             shared_tokens=4, latent_rows=12, select_width=0),
        span("serve.decode", 1110, 42, step=1, inflight=1, tokens_live=5,
             pages_held=2, pages_gathered=8, pool_pages=10, ctx_tokens=5,
             experts_hit=1, expert_tokens=2, shared_tokens=1, latent_rows=15),
        span("serve.step", 1200, 100, step=2),
        span("serve.prefill.chunk", 1201, 32, step=2, req=7, chunk=1,
             offset=4, tokens=2, last=True, experts_hit=2, expert_tokens=2,
             shared_tokens=2, latent_rows=18, select_width=0),
        span("serve.decode", 1250, 42, step=2, inflight=2, tokens_live=12,
             pages_held=3, pages_gathered=8, pool_pages=10, ctx_tokens=12,
             experts_hit=2, expert_tokens=3, shared_tokens=2, latent_rows=36),
    ]
    # the programs' device time: a chunk run has the prefill kernel a layer
    # and an experts' loop an expert layer; a decode run the decode kernel
    # a layer and an experts' loop an expert layer
    chunk_ops = lambda t: [  # noqa: E731
        ["latent_prefill_attention.1", t, 5 * ms],
        ["fusion.1", t + 5 * ms, 1 * ms],
        ["latent_prefill_attention.2", t + 6 * ms, 6 * ms],
        ["while.3", t + 12 * ms, 3 * ms],
        ["latent_prefill_attention.3", t + 15 * ms, 7 * ms],
        ["while.5", t + 22 * ms, 3 * ms], ["fusion.2", t + 25 * ms, 5 * ms]]
    decode_ops = lambda t: [  # noqa: E731
        ["latent_decode_attention.1", t, 2 * ms],
        ["fusion.3", t + 2 * ms, 8 * ms],
        ["latent_decode_attention.2", t + 10 * ms, 3 * ms],
        ["while.6", t + 13 * ms, 6 * ms],
        ["latent_decode_attention.3", t + 19 * ms, 3 * ms],
        ["while.7", t + 22 * ms, 6 * ms], ["fusion.4", t + 28 * ms, 12 * ms]]
    ops = chunk_ops(1062 * ms) + decode_ops(1111 * ms) \
        + chunk_ops(1202 * ms) + decode_ops(1251 * ms)
    modules = [["jit_chunk_body(1)", 1062 * ms, 30 * ms],
               ["jit_decode_body(2)", 1111 * ms, 40 * ms],
               ["jit_chunk_body(1)", 1202 * ms, 30 * ms],
               ["jit_decode_body(2)", 1251 * ms, 40 * ms]]
    return {
        "config": dict(TOY, first_k_dense_replace=1),
        "peaks": {"bf16_flops": 1e6, "hbm_bytes_per_s": 1e6},
        "t_ready": 0.5, "setup_s": 0.5,
        "window": {"seconds": 0.4, "traced": [0.05, 0.35]},
        "events": events,
        "trace": {"host": [["perf.window", 1050 * ms, 300 * ms]],
                  "devices": {"0": {"ops": ops, "modules": modules,
                                    "async": []}}}}


class Record:
    def __init__(self):
        self.series, self.meta = {}, {}


class Run:
    """What a reader is handed, filled from the hand-made run."""

    def __init__(self, doc, with_trace=True):
        self.t_ready, self.setup_s = doc["t_ready"], doc["setup_s"]
        self.window, self.config = doc["window"], doc["config"]
        self.record, self.notes = Record(), {}
        self.trace = trace.Trace(doc["trace"]) if with_trace else None
        self._program_spans = program_spans.from_events(
            self, True, doc["events"])
        self._peaks = doc["peaks"]

    def peaks(self):
        return self._peaks


HAND = {
    # 15 and 36 rows: 3.84 + 9.216 ms of bytes at 1e6 B/s against 8 + 8 ms
    # of the kernel
    "latent_decode_roofline": 100 * (3.840 + 9.216) / 16,
    "latent_decode_share_of_step": 100 * 16 / 80,
    # the chunks' causal operations (3 x 10 x 24 and 3 x 11 x 24) against
    # the prefill kernel's three events, 5 + 6 + 7 ms a run (the experts'
    # walks left out); the latent rows' bytes (3 x 4 x 256, 3 x 6 x 256)
    # are longer
    "latent_prefill_roofline": 100 * (3.072 + 4.608) / 36,
    # two decodes (1 and 2 tokens) and two chunks in 0.3 s at 1e6 a second
    "serve_mfu_latent_active": 100 * (
        counts.decode_ops(TOY, 1, 2, 15) + counts.decode_ops(TOY, 2, 3, 36)
        + counts.prefill_ops(TOY, 0, 4, False, 3)
        + counts.prefill_ops(TOY, 4, 6, True, 2)) / 0.3 / 1e6,
}


@pytest.mark.parametrize("name", NEW)
def test_a_reader_gives_the_hand_sum(name):
    got = manifest.metric_reader(name)(Run(_doc()))
    assert got == pytest.approx(HAND[name], rel=1e-9)


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_in_a_program_without_latent_attention(name):
    """The parent's program (no ``latent_rows`` on its spans, no kernel of
    that name) and a run without a trace: ``None``, never 0, nothing
    raises."""
    doc = _doc()
    for e in doc["events"]:
        e[6].pop("latent_rows", None)
    assert manifest.metric_reader(name)(Run(doc)) is None
    assert manifest.metric_reader(name)(Run(_doc(), with_trace=False)) is None


def test_a_chunk_run_with_other_loops_gives_no_prefill_roofline():
    """The chunk program as it was before the prefill kernel: its walks over
    the context are top-level loops, and no event carries the kernel's
    name."""
    doc = _doc()
    for e in doc["trace"]["devices"]["0"]["ops"]:
        if e[0].startswith("latent_prefill_attention"):
            e[0] = "while.1" + e[0][-1]
    run = Run(doc)
    lo, hi = axk1_spans.paired(run, axk1_spans.CHUNK_PROGRAM,
                               axk1_spans.chunks(run))[0][0]
    assert axk1_spans.prefill_kernel_ns(run, lo, hi) is None
    assert manifest.metric_reader("latent_prefill_roofline")(run) is None


def test_a_chunk_run_short_of_a_kernel_a_layer_gives_no_prefill_roofline():
    """One event of the prefill kernel fewer than the toy has layers, in
    one of the two chunk runs: no guess from the others."""
    doc = _doc()
    ops = doc["trace"]["devices"]["0"]["ops"]
    last = max(e[1] for e in ops if e[0] == "latent_prefill_attention.3")
    doc["trace"]["devices"]["0"]["ops"] = [
        e for e in ops
        if not (e[0] == "latent_prefill_attention.3" and e[1] == last)]
    run = Run(doc)
    (first, _), (second, _) = axk1_spans.paired(
        run, axk1_spans.CHUNK_PROGRAM, axk1_spans.chunks(run))
    assert axk1_spans.prefill_kernel_ns(run, *first) == 18_000_000
    assert axk1_spans.prefill_kernel_ns(run, *second) is None
    assert manifest.metric_reader("latent_prefill_roofline")(run) is None


def test_runs_and_spans_pair_at_the_windows_edges():
    """A run that started before the first traced step's span is dropped,
    and so is a span whose run the trace no longer holds."""
    run = Run(_doc())
    spans = axk1_spans.decodes(run)
    assert [d["latent_rows"] for d in spans] == [15, 36]
    pairs = axk1_spans.paired(run, axk1_spans.DECODE_PROGRAM, spans[1:])
    assert [p[1]["latent_rows"] for p in pairs] == [36]
    assert pairs[0][0][0] == 1251 * 1_000_000
    pairs = axk1_spans.paired(run, axk1_spans.DECODE_PROGRAM,
                              spans + [dict(spans[-1], latent_rows=1)])
    assert [p[1]["latent_rows"] for p in pairs] == [15, 36]


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
    """The serving cells' rule (the most even offered tokens over sixths of
    the window), on this file's rate and lengths."""
    cvs = {order: _offered_cv(order) for order in range(1, 33)}
    assert min(cvs, key=cvs.get) == TRAFFIC["arrival_order_seed"]


def test_the_traffic_is_the_stated_one():
    t = TRAFFIC
    assert t["kind"] == "serve" and t["rate_from"] and t["seed_role"]
    assert t["prompt_tokens"] == {"median": 6144, "sigma": 0.7, "min": 2048,
                                  "max": 24576}
    assert t["answer_tokens"] == {"median": 768, "sigma": 0.5, "min": 256,
                                  "max": 2048}
    assert (t["max_total_tokens"], t["max_batch"], t["prefill_chunk_tokens"],
            t["kv_page_tokens"], t["queue_depth"], t["kv_cache_mb"]) \
        == (26624, 32, 2048, 64, 256, 3072)
    assert t["check_requests"] >= 4 and 3 <= t["trace_seconds"] <= 6
    reqs = traffic.requests(3, t, 51, CONFIG["vocab_size"])
    assert len(reqs) == traffic.request_count(t, 51)
    assert all(2048 <= len(r["prompt"]) <= 24576
               and 256 <= r["max_new"] <= 2048
               and len(r["prompt"]) + r["max_new"] <= 26624 for r in reqs)
    assert all(0 <= int(r["prompt"].max()) < 20480 for r in reqs)
    assert t["max_total_tokens"] % t["prefill_chunk_tokens"] == 0
    assert t["prefill_chunk_tokens"] % t["kv_page_tokens"] == 0


# -- perf/run.py --tiny --------------------------------------------------------------

def test_tiny_run_of_the_cell_says_correct(capsys):
    seed = 2**31 + 38
    out, last, err = tiny_run(capsys, CELL, seed=seed, seconds=2.0)
    assert RESULT_KEYS <= set(last) and list(last)[-1] == "checks"
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] == traffic.request_count(
        {**TRAFFIC, **TRAFFIC["tiny"]}, 2.0)
    assert last["metrics"] == {} and last["device"]["platform"] == "cpu"
    assert set(last["dry_run"]) == {"setup_s", "ttft_p50_ms", "itl_p95_ms"}
    assert "check served_logit_gap_max:" in err


def test_tiny_traced_run_reports_the_counter_and_span_metrics(capsys):
    out, last, _ = tiny_run(capsys, CELL, seed=13, seconds=2.0, trace=1)
    named = {m["name"] for m in manifest.metrics_of(MANIFEST, CELL, "per_layer")}
    assert set(last["dry_run"]) <= named and last["metrics"] == {}
    for name in ("decode_batch_occupancy", "serve_kv_pool_peak_share",
                 "serve_ttft_queue_wait_ms", "serve_ttft_p90_ms",
                 "prefill_chunk_ms", "prefill_chunk_p90_ms",
                 "serve_ttft_prefill_ms"):
        assert last["dry_run"][name] > 0, name
    # no device trace on the CPU: no device metric, no share of a peak
    assert not set(NEW) & set(last["dry_run"])
    assert last["correct"] is True


@pytest.mark.parametrize("fault", ["experts_top7", "no_shared_expert",
                                   "no_k_pe"])
def test_a_planted_fault_comes_out_not_correct(capsys, fault):
    from perf import axk1_faults

    try:
        out, last, err = tiny_run(capsys, CELL, seed=5, seconds=2.0,
                                  prepare=axk1_faults.planting(fault))
    finally:
        axk1_faults.restore()
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


# -- the adapter ----------------------------------------------------------------------

def _adapter():
    return manifest.adapter(CONFIG)


def test_a_program_without_latent_attention_fails_at_once(monkeypatch):
    """What the parent commit does with this cell: ``environment`` raises
    ``ImportError`` before any weight is made, so the run exits at once."""
    from mlsl_tpu.models import transformer as tfm

    monkeypatch.delattr(tfm, "latent_scale")
    with pytest.raises(ImportError, match="no latent attention"):
        _adapter().environment(ring_events=1 << 19)


def test_the_ring_is_sized_without_the_processs_environment(monkeypatch):
    """The serving runner's ring size reaches the program's ring, and
    ``MLSL_TRACE_CAPACITY`` is not left in a test worker's environment."""
    from mlsl_tpu import obs

    monkeypatch.delenv("MLSL_TRACE_CAPACITY", raising=False)
    was = obs.get_tracer()
    try:
        env = _adapter().environment(ring_events=1 << 19)
        assert "MLSL_TRACE_CAPACITY" not in os.environ
        if was is not None:
            assert obs.get_tracer().capacity >= 1 << 19
        env.finalize()
    finally:
        if was is not None:
            obs.disable()
            obs.enable(was.capacity)


def test_the_program_config_is_the_files():
    cfg = _adapter().program_config(CONFIG, TRAFFIC)
    assert (cfg.d_model, cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim) \
        == (7168, 64, 1536, 512, 128, 64, 128)
    assert (cfg.n_blocks, cfg.n_experts, cfg.n_routed, cfg.held_from,
            cfg.moe_top_k, cfg.n_groups, cfg.route_groups) \
        == (7, 12, 192, 0, 8, 8, 4)
    assert (cfg.first_k_dense, cfg.dense_width, cfg.shared_experts,
            cfg.expert_width, cfg.routed_scale) == (1, 18432, 1, 2048, 2.5)
    assert cfg.yarn == (32.0, 4096, 32.0, 1.0, 1.0, 1.0)
    assert cfg.latent_row == 640 and cfg.seq_len == 26624
    assert copy.copy(cfg) == cfg
