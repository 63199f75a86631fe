"""The benchmark's yardstick on the CPU: the manifest's rules, files found by
name, a cell added by files alone, the traffic generator, the counts and the
trace reduction. No chip, no topology described, nothing at import."""

import hashlib
import json
import pathlib
import shutil
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from perf.lib import (compare, counts, manifest, peaks, record, traffic,  # noqa: E402
                      trace)

MANIFEST = manifest.load()
CELLS = [w["name"] for w in MANIFEST["workloads"]]
HERE = pathlib.Path(__file__).resolve().parent


def test_manifest_holds_the_contract():
    assert manifest.validate(MANIFEST) == []
    assert MANIFEST["paths"] == ["perf", "tests/perf"]
    assert MANIFEST["command"][-1] == "perf/run.py"
    assert (ROOT / "BENCHMARK.json").stat().st_size < 64 * 1024


def test_run_seconds_fits_a_full_check_of_24_cells():
    rs = MANIFEST["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("name", [
    m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]])
def test_metric_names_units_and_readers(name):
    m = next(x for x in MANIFEST["end_to_end"] + MANIFEST["per_layer"]
             if x["name"] == name)
    assert manifest.NAME.match(name) and manifest.UNIT.match(m["unit"])
    assert m["unit"].isascii() and " " not in m["unit"]
    if "layer" in m:
        assert callable(manifest.metric_reader(name))
        assert m["moves"] in {e["name"] for e in MANIFEST["end_to_end"]}
        if name.endswith("_roofline"):
            assert m["unit"] == "%"


@pytest.mark.parametrize("cell", CELLS)
def test_every_file_of_a_cell_is_found_by_name(cell):
    w = manifest.cell(MANIFEST, cell)
    config = manifest.config_file(MANIFEST, w["config"])
    entry = manifest.config_entry(MANIFEST, w["config"])
    assert config["source"] == entry["source"]
    assert set(entry["reduced"]) <= set(config)
    t = manifest.traffic_file(w["traffic"])
    assert t["kind"] in ("train", "serve") and t["seed_role"]
    limits = manifest.read_json(manifest.PERF / "limits" / f"{cell}.json")
    assert limits["limits"] and all(v > 0 for v in limits["limits"].values())
    assert (manifest.PERF / "configs" / f"{config['reference']}.py").exists()
    assert (manifest.PERF / "adapters" / f"{config['adapter']}.py").exists()
    assert manifest.metrics_of(MANIFEST, cell, "per_layer")
    assert len(manifest.metrics_of(MANIFEST, cell, "end_to_end")) >= 2


def test_the_reference_imports_nothing_of_the_program():
    for path in (manifest.PERF / "configs").glob("*.py"):
        assert "mlsl_tpu" not in path.read_text(), path
    for path in (manifest.PERF / "lib").glob("*.py"):
        assert "import mlsl_tpu" not in path.read_text(), path
        assert "from mlsl_tpu" not in path.read_text(), path


def _digest(root):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts and "out" not in p.parts}


def test_a_cell_a_configuration_and_a_metric_are_added_by_files_alone(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "perf", tmp_path / "perf", ignore=shutil.ignore_patterns(
        "out", ".jax_cache", "__pycache__"))
    before = _digest(tmp_path / "perf")
    perf = tmp_path / "perf"
    toy = json.loads((perf / "configs" / "gpt2-medium.json").read_text())
    toy.update(n_layer=1, source="https://example.org/toy")
    (perf / "configs" / "toy.json").write_text(json.dumps(toy))
    mix = json.loads((perf / "traffic" / "train-s1024-b8-adamw.json").read_text())
    mix["batch"] = 2
    (perf / "traffic" / "toy-b2.json").write_text(json.dumps(mix))
    shutil.copy(perf / "limits" / "gpt2-medium-train.json",
                perf / "limits" / "toy-train.json")
    (perf / "metrics" / "toy_steps.py").write_text(
        "def read(run):\n    return run.window.get('steps')\n")
    man = json.loads((tmp_path / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "toy", "source": "https://example.org/toy",
                           "file": "perf/configs/toy.json",
                           "reduced": ["n_layer"], "why": "a toy"})
    man["workloads"].append({"name": "toy-train", "config": "toy",
                             "traffic": "toy-b2", "chips": 1, "why": "a toy"})
    for m in man["end_to_end"]:
        if m["name"] == "train_rate":
            m["workloads"].append("toy-train")
    man["per_layer"].append({
        "name": "toy_steps", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "whole step",
        "moves": "train_rate", "workloads": ["toy-train"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    loaded = manifest.load(tmp_path)
    assert manifest.validate(loaded, tmp_path) == []
    assert manifest.config_file(loaded, "toy", tmp_path)["n_layer"] == 1
    assert manifest.traffic_file("toy-b2", perf)["batch"] == 2
    reader = manifest.metric_reader("toy_steps", perf)

    class Run:
        window = {"steps": 7}
    assert reader(Run) == 7
    assert [m["name"] for m in manifest.metrics_of(loaded, "toy-train", "per_layer")] \
        == ["toy_steps"]
    after = _digest(perf)
    assert {k: v for k, v in after.items() if k in before} == before


def test_an_entry_appended_to_per_layer_breaks_no_test_of_the_entries(tmp_path):
    """A later PR appends its per-layer metric at the end of ``per_layer``
    (one put in the middle reads as a change to the benchmark): in a copy
    with a toy entry appended, every test here that reads the entries, the
    cells they list and their readers still passes. The tests that drive
    the program are left out: they read no entry by its place."""
    import os
    import subprocess

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for sub in ("perf", "tests/perf"):
        shutil.copytree(ROOT / sub, tmp_path / sub, ignore=shutil.ignore_patterns(
            "out", ".jax_cache", "__pycache__"))
    (tmp_path / "perf" / "metrics" / "toy_appended.py").write_text(
        "def read(run):\n    return None\n")
    man = json.loads((tmp_path / "BENCHMARK.json").read_text())
    man["per_layer"].append({
        "name": "toy_appended", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "whole step", "moves": "itl_p95_ms",
        "workloads": ["ax-k1-ep16-pp10-serve-docreason"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man, indent=1))
    tests = tmp_path / "tests" / "perf"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [os.environ.get("PYTHONPATH", ""), str(ROOT)]).strip(os.pathsep))
    p = subprocess.run(
        [sys.executable, "-m", "pytest", "-v", "-p", "no:cacheprovider",
         "-p", "no:randomly", "-o", "addopts=",
         "-k", "not tiny and not planted and not float8 and not breaks_no_test",
         *(str(tests / f) for f in (
             "test_perf_yardstick.py", "test_perf_prefill_chunk_p90.py",
             "test_perf_keye.py", "test_perf_axk1.py",
             "test_perf_program_spans.py"))],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-4000:] + p.stderr[-2000:]
    assert "PASSED" in p.stdout and "FAILED" not in p.stdout
    # the copy's manifest was the one read: the toy's own case ran
    assert "test_metric_names_units_and_readers[toy_appended]" in p.stdout
    assert "test_the_p90_entry_matches_the_median_entry" in p.stdout


def test_validate_names_the_faults():
    bad = json.loads(json.dumps(MANIFEST))
    bad["per_layer"][0]["unit"] = "ms per step"
    bad["workloads"][0]["chips"] = 2
    bad["end_to_end"][1]["bound"] = 0.5
    faults = manifest.validate(bad)
    assert any("unit" in f for f in faults)
    assert any("chips" in f for f in faults)
    assert any("bound" in f for f in faults)


# -- traffic -----------------------------------------------------------------

SERVE = manifest.traffic_file("chat-open-loop")


def _offered_cv(order, bins=6, seconds=51):
    """The larger coefficient of variation, over ``bins`` equal parts of the
    window, of the prompt tokens and of the answer tokens that come due."""
    reqs = traffic.requests(1, {**SERVE, "arrival_order_seed": order},
                            seconds, 50257)
    prompt, answer = np.zeros(bins), np.zeros(bins)
    for r in reqs:
        b = min(bins - 1, int(r["due"] / seconds * bins))
        prompt[b] += len(r["prompt"])
        answer[b] += r["max_new"]
    return max(prompt.std() / prompt.mean(), answer.std() / answer.mean())


def test_the_arrival_order_is_the_most_even_of_its_candidates():
    """``arrival_order_from``'s rule: the cell stands at the same share of
    the knee all through its window, so a slow stretch of the machine weighs
    the same wherever it falls and the halves of a step record compare."""
    chosen = SERVE["arrival_order_seed"]
    cvs = {order: _offered_cv(order) for order in range(1, 33)}
    assert min(cvs, key=cvs.get) == chosen == 6
    assert cvs[chosen] < 0.05 < 0.12 < _offered_cv(2024)
    halves = _offered_cv(chosen, bins=2)
    assert halves < 0.03


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_every_seed_offers_the_same_requests_with_other_tokens(seed):
    a = traffic.requests(1, SERVE, 30, 50257)
    b = traffic.requests(seed, SERVE, 30, 50257)
    assert len(a) == len(b) == traffic.request_count(SERVE, 30)

    def gaps(reqs):
        due = [0.0] + [r["due"] for r in reqs]
        return sorted(round(y - x, 9) for x, y in zip(due, due[1:]))
    assert sorted(len(r["prompt"]) for r in a) == sorted(len(r["prompt"]) for r in b)
    assert sorted(r["max_new"] for r in a) == sorted(r["max_new"] for r in b)
    assert gaps(a) == gaps(b)
    # the arrivals stand at the same moments for every seed
    assert [r["due"] for r in a] == [r["due"] for r in b] and b[-1]["due"] <= 30
    due = [0.0] + [r["due"] for r in a]
    in_order = [y - x for x, y in zip(due, due[1:])]
    assert in_order != sorted(in_order)
    # so do the lengths, in an order that is not the sorted one and is not
    # the same for prompts and answers; the seed draws the token ids
    assert [len(r["prompt"]) for r in a] == [len(r["prompt"]) for r in b]
    assert [r["max_new"] for r in a] == [r["max_new"] for r in b]
    lengths = [len(r["prompt"]) for r in a]
    assert lengths != sorted(lengths)
    rank = sorted(range(len(a)), key=lambda i: lengths[i])
    assert [a[i]["max_new"] for i in rank] != sorted(r["max_new"] for r in a)
    if seed != 1:
        assert any((x["prompt"] != y["prompt"]).any() for x, y in zip(a, b))
    p = SERVE["prompt_tokens"]
    assert all(p["min"] <= len(r["prompt"]) <= p["max"] for r in b)
    assert all(len(r["prompt"]) + r["max_new"] <= SERVE["max_total_tokens"]
               for r in b)
    again = traffic.requests(seed, SERVE, 30, 50257)
    assert all((x["prompt"] == y["prompt"]).all() for x, y in zip(b, again))


def test_token_batches_repeat_for_a_seed_and_rows_all_differ():
    t1, l1 = traffic.token_batch(2**31 + 5, 3, 8, 64, 50257)
    t2, _ = traffic.token_batch(2**31 + 5, 3, 8, 64, 50257)
    t3, _ = traffic.token_batch(2**31 + 5, 4, 8, 64, 50257)
    assert (t1 == t2).all() and not (t1 == t3).all()
    assert (t1[:, 1:] == l1[:, :-1]).all()
    assert len({row.tobytes() for row in t1}) == 8
    assert t1.min() >= 0 and t1.max() < 50257


def test_image_batches_are_uint8_with_a_pattern_a_class():
    t = {"batch": 8, "distinct_batches": 3, "pattern_amplitude": 40.0,
         "noise_amplitude": 30.0}
    a = traffic.image_batches(5, t, 32, 10)
    b = traffic.image_batches(5, t, 32, 10)
    assert len(a) == 3 and a[0][0].shape == (8, 32, 32, 3)
    assert a[0][0].dtype.name == "uint8" and a[0][1].dtype.name == "int32"
    assert all((x[0] == y[0]).all() for x, y in zip(a, b))
    assert not (a[0][0] == a[1][0]).all()


# -- counts: hand sums at one small shape ---------------------------------

SMALL = {"n_embd": 8, "n_head": 2, "head_dim": 4, "n_layer": 3, "mlp_ratio": 4,
         "vocab_size": 11, "n_positions": 16}


def test_gpt2_counts_match_hand_sums():
    # a block: q, k, v, o projections 4 * 2*8*8 = 512; MLP 2 * 2*8*32 = 1024
    flat = 3 * (512 + 1024) + 2 * 8 * 11
    assert counts.gpt2_forward_ops_per_token(SMALL, 0) == flat
    # training at S = 16: causal attention 2*S*ad = 256 a block
    assert counts.gpt2_train_ops_per_token(SMALL, 16) == 3.0 * (flat + 3 * 256)
    # the token at position 5 sees 6 keys: 4 * 6 * 8 = 192 a block
    assert counts.gpt2_token_ops_at(SMALL, 5) == flat + 3 * 192
    assert counts.gpt2_sequence_ops(SMALL, 2, 9) == sum(
        counts.gpt2_token_ops_at(SMALL, p) for p in range(2, 9))
    # parameters: tok 88 + pos 128 + 3 blocks + final LN 16 + head 88
    block = 4 * 8 + 3 * 64 + 64 + 8 * 32 + 32 + 32 * 8 + 8
    assert counts.gpt2_param_count(SMALL) == 88 + 128 + 3 * block + 16 + 88


def test_attention_and_decode_counts_match_hand_sums():
    ops, moved = counts.causal_attention_forward(2, 3, 4, 8)
    assert ops == 2 * 3 * 10 * 8 * 4 and moved == 2 * 3 * 4 * 8 * 2 * 4
    ops_b, moved_b = counts.causal_attention_backward(2, 3, 4, 8)
    assert ops_b == 2 * 3 * 10 * 8 * 10 and moved_b == 2 * moved
    block = 4 * 8 + 3 * 64 + 64 + 8 * 32 + 32 + 32 * 8 + 8
    weights = (3 * block + 16 + 88) * 4
    assert counts.gpt2_decode_step_bytes(SMALL, [5, 7]) \
        == weights + 12 * 3 * 2 * 8 * 4
    v5e = peaks.of("TPU v5 lite")
    assert counts.roofline_seconds(197e12, 1, v5e) == (1.0, "compute")
    assert counts.roofline_seconds(1, 819e9, v5e) == (1.0, "bandwidth")


def test_resnet50_operations_an_image():
    macs = counts.resnet50_forward_ops_per_image(224, 1000) / 2
    assert 4.08e9 < macs < 4.10e9          # the v1.5 placement's 4.1 G
    # the stem alone: 112 * 112 * 7 * 7 * 3 * 64
    assert counts.resnet50_forward_ops_per_image(224, 1000) > 2 * 118013952
    assert counts.resnet50_train_ops_per_image() \
        == 3 * counts.resnet50_forward_ops_per_image()


def test_an_unknown_device_kind_has_no_peak():
    with pytest.raises(KeyError):
        peaks.of("TPU v9 imaginary")


# -- the reduction from a trace to numbers, on a small recorded trace -------

@pytest.fixture()
def small():
    return trace.Trace(json.loads((HERE / "small_trace.json").read_text()))


def test_window_busy_and_idle(small):
    assert small.window_s == pytest.approx(10000e-9)
    # device 0: [1000,7000) + [8000,9000) + [10500,11000) = 7500
    assert small.busy_s("0") == pytest.approx(7500e-9)
    assert small.busy_s("1") == pytest.approx(7000e-9)
    assert small.mean_busy_s() == pytest.approx(7250e-9)
    assert small.worst_idle_share() == pytest.approx(0.30)


def test_operations_programs_and_collectives(small):
    assert small.op_seconds(r"^jvp_jit__flash_fwd__") == (pytest.approx(1000e-9), 1)
    assert small.op_seconds(r"^fusion") == (pytest.approx(5000e-9), 3)
    assert small.module_seconds(r"decode_body") == (pytest.approx(1000e-9), 1)
    assert small.module_seconds(r"jit_step")[1] == 2
    assert small.top_ops(2) == [["fusion", pytest.approx(5000e-9)],
                                ["all-reduce", pytest.approx(2000e-9)]]
    # device 0: all-reduce [4000,6000) is covered by fusion.2 from 5000, and
    # the asynchronous start [8200,8700) by the copy: 1000 exposed
    assert small.exposed_collective_s("0") == pytest.approx(1000e-9)
    assert small.exposed_collective_s("1") == pytest.approx(6000e-9)
    assert len(small.collectives("0")) == 2


def test_idle_gaps_go_to_the_host_span_that_covers_them(small):
    gaps = dict(small.idle_gaps())
    assert gaps["perf.feed.next"] == pytest.approx(1000e-9)
    assert gaps["perf.loss.read_back"] == pytest.approx(1500e-9)


def test_interval_arithmetic():
    assert trace.union([[5, 7], [1, 3], [2, 4]]) == [[1, 4], [5, 7]]
    assert trace.subtract([[0, 10]], [[2, 3], [5, 20]]) == [[0, 2], [3, 5]]
    assert trace.op_name("%fusion.3 = f32[8]{0} fusion(f32[8] %p)") == "fusion.3"
    assert trace.op_family("fusion.3") == "fusion"


# -- the comparison -----------------------------------------------------------

def test_worst_leaf_gap_measures_against_the_median_leaf():
    ref = [1.0, 2.0, 1e-9]
    prog = {"a": 1.1, "b": 2.0, "c": 2e-9}
    gap, at = compare.worst_leaf_gap(prog, ref, ["a", "b", "c"])
    assert at == "a" and gap == pytest.approx(0.1)
    assert compare.still_leaves(ref, ["a", "b", "c"]) == {"c"}
    gap, at = compare.worst_leaf_gap({**prog, "b": float("nan")}, ref,
                                     ["a", "b", "c"])
    assert at == "b"


def test_the_limits_file_names_the_numbers_that_are_compared():
    paths = ["a", "b", "c"]
    ref = {"losses": [2.0, 2.0, 2.0], "paths": paths,
           "grad_norms": [1.0, 2.0, 4.0], "delta_norms": [1.0, 1.0, 1.0]}
    prog = {"losses": [2.0, 2.2, 2.0],
            "grad": {"a": 1.5, "b": 2.0, "c": 4.4},
            "delta": {"a": 1.0, "b": 1.0, "c": 0.0}}
    checks, notes = compare.training(
        prog, ref, {"delta3_median_leaf": 0.5, "loss_step2_rel": 0.05})
    assert [c["name"] for c in checks] == ["delta3_median_leaf",
                                           "loss_step2_rel"]
    assert checks[0]["value"] == 0.0 and checks[1]["value"] == pytest.approx(0.1)
    # every number is worked out and kept, compared or not
    n = notes["numbers"]
    assert n["grad1_worst_leaf"] == pytest.approx(0.25)      # leaf a: 0.5 / 2
    assert n["grad1_median_leaf"] == pytest.approx(0.1)      # leaf c: 0.4 / 4
    assert n["delta3_worst_leaf"] == pytest.approx(1.0)      # leaf c unmoved
    assert notes["grad1_top"][0] == [pytest.approx(0.25), "a"]
    assert not compare.verdict(checks)


def test_verdict_needs_every_number_under_its_limit():
    ok = [{"name": "x", "value": 0.1, "limit": 0.2}]
    assert compare.verdict(ok)
    assert not compare.verdict(ok, failed=1)
    assert not compare.verdict([])
    assert not compare.verdict(ok + [{"name": "y", "value": float("nan"),
                                      "limit": 1.0}])
    assert not compare.verdict([{"name": "x", "value": 0.3, "limit": 0.2}])
    assert compare.as_pairs(ok) == {"x": [0.1, 0.2]}


# -- the step record ----------------------------------------------------------

def test_a_step_record_is_summarised_for_the_reader_of_a_slow_run():
    steps = [[0.100 * i, 0.100 * i + 0.001, 0.100 * i + 0.003] for i in range(8)]
    for i in (1, 3, 5, 7):                  # the loss read back every 2nd step
        steps[i].append(0.100 * i + 0.090)
    steps[5][3] += 0.040                    # one stall of 40 ms
    rec = {"meta": {"workload": "w", "seed": 1, "setup_s": 2.0, "correct": True,
                    "steps": 8, "window_s": 0.84},
           "series": {"step": steps, "warmup_step_s": [[0.12], [0.10]],
                      "setup_phase": [["import", 1.5]]}}
    out = record.summarize(rec)
    assert out["step_ms"] == pytest.approx(105.0)
    assert out["feed_wait_ms"]["median"] == pytest.approx(1.0)
    assert out["dispatch_ms"]["max"] == pytest.approx(2.0)
    back = out["read_back_interval_ms_per_step"]
    assert back["min"] == pytest.approx(80.0) and back["max"] == pytest.approx(120.0)
    assert out["warmup_ms"] == [120.0, 100.0] and out["setup_phases"] == {"import": 1.5}
