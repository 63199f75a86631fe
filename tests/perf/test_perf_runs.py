"""perf/run.py end to end on the CPU at a tiny size: the last line's keys, no
device metric named off the chip, the step record, and the refusals."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

from perf_helpers import RESULT_KEYS, ROOT, tiny_run

RUN = [sys.executable, str(ROOT / "perf" / "run.py")]


def _cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return env


@pytest.mark.parametrize("workload,series", [
    ("gpt2-medium-train", "step"), ("gpt2-medium-serve-chat", "engine_step"),
    ("resnet50-1chip", "step")])
def test_tiny_run_prints_the_contracts_last_line(capsys, workload, series):
    out, last, err = tiny_run(capsys, workload, seed=2**31 + 17, seconds=1.5)
    assert RESULT_KEYS <= set(last)
    assert list(last)[-1] == "checks" and last["checks"]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    # a CPU run names no device metric: the numbers stand apart
    assert last["metrics"] == {} and last["dry_run"]["setup_s"] > 0
    assert last["device"]["platform"] == "cpu"
    assert set(last["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for name, (value, limit) in last["checks"].items():
        assert f"check {name}:" in err and value <= limit
    assert err.strip().splitlines()[-1] == "correct: True"
    records = sorted((ROOT / "perf" / "out").glob(
        f"{workload}.seed{2**31 + 17}.trace0.*.json"))
    record = json.loads(records[-1].read_text())
    assert record["series"][series] and record["meta"]["correct"] is True


def test_every_serving_step_record_keeps_what_the_ring_saw(capsys):
    """Untraced too: a run that reads slow can be opened afterwards."""
    seed = 2**31 + 41
    tiny_run(capsys, "gpt2-medium-serve-chat", seed=seed, seconds=1.5)
    records = sorted((ROOT / "perf" / "out").glob(
        f"gpt2-medium-serve-chat.seed{seed}.trace0.*.json"),
        key=lambda p: p.stat().st_mtime)
    record = json.loads(records[-1].read_text())
    ring = record["meta"]["ring_summary"]
    in_window = [s for s in record["series"]["engine_step"] if s[0] < 1.5]
    assert ring["steps"] == len(in_window) and ring["ring_full"] is False
    assert ring["spans"]["serve.step"]["n"] == ring["steps"]
    assert sum(v["n"] for v in ring["steps_by_admissions"].values()) \
        == ring["steps"]
    assert sum(int(k) * v["n"] for k, v in ring["steps_by_admissions"].items()) \
        == ring["spans"]["serve.admit"]["n"] > 0
    assert 0 <= ring["gap_share_admitting"] <= 1 and len(
        ring["halves_median_ms"]) == 2


def test_the_serving_pool_is_whole_pages_within_what_its_tables_address():
    """``kv_cache_mb`` is a whole number of pages, each the float32 keys and
    values of ``kv_page_tokens`` tokens in every layer, and no more of them
    than ``max_batch`` sequences of ``max_total_tokens`` could ever hold: what
    the traffic fills of it is measured (``serve_kv_pool_peak_share``), and
    ``kv_cache_from`` says which reading sized it."""
    from perf.lib import manifest

    man = manifest.load()
    cell = manifest.cell(man, "gpt2-medium-serve-chat")
    config = manifest.config_file(man, cell["config"])
    traffic = manifest.traffic_file(cell["traffic"])
    page_bytes = (config["n_layer"] * 2 * traffic["kv_page_tokens"]
                  * config["n_head"] * config["head_dim"] * 4)
    assert page_bytes == 3 << 20
    assert traffic["max_total_tokens"] == config["n_positions"]
    addressable = (traffic["max_batch"] * traffic["max_total_tokens"]
                   // traffic["kv_page_tokens"])
    assert addressable == 2048
    pages, rest = divmod(traffic["kv_cache_mb"] << 20, page_bytes)
    assert rest == 0 and pages == 1024 <= addressable
    # one request of the longest kind the traffic can send always fits
    assert pages >= traffic["max_total_tokens"] // traffic["kv_page_tokens"]
    assert "1,024 pages" in traffic["kv_cache_from"]
    assert traffic["tiny"]["kv_cache_mb"] == 8      # the dry run's, untouched


def test_tiny_traced_run_reports_counters_and_no_device_number(capsys):
    out, last, _ = tiny_run(capsys, "gpt2-medium-serve-chat", seed=5,
                            seconds=1.5, trace=1)
    assert last["metrics"] == {} and "busy_s" not in last["device"]
    assert 0 < last["dry_run"]["decode_batch_occupancy"] <= 100
    assert "serve_device_idle_share" not in last["dry_run"]
    assert "decode_step_roofline" not in last["dry_run"]


def test_the_same_seed_gives_the_same_inputs(capsys):
    a, _, _ = tiny_run(capsys, "gpt2-medium-train", seed=123, seconds=0.5)
    b, _, _ = tiny_run(capsys, "gpt2-medium-train", seed=123, seconds=0.5)
    assert a["checks"].keys() == b["checks"].keys()
    rec = sorted((ROOT / "perf" / "out").glob(
        "gpt2-medium-train.seed123.trace0.*.json"))
    la, lb = (json.loads(p.read_text())["meta"]["reference_losses"]
              for p in rec[-2:])
    assert la == lb


def test_without_a_chip_the_command_fails_and_prints_no_result():
    p = subprocess.run(RUN + ["--workload", "gpt2-medium-train", "--seed", "1",
                              "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=_cpu_env(), capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_an_unknown_workload_fails_and_prints_no_result():
    p = subprocess.run(RUN + ["--workload", "no-such-cell", "--tiny"],
                       cwd=ROOT, env=_cpu_env(), capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_in_a_directory_with_the_benchmark_alone_it_fails(tmp_path):
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "perf", tmp_path / "perf",
                    ignore=shutil.ignore_patterns("out", ".jax_cache",
                                                  "__pycache__"))
    env = _cpu_env()
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "perf/run.py", "--workload", "gpt2-medium-train",
         "--seed", "1", "--seconds", "1", "--trace", "0", "--tiny"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "mlsl_tpu" in p.stderr


def test_the_four_chip_cell_waits_for_its_entries_alone(tmp_path):
    """resnet50-dp4 was not proved on four chips, so BENCHMARK.json does not
    list it; its traffic, limits and readers are here. A later PR adds the
    entries and edits no file: shown in a copy, on four virtual devices."""
    import shutil

    shutil.copytree(ROOT / "perf", tmp_path / "perf",
                    ignore=shutil.ignore_patterns("out", ".jax_cache",
                                                  "__pycache__"))
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    man["workloads"].append({
        "name": "resnet50-dp4", "config": "resnet50",
        "traffic": "train-b1024-dp4-hbmfeed", "chips": 4,
        "why": "256 images a chip, gradients allreduced over ICI"})
    for m in man["end_to_end"] + man["per_layer"]:
        if "resnet50-1chip" in m.get("workloads", []):
            m["workloads"].append("resnet50-dp4")
    for name, unit in (("comm_exposed_ms_per_step", "ms"),
                       ("comm_calls_per_step", "calls")):
        man["per_layer"].append({
            "name": name, "unit": unit, "better": "lower",
            "source": "device_trace", "layer": "communication (comm/)",
            "moves": "train_rate", "workloads": ["resnet50-dp4"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    sys.path.insert(0, str(ROOT))
    from perf.lib import manifest

    assert manifest.validate(man, tmp_path) == []
    env = _cpu_env()
    env["PYTHONPATH"] = str(ROOT)
    p = subprocess.run(
        [sys.executable, "perf/run.py", "--workload", "resnet50-dp4",
         "--seed", "4", "--seconds", "1", "--trace", "0", "--tiny"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["device"]["count"] == 4
    assert last["dry_run"]["train_rate"] > 0


@pytest.mark.parametrize("family", ["transformer", "keye"])
def test_a_serving_adapter_sizes_the_ring_without_the_processs_environment(
        monkeypatch, family):
    """As the A.X-K1 adapter's test: the serving runner's ring size reaches
    the program's ring, and ``MLSL_TRACE_CAPACITY`` is not left in a test
    worker's environment, where a later test of the ring's default size
    would find it."""
    from mlsl_tpu import obs

    from perf.lib import manifest

    adapter = manifest.load_module(ROOT / "perf" / "adapters" / f"{family}.py")
    monkeypatch.delenv("MLSL_TRACE_CAPACITY", raising=False)
    was = obs.get_tracer()
    try:
        env = adapter.environment(ring_events=1 << 19)
        assert "MLSL_TRACE_CAPACITY" not in os.environ
        if was is not None:
            assert obs.get_tracer().capacity >= 1 << 19
        env.finalize()
    finally:
        if was is not None:
            obs.disable()
            obs.enable(was.capacity)
