"""The scope reader (perf/lib/scopes.py) and its seven readers on hand-made
traces: paths read from a profiler's file made by hand
(``xspace_fixture``), a fusion across a scope's edge booked to its root,
unscoped operations, a collective's scope, nested events booked once, an
executable without scopes, and the recorded fixtures, which read what they
read before."""

import json
import pathlib
import re
import sys
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
HERE = pathlib.Path(__file__).resolve().parent
for p in (ROOT, HERE):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import xspace_fixture as xf  # noqa: E402

from perf.lib import manifest, scopes, trace  # noqa: E402

OP_NAME = re.compile(r'op_name="([^"]*)"')
SHARES = ("step_backward_share", "step_update_share", "head_share_of_step",
          "norm_share_of_step", "prefill_expert_share")
COVERAGE = ("device_scoped_share", "serve_device_scoped_share")


def read(name, run):
    return manifest.metric_reader(name)(run)


def run_of(ops, paths, modules=(), window=(0, 1000)):
    """A run with a one-device trace: ``ops`` [[name, start, dur]], their
    paths beside them (None: no ``scopes`` key at all)."""
    dev = {"ops": [list(o) for o in ops], "modules": [list(m) for m in modules],
           "async": []}
    if paths is not None:
        dev["scopes"] = list(paths)
    data = {"devices": {"0": dev},
            "host": [["perf.window", window[0], window[1] - window[0]]]}
    return types.SimpleNamespace(trace=trace.Trace(data))


@pytest.mark.parametrize("style", ["str", "ref"])
def test_paths_are_read_from_the_event_metadata(style):
    raw = xf.xspace(
        xf.plane("/device:TPU:0", [
            ("fusion.3 = f32[8]{0} fusion(p)", 10, 5, "jit(s)/jvp(mlsl_head)/mul"),
            ("copy.1 = f32[8]{0} copy(q)", 20, 2, None),
        ], style),
        xf.plane("/host:CPU", [("python", 0, 1, "not a device")]))
    assert scopes.metadata_paths(raw) == {
        "0": {"fusion.3 = f32[8]{0} fusion(p)": "jit(s)/jvp(mlsl_head)/mul"}}


@pytest.mark.parametrize("style", ["str", "event"])
def test_the_paths_line_up_with_from_xplanes_operations(tmp_path, style):
    line = "fusion.3 = f32[8]{0} fusion(p)"
    raw = xf.xspace(xf.plane("/device:TPU:0", [
        ("copy.1 = f32[8]{0} copy(q)", 30, 2, "jit(s)/mlsl_update/copy"),
        (line, 10, 5, "jit(s)/transpose(jvp(mlsl_attn))/mul"),
        ("while.2 = (s32[]) while(t)", 12, 1, None),
    ], style))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(raw)
    data = trace.from_xplane(str(path))
    ops = data["devices"]["0"]["ops"]
    assert [o[0] for o in ops] == ["fusion.3", "while.2", "copy.1"]
    assert scopes.attach(data, scopes.op_paths(str(path))) == ["0"]
    assert data["devices"]["0"]["scopes"] == [
        "jit(s)/transpose(jvp(mlsl_attn))/mul", None, "jit(s)/mlsl_update/copy"]
    # paths that do not list the same operations are not attached
    assert scopes.attach(data, {"0": [["copy.1", None]]}) == []


def test_a_fusion_across_a_scope_edge_carries_its_roots_path():
    """XLA gives a fusion its root instruction's metadata: a tanh under
    mlsl_mlp fused into a sum under mlsl_head is the head's."""
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("mlsl_mlp"):
            h = jnp.tanh(x)
        with jax.named_scope("mlsl_head"):
            return jnp.sum(h * h, axis=-1)

    text = jax.jit(f).lower(jnp.ones((8, 128))).compile().as_text()
    bodies = dict(re.findall(r"(?ms)^%(\S+) \(.*?\n(.*?)^\}", text))
    crossing = []
    for line in text.splitlines():
        m = re.search(r" fusion\(.*calls=%([\w.-]+)", line)
        if m and "mlsl_mlp" in bodies[m.group(1)]:
            root = next(ln for ln in bodies[m.group(1)].splitlines() if "ROOT" in ln)
            crossing.append((line, root))
    assert crossing
    for line, root in crossing:
        assert OP_NAME.search(line).group(1) \
            == OP_NAME.search(root).group(1) == "jit(f)/mlsl_head/mul"
    path = OP_NAME.search(crossing[0][0]).group(1)
    run = run_of([["fusion.1", 0, 40], ["copy.2", 50, 10]],
                 [path, "jit(f)/mlsl_mlp/copy"])
    parts = scopes.by_scope(run.trace)
    assert parts[("forward", "head")]["ns"] == 40
    assert parts[("forward", "head")]["families"] == {"fusion": 40}
    assert read("head_share_of_step", run) == pytest.approx(80.0)


def test_booking_rules_unscoped_comm_phases_and_nesting():
    ops = [
        ["while.1", 0, 100],          # a loop whose body runs inside it
        ["fusion.2", 10, 30],
        ["fusion.3", 50, 20],
        ["all-reduce.4", 100, 50],
        ["copy.5", 150, 25],
        ["fusion.6", 175, 25],
        ["fusion.7", 200, 50],
    ]
    paths = [
        "jit(step)/jvp(mlsl_attn)/while",
        "jit(step)/jvp(mlsl_attn)/while/body/dot_general",
        "jit(step)/transpose(jvp(mlsl_attn))/while/body/dot_general",
        "jit(step)/mlsl_update/mlsl_allreduce_grad/psum",
        "jit(step)/copy",
        None,
        "jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
        "mlsl_mlp/tanh",
    ]
    run = run_of(ops, paths)
    parts = scopes.by_scope(run.trace)
    got = {k: v["ns"] for k, v in parts.items()}
    assert got == {("forward", "attn"): 50 + 30, ("backward", "attn"): 20,
                   ("update", "comm"): 50, ("forward", "_unscoped_"): 50,
                   ("backward", "mlp"): 50}
    assert sum(got.values()) == 250 == run.trace.busy_s("0") * 1e9
    assert read("step_backward_share", run) == pytest.approx(100.0 * 70 / 250)
    assert read("step_update_share", run) is None      # no op of mlsl_update's own
    assert read("device_scoped_share", run) == pytest.approx(80.0)
    table = scopes.table(parts, 2)
    assert table[0][:3] == ["forward", "attn", pytest.approx(80e-9)]
    assert table[0][3:] == ["while", pytest.approx(50e-9)]
    assert scopes.format_table(table).count("\n") == 1
    fusion = scopes.family_table(parts, 1)
    assert fusion == [["fusion", pytest.approx(125e-9), [
        ["backward", "mlp", pytest.approx(50e-9)],
        ["forward", "attn", pytest.approx(30e-9)],
        ["forward", "_unscoped_", pytest.approx(25e-9)],
        ["backward", "attn", pytest.approx(20e-9)]]]]


def test_only_the_window_and_the_programs_asked_for_count():
    ops = [["fusion.1", 0, 10], ["fusion.2", 20, 10], ["fusion.3", 40, 10],
           ["fusion.4", 60, 10], ["fusion.5", 2000, 10]]
    paths = ["jit(chunk_body)/mlsl_experts/dot"] * 2 + [
        "jit(chunk_body)/jvp(mlsl_attn)/dot", "jit(decode_body)/mlsl_experts/dot",
        "jit(chunk_body)/mlsl_experts/dot"]
    modules = [["jit_chunk_body(7)", 0, 50], ["jit_decode_body(9)", 55, 20]]
    run = run_of(ops, paths, modules)
    assert read("prefill_expert_share", run) == pytest.approx(100.0 * 20 / 30)
    assert read("head_share_of_step", run) is None
    assert scopes.share(scopes.by_scope(run.trace),
                        lambda ph, s: s == "experts") == pytest.approx(75.0)


def test_an_executable_without_scopes_reads_none_and_zero():
    run = run_of([["fusion.1", 0, 10], ["convolution.2", 10, 30]],
                 ["jit(step)/transpose(jvp())/mul", "jit(step)/conv_general_dilated"],
                 [["jit_chunk_body(3)", 0, 40]])
    for name in SHARES:
        assert read(name, run) is None, name
    for name in COVERAGE:
        assert read(name, run) == 0.0


def test_a_trace_without_paths_reads_none_everywhere_and_the_fixtures_are_unchanged():
    data = json.loads((HERE / "small_trace.json").read_text())
    bare = trace.Trace(json.loads(json.dumps(data)))
    run = types.SimpleNamespace(trace=bare)
    for name in SHARES + COVERAGE:
        assert read(name, run) is None, name
    assert read("device_scoped_share", types.SimpleNamespace(trace=None)) is None
    # paths beside the operations change nothing that the trace's own readers see
    for dev in data["devices"].values():
        dev["scopes"] = ["jit(step)/mlsl_attn/x"] * len(dev["ops"])
    scoped = trace.Trace(data)
    assert scoped.top_ops(10) == bare.top_ops(10)
    assert scoped.idle_gaps() == bare.idle_gaps()
    assert scoped.module_seconds(r"jit_step") == bare.module_seconds(r"jit_step")
    assert scoped.exposed_collective_s("1") == bare.exposed_collective_s("1")
    assert read("device_scoped_share", types.SimpleNamespace(trace=scoped)) == 100.0


def test_the_scope_table_script_runs_a_tiny_cell_on_the_cpu(tmp_path):
    """The builder's script drives the run through ``perf/run.py``'s own
    path; on the CPU the profile holds no TPU plane, so nothing is booked."""
    from perf import scope_table

    out = scope_table.main(["--workload", "gpt2-medium-train", "--seed", "3",
                            "--seconds", "1", "--tiny"])
    assert out["workload"] == "gpt2-medium-train" and out["read_s"] >= 0
    assert "table" not in out
