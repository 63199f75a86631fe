"""The parts of the model name themselves on the device: each program of the
benchmark's five cells, built at toy sizes through the benchmark's own
adapters and compiled on the CPU, holds every ``mlsl_*`` scope of the
vocabulary that it computes in its operations' ``op_name`` metadata, and the
backward operations of the two train steps carry JAX's ``transpose(``
(docs/DESIGN.md "Observability & tracing"; perf/lib/scopes.py reads them
from the profiler's file)."""

import pathlib
import re
import sys

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from perf.lib import manifest, train_cell  # noqa: E402

DENSE = {"embed", "norm", "attn", "mlp", "head"}
WANTED = {
    ("gpt2-medium-train", "step"): DENSE | {"update"},
    ("resnet50-1chip", "step"): {"conv", "norm", "head", "update"},
    ("resnet50-1chip", "feed"): {"feed"},
    ("gpt2-medium-serve-chat", "prefill"): DENSE,
    ("gpt2-medium-serve-chat", "write"): {"attn"},
    ("gpt2-medium-serve-chat", "decode"): DENSE,
    ("keye-vl2-30b-a3b-serve-longctx", "chunk"): DENSE - {"mlp"} | {"experts"},
    ("keye-vl2-30b-a3b-serve-longctx", "decode"): DENSE - {"mlp"} | {"experts"},
    ("ax-k1-ep16-pp10-serve-docreason", "chunk"): DENSE | {"experts"},
    ("ax-k1-ep16-pp10-serve-docreason", "decode"): DENSE | {"experts"},
}
_TEXTS = {}     # cell -> {program: compiled HLO text}


def _tiny(workload):
    man = manifest.load()
    cell = manifest.cell(man, workload)
    config = {**manifest.config_file(man, cell["config"]), **manifest.read_json(
        manifest.PERF / "configs" / "tiny" / f"{cell['config']}.json")}
    traffic = manifest.traffic_file(cell["traffic"])
    return config, {**traffic, **traffic.get("tiny", {})}


class _Keep:
    """A program that keeps its compiled text the first time it is called."""

    def __init__(self, fn, texts, name):
        self.fn, self.texts, self.name = fn, texts, name

    def __call__(self, *args):
        if self.name not in self.texts:
            self.texts[self.name] = self.fn.lower(*args).compile().as_text()
        return self.fn(*args)


def _train(workload, config, traffic, env, adapter, params):
    handle = adapter.Trainer(env, config, traffic, params, 1)
    source = train_cell.host_batches(5, config, traffic)
    tr = handle.trainer
    if workload == "gpt2-medium-train":
        tokens, labels = next(handle.feed(source))
        return {"step": tr.compiled_step(tokens, labels).as_text()}
    from mlsl_tpu.data.wire import FeedCodec

    n = traffic["normalize"]
    codec = FeedCodec(tr.dist.topology, traffic["wire"],
                      normalize=(n["mean"], n["std"]))
    wire, _, _ = codec.stage(source[0])
    batch = codec.decode(wire)
    return {"step": tr._fused_fn.lower(tr.params, batch).compile().as_text(),
            "feed": codec._build_decode(False).lower(wire).compile().as_text()}


def _serve(config, traffic, env, adapter, params):
    handle = adapter.Engine(env, config, traffic, params, 1)
    eng, texts = handle.engine, {}
    for attr, name in (("_prefill", "prefill"), ("_write", "write"),
                       ("_chunk_prog", "chunk")):
        if getattr(eng, attr, None) is not None:
            setattr(eng, attr, _Keep(getattr(eng, attr), texts, name))
    decode_prog = eng._decode_prog
    eng._decode_prog = lambda dtype: _Keep(decode_prog(dtype), texts, "decode")
    chunk = traffic.get("prefill_chunk_tokens", 8)
    handle.submit(list(range(1, chunk + 3)), 3)
    for _ in range(64):
        if not handle.step() and "decode" in texts:
            break
    handle.close()
    return texts


def _texts(workload):
    if workload not in _TEXTS:
        config, traffic = _tiny(workload)
        ref, adapter = manifest.reference(config), manifest.adapter(config)
        env = adapter.environment()
        params = ref.init_params(5, config)
        if traffic["kind"] == "train":
            _TEXTS[workload] = _train(workload, config, traffic, env, adapter,
                                      params)
        else:
            _TEXTS[workload] = _serve(config, traffic, env, adapter, params)
    return _TEXTS[workload]


def _scopes(text):
    paths = re.findall(r'op_name="([^"]*)"', text)
    return paths, {s[len("mlsl_"):] for p in paths
                   for s in re.findall(r"mlsl_[A-Za-z0-9_]+", p)}


@pytest.mark.parametrize("workload,program", sorted(WANTED),
                         ids=[f"{w}-{p}" for w, p in sorted(WANTED)])
def test_each_program_names_its_parts(workload, program):
    text = _texts(workload).get(program)
    assert text is not None, f"{workload} ran no {program} program"
    paths, found = _scopes(text)
    assert WANTED[workload, program] <= found, sorted(found)
    if program == "step":
        assert any("transpose(" in p for p in paths)
        # the update is no part of the backward pass
        assert not any("transpose(" in p and "mlsl_update" in p for p in paths)


def test_a_scope_leaves_the_program_unchanged():
    """The scopes live in the debug information, which the program's text
    (and JAX's compile cache key) leave out: a body lowers to the same
    StableHLO with and without them."""
    import jax.numpy as jnp

    def body(w, x, scoped):
        def loss(w):
            if scoped:
                with jax.named_scope("mlsl_head"):
                    return jnp.sum(jnp.tanh(x @ w) ** 2)
            return jnp.sum(jnp.tanh(x @ w) ** 2)

        g = jax.grad(loss)(w)
        if scoped:
            with jax.named_scope("mlsl_update"):
                return w - 0.1 * g
        return w - 0.1 * g

    w, x = jnp.ones((16, 16)), jnp.ones((4, 16))
    plain, scoped = (jax.jit(lambda w, x, s=s: body(w, x, s)).lower(w, x)
                     for s in (False, True))
    assert plain.as_text() == scoped.as_text()
    assert "mlsl_head" in scoped.as_text(debug_info=True)
    assert "mlsl_head" not in plain.as_text(debug_info=True)
