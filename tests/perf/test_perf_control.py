"""The comparison that decides ``correct`` has been shown to fail: the
control (the reference in the precision below the configuration's, put in the
program's place) and each fault a cell can have, planted under the timed
path of a tiny run, come out as not correct."""

import json

import jax
import numpy as np
import pytest

from perf_helpers import ROOT, tiny_run


def _control(capsys, workload, seed, *extra):
    from perf import control

    control.main(["--workload", workload, "--seed", str(seed), "--tiny",
                  *extra])
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    return {l["in_place"]: l for l in lines}


@pytest.mark.parametrize("workload,number", [
    ("gpt2-medium-train", "grad1_worst_leaf"),
    ("resnet50-1chip", "grad1_median_leaf")])
def test_training_control_and_half_batch_come_out_not_correct(capsys, workload,
                                                              number):
    got = _control(capsys, workload, 11)
    fp8, half = got["control_fp8"], got["fault_half_batch"]
    assert fp8["correct"] is False and half["correct"] is False
    # the fault reads ten times the limit on the first gradient
    value, limit = half["checks"][number]
    assert value > 10 * limit
    # the reference in the precision the configuration states is no control
    assert got["stated_bf16"]["correct"] is True


def test_serving_control_comes_out_not_correct(capsys):
    got = _control(capsys, "gpt2-medium-serve-chat", 11, "--seconds", "2")
    assert got["program"]["correct"] is True
    assert got["control_fp8"]["correct"] is False


class _Proxy:
    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, name):
        return getattr(self._inner, name)


class _StateUnchanged(_Proxy):
    """A step that returns its state unchanged: the loss is computed, the
    parameters and the optimizer's state stay what they were."""

    def __init__(self, inner, ctx):
        super().__init__(inner)
        from perf.lib import manifest

        self._ref = manifest.reference(ctx.config)
        self._ctx = ctx

    def step(self, batch):
        loss = self._inner.step(batch)
        tr = self._inner.trainer
        fresh = self._ref.init_params(self._ctx.args.seed, self._ctx.config)
        tr.params = jax.tree.map(
            lambda x, old: jax.device_put(x, old.sharding), fresh, tr.params)
        if hasattr(tr, "_opt_state"):       # plain SGD keeps none
            tr._opt_state = jax.tree.map(lambda s: s * 0, tr._opt_state)
        return loss


class _HalfBatch(_Proxy):
    """Half of the batch left out, the mean taken over the rest. The rows lie
    on the first axis that has the batch's size (the device feed puts the
    mesh's axes before it)."""

    def __init__(self, inner, rows):
        super().__init__(inner)
        self._rows = rows

    def step(self, batch):
        def first_half_twice(x):
            axis = x.shape.index(self._rows)
            half = jax.lax.slice_in_dim(x, 0, self._rows // 2, axis=axis)
            return jax.numpy.concatenate([half, half], axis=axis)
        return self._inner.step(jax.tree.map(first_half_twice, batch))


class _TokenAltered(_Proxy):
    """A token altered where it is produced: the third token of every
    request is handed out one id higher than the engine chose it."""

    def __init__(self, inner, vocab):
        super().__init__(inner)
        self._vocab, self._handles = vocab, []

    def submit(self, prompt, max_new):
        h = self._inner.submit(prompt, max_new)
        self._handles.append([h, False])
        return h

    def step(self):
        n = self._inner.step()
        for entry in self._handles:
            h, altered = entry
            if not altered and len(h.tokens) >= 3:
                h.tokens[2] = (h.tokens[2] + 1) % self._vocab
                entry[1] = True
        return n


@pytest.mark.parametrize("workload,fault,number", [
    ("gpt2-medium-train", "state_unchanged", "delta3_worst_leaf"),
    ("gpt2-medium-train", "half_batch", "grad1_worst_leaf"),
    ("resnet50-1chip", "state_unchanged", "delta3_median_leaf"),
    ("resnet50-1chip", "half_batch", "grad1_median_leaf")])
def test_a_broken_training_step_comes_out_not_correct(capsys, workload, fault,
                                                      number):
    def prepare(ctx):
        if fault == "state_unchanged":
            ctx.wrap_trainer = lambda t: _StateUnchanged(t, ctx)
        else:
            ctx.wrap_trainer = lambda t: _HalfBatch(t, ctx.traffic["batch"])

    out, last, err = tiny_run(capsys, workload, seed=21, seconds=0.5,
                              prepare=prepare)
    assert last["correct"] is False
    value, limit = last["checks"][number]
    assert value > limit
    if fault == "state_unchanged":
        # nothing moved: the gap of norms is the reference's own norm
        assert value == pytest.approx(1.0, abs=1e-3)
    assert err.strip().splitlines()[-1] == "correct: False"


def test_an_altered_token_comes_out_not_correct(capsys):
    def prepare(ctx):
        vocab = ctx.config["vocab_size"]
        ctx.wrap_engine = lambda e: _TokenAltered(e, vocab)

    out, last, _ = tiny_run(capsys, "gpt2-medium-serve-chat", seed=22,
                            seconds=1.5, prepare=prepare)
    assert last["correct"] is False
    value, limit = last["checks"]["served_logit_gap_max"]
    assert value > limit


def test_a_refused_request_counts_as_failed(capsys):
    class Refusing(_Proxy):
        def __init__(self, inner):
            super().__init__(inner)
            self._n = 0

        def submit(self, prompt, max_new):
            self._n += 1
            if self._n == 5:        # after the warm-up's requests
                raise RuntimeError("refused")
            return self._inner.submit(prompt, max_new)

    def prepare(ctx):
        ctx.wrap_engine = Refusing

    out, last, _ = tiny_run(capsys, "gpt2-medium-serve-chat", seed=23,
                            seconds=1.5, prepare=prepare)
    assert last["failed"] == 1 and last["correct"] is False
