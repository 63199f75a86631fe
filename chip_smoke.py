"""Chip smoke: the trainers and the serving engine, once, on the device.

    python chip_smoke.py             # on a TPU; fails without one
    python chip_smoke.py --tiny      # CPU dry run at toy sizes (tier-1)

One process. It names the machine, refuses any backend but the TPU, then drives
the main path through the public API (Environment -> Distribution -> Session ->
trainer / engine) at full width, each phase ending in block_until_ready and a
checked result:

  P1  ResNet-50 data-parallel (BASELINE.json config 5), fed by the device feed,
      on the default path and on the forced per-layer Start/Wait graph
  P2  the d1024x12 transformer at 2048 tokens: flash forward and backward are
      Pallas custom calls, and the step-0 loss matches the einsum path
  P3  serving on the same model: four requests, one checked against the oracle
  P4  the quantise kernels (packed and ragged) against their jnp references
  M1-M3 on four chips: ResNet-50 over live groups against a fused oracle,
      ring/zigzag attention with tensor parallelism, the int8 quantised ring

No phase is wrapped in an except that lets the run go on. The last line of
stdout is {"ok": true, "device": {...}}. Wall and compile seconds are printed as
set-up facts of this run; none is a benchmark metric. `--phases` runs a subset
(a builder's tool for a short chip budget; the contract is the run without it).
"""

import argparse
import contextlib
import copy
import dataclasses
import faulthandler
import json
import os
import sys
import time
from unittest import mock

#: the contract's limit is 1200 s: a wedged collective must end as a traceback
#: of every thread, not as the caller's kill
DEADLINE_S = 1150

PHASES = ("P1", "P2", "P3", "P4", "M1", "M2", "M3")


@dataclasses.dataclass(frozen=True)
class Sizes:
    """One model each at its real width (depth as the issue fixes it), or the
    toy sizes of the CPU dry run."""

    rn_batch: int = 256
    rn_hw: int = 224
    rn_classes: int = 1000
    rn_lr: float = 0.05                      # falls on one repeated batch
    tfm: dict = dataclasses.field(default_factory=lambda: dict(
        vocab=32768, d_model=1024, n_heads=16, head_dim=64, n_blocks=12,
        seq_len=2048))                       # gpt-medium-2k
    tfm_batch: int = 8
    ref_chunk: int = 2                       # sequences per einsum-reference call
    prompts: tuple = (128, 256, 384, 512)
    new_tokens: int = 32
    kv_cache_mb: int = 1024
    quant_packed: int = 16 << 20             # f32 elements: 64 MiB
    quant_ragged: int = 100_000              # under the 8*block*1024 threshold
    m2_blocks: int = 4                       # depth cut: three trainers compile
    m2_seq: int = 4096
    m2_batch: int = 2
    m3_count: int = 4 * 256 * 64


TINY = Sizes(
    rn_batch=16, rn_hw=32, rn_classes=10, rn_lr=0.003,  # 0.05 diverges here
    tfm=dict(vocab=512, d_model=64, n_heads=4, head_dim=16, n_blocks=2,
             seq_len=128),
    tfm_batch=2, ref_chunk=2, prompts=(8, 16, 24, 32), new_tokens=8,
    kv_cache_mb=64, quant_packed=1024 * 256, quant_ragged=100_000,
    m2_blocks=2, m2_seq=256, m2_batch=2, m3_count=4 * 256 * 32,
)


class CompileClock:
    """Sums what JAX reports about compilation: backend compile seconds, and
    persistent-cache hits with the seconds spent reading them back."""

    def __init__(self, jax):
        self.compile_s = self.retrieve_s = 0.0
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.retrieve_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return (self.compile_s, self.retrieve_s, self.hits, self.misses)


class Smoke:
    def __init__(self, args):
        import jax

        self.jax = jax
        self.tiny = args.tiny
        self.sizes = TINY if args.tiny else Sizes()
        self.clock = CompileClock(jax)
        self.passed = []
        self.env = None

    # -- reporting ----------------------------------------------------------

    @contextlib.contextmanager
    def phase(self, name, title):
        """Time one phase. No except clause: a failure ends the run."""
        print(f"--- {name}: {title}", flush=True)
        t0, c0 = time.perf_counter(), self.clock.snapshot()
        yield
        c1 = self.clock.snapshot()
        print(
            f"--- {name} passed | set-up facts of this run: wall "
            f"{time.perf_counter() - t0:.1f} s, backend compile "
            f"{c1[0] - c0[0]:.1f} s, cache hits {c1[2] - c0[2]} "
            f"(read back in {c1[1] - c0[1]:.1f} s), cache misses "
            f"{c1[3] - c0[3]}", flush=True)
        self.passed.append(name)

    def header(self):
        import importlib.metadata as md

        import jaxlib

        jax = self.jax
        try:
            libtpu = md.version("libtpu")
        except md.PackageNotFoundError:
            libtpu = "not installed"
        print(f"jax {jax.__version__}  jaxlib {jaxlib.__version__}  "
              f"libtpu {libtpu}  python {sys.version.split()[0]}")
        backend = jax.default_backend()
        devs = jax.devices()
        print(f"backend {backend}  device_kind {devs[0].device_kind!r}  "
              f"devices {len(devs)}")
        for d in devs:
            stats = d.memory_stats() or {}
            print(f"  device id={d.id} coords={getattr(d, 'coords', None)} "
                  f"bytes_limit={stats.get('bytes_limit', 'n/a')}")
        want = "cpu" if self.tiny else "tpu"
        if backend != want:
            sys.exit(f"chip_smoke: backend is {backend!r}, need {want!r} "
                     "(a chip run needs the TPU; --tiny is the CPU dry run)")

    # -- helpers ------------------------------------------------------------

    def finite(self, what, values):
        import numpy as np

        arr = np.asarray(values, dtype=np.float64)
        assert np.all(np.isfinite(arr)), f"{what}: non-finite {arr}"
        return arr

    def rel_l2(self, a, b):
        """||a - b|| / ||b|| over two parameter trees (host f64)."""
        import numpy as np

        jax = self.jax
        num = den = 0.0
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
            assert x.shape == y.shape, (x.shape, y.shape)
            num += float(np.sum((x - y) ** 2))
            den += float(np.sum(y ** 2))
        return (num / den) ** 0.5

    def same_training(self, l_a, l_b, p_a, p_b, p_init):
        """Two programs that run one math on the chip. On this synthetic batch
        (random images, random labels) the batch gradient is what is left
        after 256 per-example gradients all but cancel, so bf16 rounding is
        most of it: against the f32 gradient the bf16 one has cosine 0.24
        (CPU, PERF.md). Two differently fused programs therefore step in
        nearly orthogonal directions and end as far apart as either moved —
        parameter distance cannot tell a right program from a wrong one here,
        only bound a blow-up. The loss can: its fall comes from the shared
        true-gradient component, and a layer left unsynced or a wrong
        gradient scale bends the trajectory at once."""
        import numpy as np

        err, moved = self.rel_l2(p_a, p_b), self.rel_l2(p_a, p_init)
        gap = float(np.max(np.abs(l_a - l_b) / np.abs(l_b)))
        print(f"loss trajectories differ by at most {gap:.2e} (relative); "
              f"parameters rel l2 {err:.2e} apart, {moved:.2e} from initial")
        assert gap < 1e-2, f"loss trajectories part: {l_a} vs {l_b}"
        assert err < 3e-2, f"parameters {err} apart"

    def resnet_trainer(self, dist, params, **kw):
        from mlsl_tpu.models import resnet
        from mlsl_tpu.models.train import DataParallelTrainer

        sess = self.env.create_session()
        sess.set_global_minibatch_size(self.sizes.rn_batch)
        return DataParallelTrainer(
            self.env, dist, sess, params, resnet.loss_fn,
            resnet.layer_names(params), resnet.layer_subtree,
            lr=self.sizes.rn_lr, **kw)

    def resnet_feed(self, trainer):
        """The device feed as the ResNet cell builds it: uint8 wire, HBM cache, and
        one distinct batch replayed for ever (the repeated batch P1 needs)."""
        from mlsl_tpu.data import synthetic_source

        s = self.sizes
        cache_mb = s.rn_batch * s.rn_hw * s.rn_hw * 3 // (1 << 20) + 64
        return trainer.feed(
            lambda: synthetic_source(s.rn_batch, (s.rn_hw, s.rn_hw, 3),
                                     s.rn_classes, seed=1, steps=1),
            wire="uint8", cache_mb=cache_mb, epochs=None, depth=2)

    def resnet_params(self):
        from mlsl_tpu.models import resnet

        jax = self.jax
        init = jax.jit(resnet.init_resnet50, static_argnames="num_classes")
        return init(jax.random.PRNGKey(0), num_classes=self.sizes.rn_classes)

    def train_resnet(self, trainer, steps, sync_each_step=True):
        """-> per-step mean losses. The loss is read back after the loop
        unless sync_each_step: M1 leaves three steps of per-layer collectives
        un-awaited on purpose (on the CPU mesh that wedges, KNOWN_FAILURES.md)."""
        import numpy as np

        loader = self.resnet_feed(trainer)
        losses = []
        try:
            it = iter(loader)
            for _ in range(steps):
                loss = trainer.step(next(it))
                if sync_each_step:
                    self.jax.block_until_ready(loss)
                losses.append(loss)
            self.jax.block_until_ready((losses, trainer.params))
        finally:
            loader.close()
        return self.finite("resnet loss",
                           [float(np.asarray(l).mean()) for l in losses])

    def tfm_config(self, **over):
        from mlsl_tpu.models import transformer as tfm

        return tfm.TransformerConfig(**{**self.sizes.tfm, **over})

    def tokens(self, cfg, batch, seed=0):
        import numpy as np

        rng = np.random.default_rng(seed)
        toks = rng.integers(0, cfg.vocab, size=(batch, cfg.seq_len))
        toks = toks.astype(np.int32)
        return toks, np.roll(toks, -1, axis=1)

    # -- P1 -----------------------------------------------------------------

    def p1(self):
        jax, s = self.jax, self.sizes
        n_dev = jax.device_count()
        dist = self.env.create_distribution(n_dev, 1)
        params = self.resnet_params()
        default = self.resnet_trainer(dist, params)
        graph = self.resnet_trainer(dist, params, force_graph_path=True)
        path = "fused single program" if default._fused_fn is not None \
            else "per-layer graph over live groups"
        print(f"ResNet-50 batch {s.rn_batch} {s.rn_hw}x{s.rn_hw} "
              f"{s.rn_classes} classes over {n_dev} device(s); default path: "
              f"{path}")
        l_def = self.train_resnet(default, 5)
        l_gr = self.train_resnet(graph, 5)
        print(f"losses default {l_def.round(4).tolist()}")
        print(f"losses graph   {l_gr.round(4).tolist()}")
        for name, l in (("default", l_def), ("graph", l_gr)):
            assert l[4] < l[0], f"{name} path: loss did not fall {l}"
        self.same_training(l_def, l_gr, default.params, graph.params, params)

    # -- P2 -----------------------------------------------------------------

    def p2(self):
        import numpy as np

        from mlsl_tpu.models import transformer as tfm
        from mlsl_tpu.ops import attention_kernels
        from mlsl_tpu.parallel import sequence

        jax, s = self.jax, self.sizes
        cfg = self.tfm_config()
        # the dry run has no TPU for _use_flash to find: send it down the same
        # kernel path, interpreted (sysinfo.pallas_interpret)
        flash = mock.patch.object(
            sequence, "_use_flash", attention_kernels.supports
        ) if self.tiny else contextlib.nullcontext()
        with flash:
            trainer = tfm.HybridTrainer(
                self.env, cfg, 1, 1, 1, batch=s.tfm_batch,
                devices=self.env.devices[:1])
            toks, labels = self.tokens(cfg, s.tfm_batch)
            tb, lb = trainer.shard_tokens(toks, labels)

            # reference: the einsum branch of _dense_attention on the same
            # parameters, forward only, a few sequences at a time (the f32
            # score matrices of a whole batch would not fit beside the model)
            with mock.patch.object(sequence, "_use_flash",
                                   lambda *a: False):
                ref_fn = jax.jit(lambda p, t, l: tfm.local_loss(
                    p, t, l, cfg, 1, 1)[0])
                ce = 0.0
                for i in range(0, s.tfm_batch, s.ref_chunk):
                    ce += float(ref_fn(trainer.params,
                                       toks[i:i + s.ref_chunk],
                                       labels[i:i + s.ref_chunk]))
            ref = ce / (s.tfm_batch * cfg.seq_len)

            if not self.tiny:
                text = trainer.compiled_step(tb, lb).as_text()
                calls = text.count("tpu_custom_call")
                named = {k: text.count(k) for k in (
                    "_flash_kernel", "_bwd_dq_kernel", "_bwd_dkv_kernel")}
                print(f"compiled step: {calls} tpu_custom_call "
                      f"(3 per block expected: {3 * cfg.n_blocks}); kernel "
                      f"names in the text {named}")
                # the flash kernels are the program's only pallas_calls:
                # forward, dq and dk/dv — the kernel did not give way to the
                # einsum branch in either direction
                assert calls >= 3 and calls % 3 == 0, calls
            else:
                print("custom-call check: skipped (interpreted kernels leave "
                      "no custom call)")

            losses = [float(trainer.step(tb, lb)) for _ in range(3)]
        self.finite("transformer loss", losses)
        print(f"losses {np.round(losses, 4).tolist()}; einsum-path step-0 "
              f"loss {ref:.4f}")
        # one bf16 rounding of a ~ln(vocab) loss
        assert abs(losses[0] - ref) <= 2 ** -8 * abs(ref), (losses[0], ref)

        # one steady step timed both ways (set-up fact for ROADMAP S2: does
        # block_until_ready await on this machine?)
        t0 = time.perf_counter()
        jax.block_until_ready((trainer.step(tb, lb), trainer.params))
        t_block = time.perf_counter() - t0
        t0 = time.perf_counter()
        trainer.step(tb, lb)
        leaf = jax.tree.leaves(trainer.params)[0]
        float(np.asarray(jax.numpy.ravel(leaf)[0]))
        t_read = time.perf_counter() - t0
        print(f"one step, host clock: {t_block * 1e3:.1f} ms to "
              f"block_until_ready, {t_read * 1e3:.1f} ms to a one-element "
              f"read-back of the new parameters")

    # -- P3 -----------------------------------------------------------------

    def p3(self):
        import numpy as np

        from mlsl_tpu.core import stats
        from mlsl_tpu.serve import InferenceEngine

        s = self.sizes
        cfg = self.tfm_config()
        config = copy.copy(self.env.config)
        config.serve_kv_cache_mb = s.kv_cache_mb
        engine = InferenceEngine(self.env, cfg, tp=1,
                                 devices=self.env.devices[:1], config=config)
        try:
            rng = np.random.default_rng(3)
            prompts = [rng.integers(0, cfg.vocab, size=(n,)).astype(np.int32)
                       for n in s.prompts]
            reqs = [engine.submit(p, s.new_tokens) for p in prompts]
            engine.run()
            for r in reqs:
                assert r.state == "done" and len(r.tokens) == s.new_tokens, (
                    r.id, r.state, len(r.tokens), r.error)
                assert all(0 <= t < cfg.vocab for t in r.tokens)
            forks = self.oracle_agrees(engine, prompts[0], reqs[0].tokens)
        finally:
            engine.close()
        bad = {k: v for k, v in stats.SERVE_COUNTERS.items() if v and (
            k in ("failed", "rejected", "retries", "kv_evictions",
                  "kv_rejects") or k.startswith("shed_"))}
        assert not bad, f"serve failure counters: {bad}"
        print(f"{len(reqs)} requests done, {s.new_tokens} tokens each; "
              f"no request failed, shed, evicted or retried; request 0 "
              + ("equals oracle_generate token for token" if not forks else
                 f"parts from oracle_generate at near-ties {forks}"))

    def oracle_agrees(self, engine, prompt, tokens):
        """Greedy decoding is a chain of argmaxes. The paged decode step and
        the unpaged prefill are different programs (the step sums over the
        live pages, the prefill over its padded context): on the CPU their
        float32 logits agree to rounding, on the chip they differ in the
        last bf16 bits, so a near-tie may
        fall the other way and the two chains part for good. Walk the
        engine's chain: wherever oracle_generate picks another token, the
        engine's token must be a near-tie under the ORACLE's own logits, and
        the comparison restarts from the engine's prefix. -> [(position,
        logit gap)] of the forks; empty = token-for-token equal."""
        import numpy as np

        from mlsl_tpu.serve import oracle_generate, oracle_logits

        forks, pos = [], 0
        while pos < len(tokens):
            prefix = np.concatenate([prompt, tokens[:pos]]).astype(np.int32)
            want = oracle_generate(engine, prefix, len(tokens) - pos)
            k = next((i for i, w in enumerate(want)
                      if w != tokens[pos + i]), None)
            if k is None:
                break
            at = pos + k
            logits = oracle_logits(
                engine, np.concatenate([prompt, tokens[:at]]))
            gap = float(logits.max() - logits[tokens[at]])
            # the two programs' logits were seen one bf16 rounding (2^-8 of
            # the largest) apart on the chip; allow four
            tol = 2 ** -6 * float(np.abs(logits).max())
            print(f"token {at}: engine {tokens[at]}, oracle {want[k]}, "
                  f"oracle logit gap {gap:.5f} (tolerance {tol:.5f})")
            assert gap <= tol, (at, gap, tol)
            forks.append((at, round(gap, 5)))
            pos = at + 1
        return forks

    # -- P4 -----------------------------------------------------------------

    def p4(self):
        import numpy as np

        from mlsl_tpu.ops import quant_kernels as qk

        jax, jnp = self.jax, self.jax.numpy
        block = 256
        for name, n in (("packed", self.sizes.quant_packed),
                        ("ragged", self.sizes.quant_ragged)):
            x = jax.random.normal(jax.random.PRNGKey(n % 97), (n,),
                                  jnp.float32)
            q, scales, orig = qk.quantize(x, block)
            rows = q.shape[0] // block
            packed = rows % qk.PACK_ROWS == 0
            assert packed == (name == "packed"), (name, rows)
            x2d = jnp.pad(x, (0, q.shape[0] - n)).reshape(rows, block)
            q_ref, s_ref = qk.quantize_blocks_ref(x2d)
            np.testing.assert_allclose(np.asarray(scales), np.asarray(s_ref),
                                       rtol=1e-6)
            # x / scale may round one ulp apart in the two compilers, which
            # moves a value sitting on .5 to the neighbouring integer
            dq = np.abs(np.asarray(q, np.int32).reshape(rows, block)
                        - np.asarray(q_ref, np.int32))
            assert dq.max() <= 1 and (dq != 0).mean() < 1e-4, (
                dq.max(), (dq != 0).mean())
            out = qk.dequantize(q, scales, block, orig)
            want = qk.dequantize_blocks_ref(
                q.reshape(rows, block), scales).reshape(-1)[:n]
            np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                       rtol=1e-6, atol=0)
            # the int8 block bound: half a quantisation step per element
            bound = np.repeat(np.asarray(scales), block)[:n] * (0.5 + 1e-3)
            assert np.all(np.abs(np.asarray(out) - np.asarray(x)) <= bound)
            jax.block_until_ready(out)
            print(f"quantise/dequantise {name}: {n} elements, {rows} rows of "
                  f"{block}, differing int8 codes {(dq != 0).sum()}")

    # -- M1 -----------------------------------------------------------------

    def m1(self):
        """P1 on a four-chip host is already the default configuration over
        live groups: auto_config is opt-in (MLSL_AUTO_CONFIG_TYPE, default 0)
        and so is priority deferral (MLSL_MSG_PRIORITY). M1 arms both the way
        a user would, through the environment, so the TPU-class row of
        sysinfo._CLASS_DEFAULTS — 4 MiB gradient buckets, the 256 Ki-element
        deferral threshold, the native priority queue — runs on the device."""
        import mlsl_tpu as mlsl

        armed = {"MLSL_AUTO_CONFIG_TYPE": "1", "MLSL_MSG_PRIORITY": "1"}
        assert not set(armed) & set(os.environ), "armed from outside already"
        self.env.finalize()
        os.environ.update(armed)
        try:
            self.env = mlsl.Environment.get_env().init()
            self._m1()
        finally:
            for k in armed:
                del os.environ[k]
            self.env.finalize()
            self.env = mlsl.Environment.get_env().init()

    def _m1(self):
        import numpy as np
        from jax import lax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from mlsl_tpu.models import resnet

        jax, s = self.jax, self.sizes
        devs = self.env.devices[:4]
        dist = self.env.create_distribution(4, 1, devices=devs)
        print("ring order (Topology reshapes the device list as enumerated): "
              + " -> ".join(f"id{d.id}@{getattr(d, 'coords', None)}"
                            for d in dist.topology.mesh.devices.flat))
        params = self.resnet_params()
        trainer = self.resnet_trainer(dist, params)
        assert trainer._fused_fn is None, "four live ranks must sync per layer"
        cfg = self.env.config
        print(f"armed config: grad_bucket_mb={cfg.grad_bucket_mb} "
              f"msg_priority={cfg.msg_priority} "
              f"msg_priority_threshold={cfg.msg_priority_threshold} "
              f"large_msg_size_mb={cfg.large_msg_size_mb} "
              f"large_msg_chunks={cfg.large_msg_chunks}")

        # the fused oracle: a single raw-JAX program (loss + grad +
        # SGD, no framework), written per shard because the trainer's batch
        # norm is per device (models/resnet.py) — a GSPMD jit over the global
        # batch would normalise over all 256 images and be another model
        mesh = Mesh(np.array(devs), ("data",))
        lr = s.rn_lr

        def body(p, x, y):
            loss, g = jax.value_and_grad(resnet.loss_fn)(p, (x, y))
            g = jax.tree.map(lambda t: lax.pmean(t, "data"), g)
            return (lax.pmean(loss, "data"),
                    jax.tree.map(lambda w, gg: w - lr * gg, p, g))

        # check_vma=False: gradients of the replicated parameters stay local
        # until the pmean above (with the check on, JAX would already have
        # summed them over the mesh)
        oracle = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(P(), P("data"), P("data")),
            out_specs=(P(), P()), check_vma=False))

        loader = self.resnet_feed(trainer)
        try:
            it = iter(loader)
            xb, yb = next(it)
            shards = {sh.device for sh in xb.addressable_shards}
            assert shards == set(devs), f"batch lives on {shards}"
            assert all(sh.data.shape[4] == s.rn_batch // 4
                       for sh in xb.addressable_shards)
            # the one reach past the public surface: the gradient buffers the
            # per-layer requests are started on
            _, grads = trainer._grad_fn(trainer.params, (xb, yb))
            probe = (trainer.layers[0], trainer.layers[-1])
            for name in probe:
                got = {sh.device for sh in grads[name].addressable_shards}
                assert got == set(devs), f"grad {name} lives on {got}"
            # what the loss cannot show sharply, the wire can: one layer's
            # Start/Wait over ICI returns the sum of the four shards
            sets = [trainer.ops[n].get_parameter_set(0) for n in probe]
            for ps, name in zip(sets, probe):
                ps.start_gradient_comm(grads[name])
            for ps, name in zip(sets, probe):
                out = np.asarray(ps.wait_gradient_comm())
                want = np.asarray(grads[name]).sum(axis=1, keepdims=True)
                np.testing.assert_allclose(
                    out, np.broadcast_to(want, out.shape),
                    rtol=1e-5, atol=1e-6 * np.abs(want).max())
            print(f"allreduce of {probe} gradient buffers = sum of 4 shards")
            del grads

            op = jax.device_put(params, NamedSharding(mesh, P()))
            ox = xb.reshape(s.rn_batch, *xb.shape[5:])
            oy = yb.reshape(s.rn_batch)
            o_losses, losses = [], [trainer.step((xb, yb))]
            for _ in range(2):
                # un-awaited on the chip: three steps of per-layer
                # collectives in flight wedge XLA:CPU's rendezvous on the CPU
                # mesh, where the dry run therefore awaits every step
                if self.tiny:
                    jax.block_until_ready(losses[-1])
                losses.append(trainer.step(next(it)))
            for _ in range(3):
                ol, op = oracle(op, ox, oy)
                o_losses.append(ol)
            jax.block_until_ready((losses, o_losses, trainer.params, op))
        finally:
            loader.close()
        losses = self.finite("M1 loss", [float(np.asarray(l).mean())
                                         for l in losses])
        o_losses = self.finite("oracle loss", [float(l) for l in o_losses])
        print(f"losses graph  {losses.round(4).tolist()}")
        print(f"losses oracle {o_losses.round(4).tolist()}")
        self.same_training(losses, o_losses, trainer.params, op, params)
        if not self.tiny:
            used = [d.memory_stats()["bytes_in_use"] for d in devs]
            print(f"bytes_in_use per device {used}")
            assert max(used) < 4 * min(used), "memory piled up on one device"

    # -- M2 -----------------------------------------------------------------

    def m2(self):
        from mlsl_tpu.models import transformer as tfm

        s = self.sizes
        devs = self.env.devices[:4]
        base = self.tfm_config(n_blocks=s.m2_blocks, seq_len=s.m2_seq)
        toks, labels = self.tokens(base, s.m2_batch, seed=2)
        losses = {}
        for attn in ("ring", "zigzag"):
            cfg = dataclasses.replace(base, attention=attn)
            trainer = tfm.HybridTrainer(self.env, cfg, 1, 2, 2,
                                        batch=s.m2_batch, devices=devs)
            tb, lb = trainer.shard_tokens(toks, labels)
            if not self.tiny:
                text = trainer._grad_fn.lower(trainer.params, tb, lb).as_text()
                n = text.count('kernel_name = "_block_kernel"')
                print(f"{attn}: flash_block_update lowered inside shard_map "
                      f"as a Pallas custom call ({n} call sites)")
                assert n >= 1 and "tpu_custom_call" in text
            losses[attn] = float(trainer.step(tb, lb))
            del trainer
        one = tfm.HybridTrainer(self.env, base, 1, 1, 1, batch=s.m2_batch,
                                devices=devs[:1])
        tb, lb = one.shard_tokens(toks, labels)
        losses["one chip"] = float(one.step(tb, lb))
        self.finite("M2 loss", list(losses.values()))
        print(f"step-0 losses at {s.m2_seq} tokens, d{base.d_model}x"
              f"{base.n_blocks}: {losses}")
        ref = losses["one chip"]
        for attn in ("ring", "zigzag"):
            assert abs(losses[attn] - ref) <= 2 ** -8 * abs(ref), losses

    # -- M3 -----------------------------------------------------------------

    def m3(self):
        import numpy as np

        from mlsl_tpu import supervisor
        from mlsl_tpu.types import (CompressionType, DataType, GroupType,
                                    ReductionType)

        n, g = self.sizes.m3_count, 4
        dist = self.env.create_distribution(4, 1, devices=self.env.devices[:4])
        buf = dist.make_buffer(
            lambda p: np.random.default_rng(p).normal(size=n), n)
        plain = self.env.wait(dist.all_reduce(
            buf, n, DataType.FLOAT, ReductionType.SUM, GroupType.DATA))
        quant = self.env.wait(dist.all_reduce(
            buf, n, DataType.FLOAT, ReductionType.SUM, GroupType.DATA,
            compression=CompressionType.QUANTIZATION))
        self.jax.block_until_ready((plain, quant))
        plain, quant = np.asarray(plain), np.asarray(quant)
        assert quant.shape == plain.shape and np.all(np.isfinite(quant))
        # every quantisation on the way is off by at most half a step of the
        # value it encodes: G entry codes, a partial sum of t+1 ranks on hop
        # t of the reduce-scatter, the finished sum once for the all-gather
        amax = float(np.abs(np.asarray(buf)).max())
        bound = amax / 254 * (g + g * (g - 1) / 2 + g) * 1.01
        err = float(np.abs(quant - plain).max())
        print(f"int8 ring vs f32 psum over 4: max error {err:.4f}, block "
              f"bound {bound:.4f}")
        assert err <= bound, (err, bound)
        state = supervisor.status()["quant"]
        assert state["state"] == "closed" and not state["trips"], state

    # -- exit ---------------------------------------------------------------

    def exit_checks(self):
        from mlsl_tpu import native, supervisor
        from mlsl_tpu.core import stats

        status = supervisor.status()
        for name in supervisor.SUBSYSTEMS:
            st = status[name]
            assert st["state"] == "closed" and not st["trips"] \
                and not st["failures_in_window"], (name, st)
        assert not any(stats.DEGRADE_COUNTERS.values()), stats.DEGRADE_COUNTERS
        assert not stats.DEGRADE_FALLBACKS, stats.DEGRADE_FALLBACKS
        assert not stats.SERVE_COUNTERS["failed"], stats.SERVE_COUNTERS
        native.load()
        nat = native.status()
        assert nat["loaded"], f"native core not loaded: {nat}"
        print(f"breakers closed: {list(supervisor.SUBSYSTEMS)}; degrade and "
              f"serve-failure counters zero; native core "
              f"{'built in this run' if nat['built_this_run'] else 'found current by make'}"
              f" ({nat['path']})")

    def run(self, phases):
        import mlsl_tpu as mlsl

        self.env = mlsl.Environment.get_env().init()
        print(f"compile cache: {self.env.compile_cache_dir or 'off (cpu)'}")
        steps = [
            ("P1", "ResNet-50 data-parallel, default path and per-layer graph",
             self.p1),
            ("P2", "transformer train step, flash kernels against einsum",
             self.p2),
            ("P3", "serving engine against the unpaged oracle", self.p3),
            ("P4", "quantise kernels, packed and ragged", self.p4),
        ]
        multi = [
            ("M1", "ResNet-50 over four ranks against a fused oracle",
             self.m1),
            ("M2", "ring and zigzag attention, sp=2 x tp=2", self.m2),
            ("M3", "int8 quantised ring over four", self.m3),
        ]
        if self.jax.device_count() >= 4:
            steps += multi
        else:
            print(f"multi-chip phases: skipped, {self.jax.device_count()} "
                  f"device")
        for name, title, fn in steps:
            if name in phases:
                with self.phase(name, title):
                    fn()
        self.exit_checks()
        c = self.clock
        print(f"set-up facts of this run: backend compile {c.compile_s:.1f} s "
              f"in total, {c.hits} cache hits, {c.misses} cache misses; "
              f"phases passed: {' '.join(self.passed)}")
        self.env.finalize()


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="CPU dry run at toy sizes, kernels interpreted")
    ap.add_argument("--phases", default=",".join(PHASES),
                    help="comma list out of " + ",".join(PHASES))
    args = ap.parse_args()
    phases = [p for p in args.phases.split(",") if p]
    unknown = set(phases) - set(PHASES)
    if unknown:
        ap.error(f"unknown phases {sorted(unknown)}")
    if args.tiny:
        print("DRY RUN (cpu): not a chip result", flush=True)
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4").strip()
    elif "tpu" not in os.environ.get("JAX_PLATFORMS", "tpu"):
        # the platform list rules the chip out: no need to start JAX to know
        sys.exit(f"chip_smoke: JAX_PLATFORMS={os.environ['JAX_PLATFORMS']} "
                 "names no TPU, need 'tpu' (--tiny is the CPU dry run)")
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)

    smoke = Smoke(args)
    smoke.header()
    smoke.run(phases)
    dev = smoke.jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(smoke.jax.devices())}}))


if __name__ == "__main__":
    main()
