"""Hybrid dp x sp x tp transformer training vs a single-device oracle."""

import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from mlsl_tpu.models import transformer as tfm


CFG = tfm.TransformerConfig(
    vocab=32, d_model=16, n_heads=4, head_dim=4, n_blocks=2, seq_len=16,
    dtype="float32",  # exactness vs the oracle; bf16 is the production default
)


def _data(b, seed=0):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, CFG.vocab, size=(b, CFG.seq_len)).astype(np.int32)
    labels = rng.integers(0, CFG.vocab, size=(b, CFG.seq_len)).astype(np.int32)
    return toks, labels


def _oracle_steps(params, toks, labels, lr, n_steps, cfg=CFG):
    """Single-device full-batch SGD on mean CE (tp=sp=1 path)."""

    def mean_loss(p):
        ce, _ = tfm.local_loss(p, jnp.asarray(toks), jnp.asarray(labels), cfg, 1, 1)
        return ce / (toks.shape[0] * cfg.seq_len)

    for _ in range(n_steps):
        g = jax.grad(mean_loss)(params)
        params = jax.tree.map(lambda p, gg: p - lr * gg, params, g)
    return params, float(mean_loss(params))


def _assert_params_close(trainer, ref_params, atol=2e-2, rtol=2e-2):
    for g, w in zip(
        jax.tree.leaves(jax.device_get(trainer.params)),
        jax.tree.leaves(jax.device_get(ref_params)),
    ):
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32), atol=atol, rtol=rtol
        )


@pytest.mark.parametrize("dp,sp,tp", [(2, 2, 2), (8, 1, 1), (1, 4, 2), (2, 4, 1), (1, 2, 4), (1, 1, 2)])
def test_hybrid_matches_oracle(env, dp, sp, tp):
    b = 2 * dp
    trainer = tfm.HybridTrainer(env, CFG, dp, sp, tp, batch=b, lr=0.5,
                                devices=env.devices[: dp * sp * tp])
    toks, labels = _data(b)
    # oracle from identical initial params (single device, no sharding)
    ref_params = tfm.init_params(jax.random.PRNGKey(0), CFG)
    st, sl_ = trainer.shard_tokens(toks, labels)
    losses = []
    for _ in range(2):
        losses.append(float(trainer.step(st, sl_)))
    ref_params, _ = _oracle_steps(ref_params, toks, labels, 0.5, 2)
    _assert_params_close(trainer, ref_params)
    assert np.isfinite(losses).all()


@pytest.mark.parametrize("dp,sp,tp", [(2, 2, 2), (4, 1, 2), (8, 1, 1), (1, 1, 2)])
def test_hybrid_distributed_update_matches_oracle(env, dp, sp, tp):
    """ZeRO-1 (reduce-scatter grads / owned update / all-gather increments)
    combined with TP and SP must still reproduce plain SGD."""
    b = 2 * dp
    trainer = tfm.HybridTrainer(
        env, CFG, dp, sp, tp, batch=b, lr=0.5, distributed_update=True,
        devices=env.devices[: dp * sp * tp],
    )
    toks, labels = _data(b)
    ref_params = tfm.init_params(jax.random.PRNGKey(0), CFG)
    st, sl_ = trainer.shard_tokens(toks, labels)
    for _ in range(2):
        trainer.step(st, sl_)
    ref_params, _ = _oracle_steps(ref_params, toks, labels, 0.5, 2)
    _assert_params_close(trainer, ref_params)


def test_hybrid_zero1_with_quantization(env):
    """The combined path: quantized reduce-scatter grads + all-gather increments."""
    from mlsl_tpu.types import CompressionType

    trainer = tfm.HybridTrainer(
        env, CFG, 4, 1, 2, batch=8, lr=0.5,
        distributed_update=True, compression=CompressionType.QUANTIZATION,
    )
    toks, labels = _data(8, seed=3)
    st, sl_ = trainer.shard_tokens(toks, labels)
    losses = [float(trainer.step(st, sl_)) for _ in range(6)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_hybrid_zero1_degenerate_grad_group(env):
    """dp=sp=1 (pure TP): distributed update falls back to the local increment."""
    trainer = tfm.HybridTrainer(
        env, CFG, 1, 1, 2, batch=1, lr=0.5, distributed_update=True,
        devices=env.devices[:2],
    )
    toks, labels = _data(1, seed=4)
    st, sl_ = trainer.shard_tokens(toks, labels)
    losses = [float(trainer.step(st, sl_)) for _ in range(4)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_hybrid_quantized_converges(env):
    from mlsl_tpu.types import CompressionType

    trainer = tfm.HybridTrainer(
        env, CFG, 2, 2, 2, batch=4, lr=0.5,
        compression=CompressionType.QUANTIZATION,
    )
    toks = np.random.default_rng(1).integers(0, 32, size=(4, 16)).astype(np.int32)
    labels = np.roll(toks, -1, axis=1)
    st, sl_ = trainer.shard_tokens(toks, labels)
    losses = [float(trainer.step(st, sl_)) for _ in range(8)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_sharded_vocab_matches_oracle(env):
    """Model-axis-sharded LM head (CE via pmax/psum, no full-V logits): training
    must be exactly the replicated-head math."""
    cfg = dataclasses.replace(CFG, sharded_vocab=True)
    dp, sp, tp = 2, 2, 2
    b = 2 * dp
    trainer = tfm.HybridTrainer(env, cfg, dp, sp, tp, batch=b, lr=0.5)
    toks, labels = _data(b, seed=6)
    ref_params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    st, sl_ = trainer.shard_tokens(toks, labels)
    for _ in range(2):
        trainer.step(st, sl_)
    ref_params, _ = _oracle_steps(ref_params, toks, labels, 0.5, 2, cfg=cfg)
    _assert_params_close(trainer, ref_params)


def test_hybrid_moe_expert_parallel(env):
    """MoE transformer with expert parallelism over the model axis (ep=tp=2):
    trains with finite decreasing loss + aux load balancing. (The moe module's
    own tests pin SPMD-vs-oracle exactness, forward and gradients.)"""
    cfg = tfm.TransformerConfig(
        vocab=32, d_model=16, n_heads=4, head_dim=4, n_blocks=2, seq_len=16,
        dtype="float32", n_experts=4, moe_aux_weight=0.01,
    )
    dp, sp, tp = 2, 1, 2
    b = 2 * dp
    trainer = tfm.HybridTrainer(
        env, cfg, dp, sp, tp, batch=b, lr=0.5, devices=env.devices[: dp * sp * tp]
    )
    toks = np.random.default_rng(5).integers(0, 32, size=(b, 16)).astype(np.int32)
    labels = np.roll(toks, -1, axis=1)
    st, sl_ = trainer.shard_tokens(toks, labels)
    losses = [float(np.asarray(trainer.step(st, sl_))) for _ in range(10)]
    assert np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0], losses


def test_hybrid_ulysses_variant(env):
    cfg = tfm.TransformerConfig(
        vocab=32, d_model=16, n_heads=4, head_dim=4, n_blocks=1, seq_len=16,
        attention="ulysses",
    )
    trainer = tfm.HybridTrainer(env, cfg, 2, 2, 2, batch=4, lr=0.5)
    toks = np.random.default_rng(0).integers(0, 32, size=(4, 16)).astype(np.int32)
    labels = np.roll(toks, -1, axis=1)
    st, sl_ = trainer.shard_tokens(toks, labels)
    l0 = float(trainer.step(st, sl_))
    l5 = l0
    for _ in range(5):
        l5 = float(trainer.step(st, sl_))
    assert np.isfinite(l0) and l5 < l0  # memorizing a fixed batch must reduce loss


def test_bf16_config_runs_on_cpu_mesh(env):
    """The production bf16 dtype must stay executable on the CPU simulation mesh
    (mixed bf16->f32 dots are unsupported there; mxu_einsum guards this).
    Regression: the multichip dryrun uses the default bf16 config."""
    cfg = tfm.TransformerConfig(
        vocab=32, d_model=16, n_heads=4, head_dim=4, n_blocks=1, seq_len=16,
        n_experts=2,
    )
    assert cfg.dtype == "bfloat16"
    tr = tfm.HybridTrainer(env, cfg, 2, 1, 2, batch=2, lr=0.1,
                           devices=env.devices[:4])
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 32, size=(2, 16)).astype(np.int32)
    st, sl = tr.shard_tokens(toks, np.roll(toks, -1, axis=1))
    loss = tr.step(st, sl)
    assert np.isfinite(float(np.asarray(loss))), loss


def test_donate_params_escape(env):
    """donate_params=False keeps previous param trees readable after a fused
    step (EMA/debug snapshots); default donation still trains to the oracle.
    (ADVICE r2: the donation contract must be optional and documented.)"""
    toks, labels = _data(2)

    tr = tfm.HybridTrainer(env, CFG, 1, 1, 1, batch=2, lr=0.5,
                           devices=env.devices[:1], donate_params=False)
    assert tr._fused_fn is not None  # the no-comm fused path is what donates
    old_leaf = jax.tree.leaves(tr.params)[0]
    st, sl_ = tr.shard_tokens(toks, labels)
    tr.step(st, sl_)
    np.asarray(old_leaf)  # must still be readable: not donated

    tr2 = tfm.HybridTrainer(env, CFG, 1, 1, 1, batch=2, lr=0.5,
                            devices=env.devices[:1])  # default: donate
    assert tr2.donate_params
    st2, sl2 = tr2.shard_tokens(toks, labels)
    for _ in range(2):
        tr2.step(st2, sl2)
    ref_params = tfm.init_params(jax.random.PRNGKey(0), CFG)
    ref_params, _ = _oracle_steps(ref_params, toks, labels, 0.5, 2)
    _assert_params_close(tr2, ref_params)


@pytest.mark.parametrize("dp,sp,tp", [(1, 4, 2), (2, 4, 1), (1, 8, 1)])
def test_hybrid_zigzag_matches_oracle(env, dp, sp, tp):
    """Zigzag sequence parallelism trains to the SAME parameters as the dense
    single-device oracle: the trainer permutes tokens/labels and the position
    rows follow, so only the attention schedule changes."""
    cfg = dataclasses.replace(CFG, attention="zigzag")
    b = 2 * dp
    trainer = tfm.HybridTrainer(env, cfg, dp, sp, tp, batch=b, lr=0.5,
                                devices=env.devices[: dp * sp * tp])
    toks, labels = _data(b)
    ref_params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    st, sl_ = trainer.shard_tokens(toks, labels)
    losses = []
    for _ in range(2):
        losses.append(float(trainer.step(st, sl_)))
    ref_params, ref_loss = _oracle_steps(ref_params, toks, labels, 0.5, 2,
                                         cfg=dataclasses.replace(cfg, attention="ring"))
    _assert_params_close(trainer, ref_params)
    assert np.isfinite(losses).all()
    # loss at the post-2-update parameters must equal the oracle's
    np.testing.assert_allclose(float(trainer.step(st, sl_)), ref_loss, rtol=1e-3)


@pytest.mark.parametrize("dp,sp,tp", [(2, 2, 2), (8, 1, 1)])
def test_remat_matches_no_remat(env, dp, sp, tp):
    """cfg.remat wraps each block in jax.checkpoint — the backward replays the
    block (incl. ring-hop collectives) instead of saving intermediates. The
    replayed ops are the same deterministic programs, so the trajectory must
    match the non-remat run to f32 tolerance across the hybrid grid."""
    cfg_r = dataclasses.replace(CFG, remat=True)
    b = 2 * dp
    toks, labels = _data(b)
    results = []
    for cfg in (CFG, cfg_r):
        trainer = tfm.HybridTrainer(env, cfg, dp, sp, tp, batch=b, lr=0.5,
                                    devices=env.devices[: dp * sp * tp])
        st, sl_ = trainer.shard_tokens(toks, labels)
        losses = [float(trainer.step(st, sl_)) for _ in range(2)]
        results.append((losses, jax.device_get(trainer.params)))
    (l0, p0), (l1, p1) = results
    np.testing.assert_allclose(l0, l1, atol=1e-6, rtol=1e-6)
    for a, b_ in zip(jax.tree.leaves(p0), jax.tree.leaves(p1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=1e-6, rtol=1e-6)


def test_remat_dots_policy_matches_full(env):
    """remat_policy='dots' (checkpoint_dots: matmul outputs saved, elementwise
    replayed) must stay on the identical trajectory — only the memory/FLOP
    trade differs; unknown policies fail loudly."""
    b = 4
    toks, labels = _data(b)
    results = []
    for cfg in (dataclasses.replace(CFG, remat=True),
                dataclasses.replace(CFG, remat=True, remat_policy="dots")):
        trainer = tfm.HybridTrainer(env, cfg, 2, 2, 2, batch=b, lr=0.5,
                                    devices=env.devices[:8])
        st, sl_ = trainer.shard_tokens(toks, labels)
        losses = [float(trainer.step(st, sl_)) for _ in range(2)]
        results.append((losses, jax.device_get(trainer.params)))
    (l0, p0), (l1, p1) = results
    np.testing.assert_allclose(l0, l1, atol=1e-6, rtol=1e-6)
    for a, b_ in zip(jax.tree.leaves(p0), jax.tree.leaves(p1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=1e-6, rtol=1e-6)

    import mlsl_tpu

    with pytest.raises(mlsl_tpu.MLSLError):
        bad = dataclasses.replace(CFG, remat=True, remat_policy="nope")
        tr = tfm.HybridTrainer(env, bad, 2, 2, 2, batch=b, lr=0.5,
                               devices=env.devices[:8])
        st, sl_ = tr.shard_tokens(toks, labels)
        tr.step(st, sl_)


def test_remat_replays_forward(env):
    """cfg.remat must actually re-run the block forwards in the backward:
    the compiled fused step's cost-model FLOPs grow by roughly the one extra
    forward (+1/4 to +1/3 of the plain 3x-forward step). The MEMORY win is a
    TPU-backend liveness property — XLA:CPU's temp accounting does not
    reflect it (measured: remat temp slightly LARGER on CPU at d128 x 8blk x
    s512), so on-chip evidence is the training cell's `peak_hbm_gib`, not this
    test."""
    cfg = dataclasses.replace(
        CFG, n_blocks=8, seq_len=512, d_model=128, n_heads=4, head_dim=32
    )
    cfg_r = dataclasses.replace(cfg, remat=True)
    b = 4
    toks, labels = _data_cfg(b, cfg)
    flops = {}
    for key, c in (("plain", cfg), ("remat", cfg_r)):
        trainer = tfm.HybridTrainer(env, c, 1, 1, 1, batch=b, lr=0.5,
                                    devices=env.devices[:1])
        st, sl_ = trainer.shard_tokens(toks, labels)
        compiled = trainer.compiled_step(st, sl_)
        assert compiled is not None
        try:
            ca = compiled.cost_analysis()
            ca = ca[0] if isinstance(ca, (list, tuple)) else ca
            flops[key] = float(ca.get("flops", 0.0))
        except Exception as e:  # pragma: no cover - backend-dependent surface
            pytest.skip(f"cost_analysis unavailable: {e}")
    assert flops["plain"] > 0
    ratio = flops["remat"] / flops["plain"]
    assert 1.15 < ratio < 1.45, flops


def _data_cfg(b, cfg, seed=0):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, cfg.vocab, size=(b, cfg.seq_len)).astype(np.int32)
    labels = rng.integers(0, cfg.vocab, size=(b, cfg.seq_len)).astype(np.int32)
    return toks, labels
