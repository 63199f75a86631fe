"""Transformer training throughput at a realistic long-context config.

The bench.py transformer row uses a tiny d512/seq-512 model where the vocab
projection dominates; this harness measures the long-context workload family
the framework is built for: a GPT-medium-shaped model (d1024 x 16 heads x
12 blocks) at seq 2048 with the flash causal kernel, through the
HybridTrainer on the attached device. Reports tok/s plus achieved TFLOP/s
and MFU from XLA's cost model on the compiled step.

Single chip: dp=sp=tp=1 (groups degenerate — this is the compute headline;
the multi-chip sharding evidence is the CPU-mesh suite). Batch auto-halves
on OOM. One JSON row per config on stdout.
"""

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

from benchmarks._common import model_flops, setup_chip, timed

jax = setup_chip()

import jax.numpy as jnp  # noqa: E402


def peak_tflops(kind: str) -> float:
    from bench import _peak_tflops

    return _peak_tflops(kind)


def run_config(env, name, cfg, batch):
    from mlsl_tpu.models import transformer as tfm

    trainer = tfm.HybridTrainer(
        env, cfg, 1, 1, 1, batch=batch, lr=0.1, devices=env.devices[:1]
    )
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab, size=(batch, cfg.seq_len)).astype(np.int32)
    labels = np.roll(toks, -1, axis=1)
    tb, lb = trainer.shard_tokens(toks, labels)

    ms = timed(lambda: trainer.step(tb, lb), iters=24, warmup=4, blocks=6)
    tokens = batch * cfg.seq_len
    row = {
        "metric": "transformer_train_step",
        "config": name,
        "d_model": cfg.d_model,
        "n_blocks": cfg.n_blocks,
        "seq_len": cfg.seq_len,
        "batch": batch,
        "step_ms": round(ms, 3),
        "tok_s": round(tokens / (ms / 1e3)),
    }
    # an unlisted TPU kind raises (bench._peak_tflops); the CPU (--quick) has
    # no peak and reports no MFU
    peak = (peak_tflops(jax.devices()[0].device_kind)
            if jax.default_backend() == "tpu" else None)
    # mfu_model = canonical model-FLOPs MFU (analytic, remat-comparable) —
    # needs nothing from the XLA cost model
    mf = model_flops(cfg, batch)
    if peak and mf:
        row["mfu_model"] = round(mf / (ms / 1e3) / 1e12 / peak, 4)
    # achieved TFLOP/s + MFU (executed-program utilization: counts remat
    # recompute) from the compiled step's own cost model
    try:
        compiled = trainer.compiled_step(tb, lb)
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        flops = float(ca.get("flops", 0.0))
        if flops > 0:
            tf = flops / (ms / 1e3) / 1e12
            row["tflops"] = round(tf, 3)
            if peak:
                row["mfu"] = round(tf / peak, 4)
    except Exception as e:
        print(f"transformer_bench: cost_analysis unavailable ({e})",
              file=sys.stderr)
    print(json.dumps(row), flush=True)
    return row


def main():
    import argparse

    import mlsl_tpu as mlsl
    from mlsl_tpu.models import transformer as tfm

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="tiny config (CI smoke on the CPU backend)")
    args = ap.parse_args()

    env = mlsl.Environment.get_env().init()

    if args.quick:
        configs = [
            ("quick-d64", tfm.TransformerConfig(
                vocab=512, d_model=64, n_heads=4, head_dim=16,
                n_blocks=2, seq_len=128), 4),
        ]
    else:
        configs = [
            ("gpt-medium-2k", tfm.TransformerConfig(
                vocab=32768, d_model=1024, n_heads=16, head_dim=64,
                n_blocks=12, seq_len=2048), 8),
            ("gpt-medium-2k-remat", tfm.TransformerConfig(
                vocab=32768, d_model=1024, n_heads=16, head_dim=64,
                n_blocks=12, seq_len=2048, remat=True), 8),
            ("gpt-medium-2k-remat-dots", tfm.TransformerConfig(
                vocab=32768, d_model=1024, n_heads=16, head_dim=64,
                n_blocks=12, seq_len=2048, remat=True,
                remat_policy="dots"), 8),
            # long-context single-chip row: at seq 8k the plain step's saved
            # activations overflow a 16 GiB v5e — remat makes it fit
            ("gpt-medium-8k-remat", tfm.TransformerConfig(
                vocab=32768, d_model=1024, n_heads=16, head_dim=64,
                n_blocks=12, seq_len=8192, remat=True), 2),
            # 'dots' keeps matmul/attention outputs and replays only
            # elementwise work — the cheaper long-context remat when the
            # saved O(blocks*S*d) bytes still fit
            ("gpt-medium-8k-remat-dots", tfm.TransformerConfig(
                vocab=32768, d_model=1024, n_heads=16, head_dim=64,
                n_blocks=12, seq_len=8192, remat=True,
                remat_policy="dots"), 2),
            ("d512-8blk-512", tfm.TransformerConfig(
                vocab=32768, d_model=512, n_heads=8, head_dim=64,
                n_blocks=8, seq_len=512), 32),
        ]
    for name, cfg, batch in configs:
        while batch >= 1:
            try:
                run_config(env, name, cfg, batch)
                break
            except Exception as e:
                s = str(e)
                if batch > 1 and ("RESOURCE_EXHAUSTED" in s or "emory" in s):
                    print(f"transformer_bench: {name} batch {batch} OOM; "
                          f"halving", file=sys.stderr)
                    batch //= 2
                    continue
                raise


if __name__ == "__main__":
    main()
