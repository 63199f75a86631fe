"""On-chip Pallas kernel validation + timing: flash attention and int8 quant.

The CPU-mesh suite exercises these kernels in interpret mode only
(tests/test_flash.py, tests/test_quant.py); this script compiles the real
pallas_call programs on the attached accelerator, checks them against the XLA
reference implementations, and times both sides. One JSON line per kernel:
{"kernel", "ok", "max_err", "pallas_ms", "xla_ms", "speedup"}.

Needs a TPU backend in this process: off the chip it exits non-zero and
prints no row.
"""

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


from benchmarks._common import setup_chip
from benchmarks._common import timed_scan as _time_scan


def main():
    jax = setup_chip()
    import jax.numpy as jnp
    import numpy as np

    if jax.default_backend() != "tpu":
        # the compiled (non-interpret) Pallas timings this script exists for
        # are TPU-only; interpret-mode numbers would be meaningless
        sys.exit("kernels_on_chip: needs a TPU backend "
                 f"(got {jax.default_backend()})")

    from mlsl_tpu.ops import attention_kernels as ak
    from mlsl_tpu.ops import quant_kernels as qk

    results = []

    def _retry_scan(step, carry, iters):
        # a floored timed_scan (1 µs) means the paired difference went
        # negative under a load spike — remeasure, then give up honestly
        for _ in range(3):
            ms = _time_scan(step, carry, iters=iters)
            if ms > 2e-3:
                return ms
        return None

    def _row(name, ok, err, p_ms, x_ms):
        row = {"kernel": name, "ok": ok, "max_err": err,
               "pallas_ms": None if p_ms is None else round(p_ms, 3),
               "xla_ms": None if x_ms is None else round(x_ms, 3)}
        if p_ms is None or x_ms is None:
            row["speedup"] = None
            row["floored"] = True  # never fabricate a ratio from the floor
        else:
            row["speedup"] = round(x_ms / p_ms, 3)
        return row

    # --- flash attention fwd (+bwd), causal, long-ish sequence. D=128 is the
    # kernel's best case; D=64 is the head_dim the GPT-shaped bench configs
    # actually run (half the MXU contraction depth) ---
    BH, S, D = 8, 2048, 128
    rng = np.random.default_rng(0)
    off = jnp.zeros((1,), jnp.int32)

    def mk(d):
        return tuple(
            jnp.asarray(rng.normal(size=(BH, S, d)).astype(np.float32)) * 0.3
            for _ in range(3)
        )

    q, k, v = mk(D)

    # scan-timing: the attention output is a convex combination of v rows, so
    # feeding it back as the next q keeps the carry bounded for any length
    def _attn_step(f):
        return lambda c: (f(c[0], c[1], c[2]), c[1], c[2])

    for d, causal in ((128, False), (128, True), (64, True)):
        name = f"flash_fwd_{'causal' if causal else 'full'}"
        if d != D:
            name += f"_d{d}"
        qd, kd, vd = (q, k, v) if d == D else mk(d)
        fl = lambda q, k, v: ak.flash_attention(q, k, v, off, off, causal=causal)
        ref = lambda q, k, v: ak._reference_attention(q, k, v, off, off, causal)
        got, want = jax.jit(fl)(qd, kd, vd), jax.jit(ref)(qd, kd, vd)
        err = float(jnp.max(jnp.abs(got - want)))
        p_ms = _retry_scan(_attn_step(fl), (qd, kd, vd), 100)
        x_ms = _retry_scan(_attn_step(ref), (qd, kd, vd), 100)
        results.append(_row(name, err < 2e-2, round(err, 5), p_ms, x_ms))

    # fwd+bwd through the custom vjp
    def fl_loss(q, k, v):
        return jnp.sum(ak.flash_attention(q, k, v, off, off, causal=True) ** 2)

    def ref_loss(q, k, v):
        return jnp.sum(ak._reference_attention(q, k, v, off, off, True) ** 2)

    fl_g = jax.jit(jax.grad(fl_loss, argnums=(0, 1, 2)))
    ref_g = jax.jit(jax.grad(ref_loss, argnums=(0, 1, 2)))
    gf, gr = fl_g(q, k, v), ref_g(q, k, v)
    err = float(max(jnp.max(jnp.abs(a - b)) for a, b in zip(gf, gr)))

    # carry stays pinned near the original inputs; tanh bounds the feedback
    def _grad_step(g):
        def step(c):
            dq, dk, dv = g(*c)
            return (q + 1e-3 * jnp.tanh(dq), k + 1e-3 * jnp.tanh(dk),
                    v + 1e-3 * jnp.tanh(dv))
        return step

    p_ms = _retry_scan(_grad_step(fl_g), (q, k, v), 50)
    x_ms = _retry_scan(_grad_step(ref_g), (q, k, v), 50)
    results.append(_row("flash_fwd_bwd_causal", err < 5e-2, round(err, 5),
                        p_ms, x_ms))

    # --- int8 block quant, measured as the codec actually runs it: quantize
    # and dequantize SEPARATELY (the roundtrip comparison flatters XLA, which
    # fuses the two and never materializes the int8 wire buffer), at 256 MiB
    # so the working set exceeds VMEM and the kernels stream from HBM (a
    # 32 MiB scan carry stayed VMEM-resident and measured ~3 TB/s) ---
    from benchmarks._common import timed as _time_multi

    n = 64 * 1024 * 1024  # 256 MiB fp32
    x = jnp.asarray(rng.normal(size=(n // 256, 256)).astype(np.float32))

    qp = jax.jit(lambda x: qk._quantize_pallas(x))
    qr = jax.jit(lambda x: qk.quantize_blocks_ref(x))
    dp = jax.jit(lambda q, s: qk._dequantize_pallas(q, s))
    dr = jax.jit(lambda q, s: qk.dequantize_blocks_ref(q, s))
    qv, s = qp(x)
    qv_r, s_r = qr(x)
    # the two quantizers may differ by one ulp in a scale or by one code at a
    # rounding tie (round and divide are ordered differently): hold what they
    # decode to within one quantization step (both at once read a hair over
    # 1.0, hence the 1e-3), and record the measured distance in steps, never
    # a made-up 0 or 1
    deq = dr(qv, s)
    q_err = float(jnp.max(jnp.abs(deq - dr(qv_r, s_r)) / s_r[:, None]))
    # same (qv, s) on both sides: isolates the dequant kernel under test from
    # any one-ulp quantizer divergence
    err = float(jnp.max(jnp.abs(dp(qv, s) - deq)))

    def _t(f, *a):
        # iters=1800 -> 200-call arms (~100 ms paired diff on a ~0.5 ms
        # kernel); a floored result (1 µs) means the paired difference went
        # negative under a load spike — remeasure, then give up honestly
        for _ in range(3):
            ms = _time_multi(f, *a, iters=1800)
            if ms > 2e-3:
                return ms
        return None

    p_ms, x_ms = _t(qp, x), _t(qr, x)
    results.append(_row("quant_int8_256MiB", q_err <= 1.0 + 1e-3,
                        round(q_err, 6), p_ms, x_ms))
    p_ms = _t(dp, qv, s)
    x_ms = _t(dr, qv, s)
    results.append(_row("dequant_int8_256MiB", err < 1e-6,
                              round(err, 8), p_ms, x_ms))

    for r in results:
        print(json.dumps(r))
    if not all(r["ok"] for r in results):
        sys.exit(1)


if __name__ == "__main__":
    main()
