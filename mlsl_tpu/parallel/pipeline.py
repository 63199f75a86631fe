"""Pipeline parallelism: GPipe-style microbatch schedule over the model axis.

The reference declares (but never implements) the point-to-point primitive a pipeline
needs — SendRecvList (src/comm.hpp:212-248). This module is that capability completed:
pipeline stages live on the 'model' mesh axis, microbatch activations flow stage->
stage+1 via lax.ppermute (the SendRecvList realization), and a fill-drain schedule
keeps every stage busy once the pipeline is full. Differentiating through the schedule
gives the reversed (drain-fill) backward automatically — JAX transposes ppermute to
the opposite shift — so training just calls jax.grad on the pipelined loss.

Usage (inside or outside shard_map via the provided driver):
    out = gpipe_forward(stage_fn, stage_params, x_micro, axis, n_stages)
with stage_fn(params, x) -> y applied at every stage (all stages share the fn shape;
per-stage weights differ — the usual homogeneous-blocks pipeline).
"""

from __future__ import annotations

from typing import Callable

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from mlsl_tpu.comm import algos
from mlsl_tpu.parallel.sequence import _pvary

# The stage->stage boundary ppermutes below stay RAW in-graph collectives
# (per-site pragmas): they are this module's primitive — the SendRecvList
# realization — and must remain lax.ppermute so jax.grad transposes them
# into the drain-fill backward. Everything reduction-shaped (the microbatch
# loss sums and the data-parallel gradient reduction) routes through the
# collective engine instead (comm/algos inline helpers / overlap engine),
# so the selection table, breakers, and stats see it.


def gpipe_forward(
    stage_fn: Callable,
    stage_params,
    x_micro: jax.Array,
    axis: str,
    n_stages: int,
    remat: bool = False,
):
    """SPMD body (call inside shard_map over ``axis`` of size n_stages).

    stage_params: this stage's weights (the caller shards them over ``axis``).
    x_micro: (M, mb, d_in) microbatches — the stage-0 input (replicated copies on
    other stages are ignored).
    remat: wrap the stage in jax.checkpoint so the backward replay recomputes
    stage internals instead of storing per-tick activations — bounds pipeline
    activation memory by the stage boundary size rather than the stage interior
    (the practical core of the 1F1B memory benefit).
    Returns (M, mb, d_out): the last stage's outputs (zeros elsewhere; reduce with
    a psum/select or read the last stage's shard).
    """
    if remat:
        # prevent_cse=False: XLA never CSEs across loop iterations, so inside the
        # fori/scan body the default's optimization barriers would only block fusion
        stage_fn = jax.checkpoint(stage_fn, prevent_cse=False)
    m_count, mb, _ = x_micro.shape
    me = lax.axis_index(axis)
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    ticks = m_count + n_stages - 1

    probe = jax.eval_shape(stage_fn, stage_params, x_micro[0])
    d_out = probe.shape[-1]
    assert d_out == x_micro.shape[-1], (
        "pipeline boundary width mismatch: stage_fn maps wire width "
        f"{x_micro.shape[-1]} -> {d_out}; pad heterogeneous stages to a common "
        "wire width (see pad_stage_weights)"
    )

    like = (probe, x_micro)
    outs = _pvary(jnp.zeros((m_count, mb, d_out), probe.dtype), axis, like)
    recv = _pvary(jnp.zeros((mb, d_out), probe.dtype), axis, like)

    def tick(t, state):
        recv, outs = state
        mb_idx = t - me                       # which microbatch this stage handles
        active = jnp.logical_and(mb_idx >= 0, mb_idx < m_count)
        safe_idx = jnp.clip(mb_idx, 0, m_count - 1)
        inp = jnp.where(
            me == 0,
            lax.dynamic_index_in_dim(x_micro, safe_idx, axis=0, keepdims=False),
            recv,
        )
        y = stage_fn(stage_params, inp)
        y = jnp.where(active, y, jnp.zeros_like(y))
        # last stage banks its result for microbatch mb_idx (same clamped index
        # as the input selection)
        is_last = me == n_stages - 1
        banked = lax.dynamic_update_index_in_dim(
            outs,
            jnp.where(jnp.logical_and(is_last, active), y,
                      lax.dynamic_index_in_dim(outs, safe_idx, axis=0, keepdims=False)),
            safe_idx,
            axis=0,
        )
        # boundary transfer: stage s -> s+1 (the SendRecvList ring)
        recv_next = lax.ppermute(y, axis, perm)  # mlsl-lint: disable=A201 -- boundary primitive
        return recv_next, banked

    _, outs = lax.fori_loop(0, ticks, tick, (recv, outs))
    return outs


def pad_stage_weights(weights, biases, boundary_dims):
    """Make heterogeneous-width pipeline stages wire-uniform by zero-padding.

    ppermute moves fixed-shape buffers, so differing boundary widths ride a wire
    padded to d_wire = max(boundary_dims); padding a stage's (d_in, d_out) weight
    matrix into (d_wire, d_wire) with zeros makes the padded lanes self-annihilating
    — y_pad = [y, 0...] exactly, provided the stage activation maps 0 to 0 (tanh,
    relu, gelu do; add biases only on real lanes, which the padded bias guarantees).

    weights[s]: (d_in_s, d_out_s) with d_in_s = boundary_dims[s],
    d_out_s = boundary_dims[s+1]; biases[s]: (d_out_s,).
    -> (stacked (S, d_wire, d_wire), stacked (S, d_wire), d_wire), in the weights'
    own dtype. The caller pads its input to d_wire and slices the output to
    boundary_dims[-1].
    """
    d_wire = max(boundary_dims)
    s_count = len(weights)
    dtype = np.asarray(weights[0]).dtype
    w_pad = np.zeros((s_count, d_wire, d_wire), dtype)
    b_pad = np.zeros((s_count, d_wire), dtype)
    for s in range(s_count):
        d_in, d_out = boundary_dims[s], boundary_dims[s + 1]
        assert weights[s].shape == (d_in, d_out), (
            f"stage {s}: weight {weights[s].shape} != ({d_in}, {d_out})"
        )
        w_pad[s, :d_in, :d_out] = weights[s]
        b_pad[s, :d_out] = biases[s]
    return w_pad, b_pad, d_wire


def f1b_schedule(n_stages: int, m_count: int) -> dict:
    """Static 1F1B schedule facts (for tests/telemetry, no tracing).

    Tick model: stage s runs forward of microbatch i at tick 2i+s and backward of
    i at tick 2i+2S-1-s. F ticks have parity (t-s) even, B ticks odd, so each
    stage does exactly one op per tick in steady state (the 1F1B alternation).
    """
    S, M = n_stages, m_count
    ticks = 2 * M + 2 * S - 2
    busy = 2 * M * S  # one F + one B per (stage, microbatch)
    return {
        "ticks": ticks,
        "utilization": busy / (ticks * S),
        "bubble_fraction": 1.0 - busy / (ticks * S),
        # microbatches resident between their F and B at stage s: S - s, vs
        # GPipe's M at every stage — the 1F1B memory bound.
        "peak_in_flight": [S - s for s in range(S)],
        "gpipe_peak_in_flight": [M] * S,
    }


def one_f1b_step(
    stage_fn: Callable,
    loss_head: Callable,
    stage_params,
    x_micro: jax.Array,
    y_micro: jax.Array,
    axis: str,
    n_stages: int,
):
    """1F1B pipeline schedule: (loss, stage_grads) without O(M) activation memory.

    SPMD body (call inside shard_map over ``axis``). Unlike differentiating
    ``pipeline_loss`` (GPipe: full forward sweep, then the autodiff-transposed
    sweep, saving residuals for every one of the M microbatches), this interleaves
    each microbatch's backward one-forward-one-backward style, so a stage holds at
    most S - s in-flight boundary activations (f1b_schedule). The backward leg
    rematerializes the stage from its saved INPUT (explicit remat: only the (mb, d)
    boundary tensor is stored, stage internals are recomputed in the vjp), and the
    tick loop itself is never differentiated — gradients come from per-tick
    jax.vjp calls, accumulated directly.

    Wire realization of the reference's declared-but-unimplemented SendRecvList
    p2p primitive (src/comm.hpp:212-248): forward boundary rides ppermute(+1),
    gradient boundary rides ppermute(-1), both every tick.

    Requires stage_fn to preserve the wire width (see pad_stage_weights) and
    loss_head(y, target) -> scalar. Returns (psum'd scalar loss, grads for THIS
    stage's params).
    """
    m_count, mb, d = x_micro.shape
    S = n_stages
    me = lax.axis_index(axis)
    fwd_perm = [(i, (i + 1) % S) for i in range(S)]
    bwd_perm = [(i, (i - 1) % S) for i in range(S)]
    ticks = 2 * m_count + 2 * S - 2

    probe = jax.eval_shape(stage_fn, stage_params, x_micro[0])
    assert probe.shape[-1] == d, (
        f"pipeline boundary width mismatch: {d} -> {probe.shape[-1]}"
    )

    # In-flight boundary inputs: slot i % S is free again strictly before
    # microbatch i+S forwards (B_i at tick 2i+2S-1-2s < F_{i+S} at 2i+2S+s...
    # equality never holds since parities differ at s=0: 2i+2S-1 < 2i+2S).
    like = (probe, x_micro)
    x_buf = _pvary(jnp.zeros((S, mb, d), probe.dtype), axis, like)
    recv_f = _pvary(jnp.zeros((mb, d), probe.dtype), axis, like)
    recv_b = _pvary(jnp.zeros((mb, d), probe.dtype), axis, like)
    grads0 = jax.tree.map(lambda p: jnp.zeros_like(p), stage_params)
    is_last = me == S - 1

    def tick(t, state):
        recv_f, recv_b, x_buf, grads, loss_acc = state
        rel = t - me
        f_idx = rel // 2                      # floor div: negative -> inactive
        f_active = jnp.logical_and(rel % 2 == 0,
                                   jnp.logical_and(f_idx >= 0, f_idx < m_count))
        b_idx = (t + me - (2 * S - 1)) // 2
        b_active = jnp.logical_and(rel % 2 != 0,
                                   jnp.logical_and(b_idx >= 0, b_idx < m_count))
        f_slot = jnp.clip(f_idx, 0, m_count - 1) % S
        b_slot = jnp.clip(b_idx, 0, m_count - 1) % S

        def f_branch(args):
            recv_f, recv_b, x_buf, grads, loss_acc = args
            inp = jnp.where(
                me == 0,
                lax.dynamic_index_in_dim(
                    x_micro, jnp.clip(f_idx, 0, m_count - 1), 0, keepdims=False
                ),
                recv_f,
            )
            y = stage_fn(stage_params, inp)
            x_buf = jnp.where(
                f_active,
                lax.dynamic_update_index_in_dim(x_buf, inp, f_slot, axis=0),
                x_buf,
            )
            send_f = jnp.where(f_active, y, jnp.zeros_like(y))
            return x_buf, grads, loss_acc, send_f, jnp.zeros((mb, d), probe.dtype)

        def b_branch(args):
            recv_f, recv_b, x_buf, grads, loss_acc = args
            x_saved = lax.dynamic_index_in_dim(x_buf, b_slot, 0, keepdims=False)
            y, vjp = jax.vjp(stage_fn, stage_params, x_saved)
            target = lax.dynamic_index_in_dim(
                y_micro, jnp.clip(b_idx, 0, m_count - 1), 0, keepdims=False
            )
            loss_val, dy_last = jax.value_and_grad(loss_head)(y, target)
            dy = jnp.where(is_last, dy_last, recv_b)
            dp, dx = vjp(dy)
            grads = jax.tree.map(
                lambda g, d_: g + jnp.where(b_active, d_, jnp.zeros_like(d_)),
                grads, dp,
            )
            loss_acc = loss_acc + jnp.where(
                jnp.logical_and(is_last, b_active), loss_val, 0.0
            )
            send_b = jnp.where(b_active, dx, jnp.zeros_like(dx))
            return x_buf, grads, loss_acc, jnp.zeros((mb, d), probe.dtype), send_b

        # F and B parities are disjoint, so exactly one branch runs per tick per
        # stage; the branches hold no collectives, so divergent per-device
        # control flow is safe (the ppermutes below are unconditional).
        x_buf, grads, loss_acc, send_f, send_b = lax.cond(
            rel % 2 == 0, f_branch, b_branch,
            (recv_f, recv_b, x_buf, grads, loss_acc),
        )
        recv_f = lax.ppermute(send_f, axis, fwd_perm)  # mlsl-lint: disable=A201 -- boundary primitive
        recv_b = lax.ppermute(send_b, axis, bwd_perm)  # mlsl-lint: disable=A201 -- boundary primitive
        return recv_f, recv_b, x_buf, grads, loss_acc

    _, _, _, grads, loss_acc = lax.fori_loop(
        0, ticks, tick, (recv_f, recv_b, x_buf, grads0, jnp.float32(0.0))
    )
    return algos.inline_allreduce(loss_acc, axis), grads


def interleaved_schedule(n_stages: int, v_chunks: int, m_count: int) -> dict:
    """Static interleaved-1F1B schedule (Megatron-style virtual stages), host-side.

    The model is split into v*S stages; device d holds chunks c=0..v-1 as global
    stages k = c*S + d, so every stage->stage+1 boundary is still a +1 ring hop
    (device S-1 wraps to device 0, chunk c+1) and the backward boundary a -1 hop.
    The schedule is built by greedy list-scheduling of the dependency DAG, one op
    per device per tick: backward ops take priority (the 1F1B memory discipline),
    remaining forward ops run deepest-chunk-first (depth-first fill, which is what
    shrinks the bubble by ~v: the last device starts after S-1 hops and then stays
    busy across its v chunks, instead of waiting for a v*S-deep fill).

    Returns numpy tables (ticks, S) describing each device's op per tick plus the
    receiver-side staging-store tables, and slot counts sized so no staged buffer
    is overwritten before consumption (verified by construction below).
    """
    S, V, M = int(n_stages), int(v_chunks), int(m_count)
    assert S >= 1 and V >= 1 and M >= 1
    K_tot = V * S

    # --- greedy list scheduling -> t_f[k, i], t_b[k, i] ---------------------
    t_f = np.full((K_tot, M), -1, dtype=np.int64)
    t_b = np.full((K_tot, M), -1, dtype=np.int64)
    done_f = np.zeros((K_tot, M), dtype=bool)
    done_b = np.zeros((K_tot, M), dtype=bool)
    # Each device follows a FIXED op sequence (Megatron's discipline): W warmup
    # forwards, then strict F/B alternation (1F1B steady state), then cooldown
    # backwards. Forwards walk microbatch groups of S with chunks ascending;
    # backwards walk the same groups with chunks descending (the deepest chunk
    # drains first). A device whose next op isn't ready idles that tick — the
    # schedule stays synchronous and the in-flight memory is bounded by W+1.
    def _group_order(desc):
        order = []
        for g in range(0, M, S):
            span = range(g, min(g + S, M))
            chunks = range(V - 1, -1, -1) if desc else range(V)
            for c in chunks:
                order.extend((c, i) for i in span)
        return order

    n_ops = V * M
    seqs = []
    for d in range(S):
        if V == 1:
            warm = min(S - d - 1, n_ops)
        else:
            warm = min((S - d - 1) * 2 + (V - 1) * S, n_ops)
        f_seq = _group_order(desc=False)
        b_seq = _group_order(desc=True)
        kinds = ["F"] * warm
        for _ in range(n_ops - warm):
            kinds += ["F", "B"]
        kinds += ["B"] * warm
        fi = bi = 0
        seq = []
        for kind in kinds:
            if kind == "F":
                c, i = f_seq[fi]
                fi += 1
            else:
                c, i = b_seq[bi]
                bi += 1
            seq.append((kind, c * S + d, i))
        seqs.append(seq)

    def _f_ready(k, i, t):
        # the upstream forward must have completed on an EARLIER tick (the
        # boundary rides a one-tick ppermute)
        return not done_f[k, i] and (
            k == 0 or (done_f[k - 1, i] and t_f[k - 1, i] < t)
        )

    def _b_ready(k, i, t):
        return (
            not done_b[k, i]
            and done_f[k, i]
            and t_f[k, i] < t
            and (k == K_tot - 1 or (done_b[k + 1, i] and t_b[k + 1, i] < t))
        )

    def _do(kind, k, i, t):
        if kind == "F":
            t_f[k, i] = t
            done_f[k, i] = True
        else:
            t_b[k, i] = t
            done_b[k, i] = True

    pos = [0] * S
    remaining = 2 * K_tot * M
    t = 0
    no_progress = 0
    while remaining > 0:
        progressed = False
        for d in range(S):
            if pos[d] >= len(seqs[d]):
                continue
            kind, k, i = seqs[d][pos[d]]
            ready = _f_ready(k, i, t) if kind == "F" else _b_ready(k, i, t)
            if ready:
                _do(kind, k, i, t)
                pos[d] += 1
                remaining -= 1
                progressed = True
        t += 1
        # Relief valve: arrivals matter for exactly one tick, so two consecutive
        # all-idle sweeps mean the fixed sequences deadlocked (possible only for
        # irregular M vs S); fall back to scheduling ANY ready op once, which
        # always exists for an unfinished DAG and restores progress.
        no_progress = 0 if progressed else no_progress + 1
        if no_progress >= 2:
            for d in range(S):
                pick = None
                for kk in range(d, K_tot, S):
                    for i in range(M):
                        if _f_ready(kk, i, t):
                            pick = ("F", kk, i)
                            break
                        if _b_ready(kk, i, t):
                            pick = ("B", kk, i)
                            break
                    if pick:
                        break
                if pick:
                    _do(*pick, t)
                    remaining -= 1
                    seqs[d].remove(pick)
            t += 1
            no_progress = 0
    ticks = t

    # --- minimal slot counts so slot reuse never clobbers live data ---------
    def _min_slots(write_t, read_t):
        # writing slot i%K at write_t[i+K] must not precede the read at read_t[i]
        for K in range(1, M + 1):
            ok = True
            for k in range(write_t.shape[0]):
                for i in range(M - K):
                    if write_t[k, i + K] < read_t[k, i]:
                        ok = False
                        break
                if not ok:
                    break
            if ok:
                return K
        return M

    # fwd staging at stage k (k>0): stored at end of t_f[k-1, i], read at t_f[k, i]
    k_f = _min_slots(t_f[:-1], t_f[1:]) if K_tot > 1 else 1
    # bwd staging at stage k (k<last): stored at end of t_b[k+1, i], read at t_b[k, i]
    k_b = _min_slots(t_b[1:], t_b[:-1]) if K_tot > 1 else 1
    # saved inputs at stage k: written during t_f[k, i], read at t_b[k, i]
    k_s = _min_slots(t_f, t_b)

    # --- per-tick tables ----------------------------------------------------
    kind_t = np.zeros((ticks, S), np.int32)          # 0 idle, 1 F, 2 B
    chunk_t = np.zeros((ticks, S), np.int32)
    micro_t = np.zeros((ticks, S), np.int32)
    first_t = np.zeros((ticks, S), np.int32)         # F reads x_micro (k == 0)
    last_t = np.zeros((ticks, S), np.int32)          # B computes loss grad (k == last)
    fstore_valid = np.zeros((ticks, S), np.int32)
    fstore_idx = np.zeros((ticks, S), np.int32)      # chunk*k_f + slot at receiver
    bstore_valid = np.zeros((ticks, S), np.int32)
    bstore_idx = np.zeros((ticks, S), np.int32)
    for k in range(K_tot):
        d, c = k % S, k // S
        for i in range(M):
            tf = t_f[k, i]
            kind_t[tf, d], chunk_t[tf, d], micro_t[tf, d] = 1, c, i
            first_t[tf, d] = int(k == 0)
            if k + 1 < K_tot:
                d2, c2 = (k + 1) % S, (k + 1) // S
                fstore_valid[tf, d2] = 1
                fstore_idx[tf, d2] = c2 * k_f + i % k_f
            tb = t_b[k, i]
            kind_t[tb, d], chunk_t[tb, d], micro_t[tb, d] = 2, c, i
            last_t[tb, d] = int(k == K_tot - 1)
            if k > 0:
                d2, c2 = (k - 1) % S, (k - 1) // S
                bstore_valid[tb, d2] = 1
                bstore_idx[tb, d2] = c2 * k_b + i % k_b
    busy = 2 * K_tot * M
    return {
        "tables": {
            "kind": kind_t, "chunk": chunk_t, "micro": micro_t,
            "first": first_t, "last": last_t,
            "fstore_valid": fstore_valid, "fstore_idx": fstore_idx,
            "bstore_valid": bstore_valid, "bstore_idx": bstore_idx,
        },
        "k_f": k_f, "k_b": k_b, "k_s": k_s,
        "ticks": ticks,
        "utilization": busy / (ticks * S),
        "bubble_fraction": 1.0 - busy / (ticks * S),
        "t_f": t_f, "t_b": t_b,
    }


def interleaved_1f1b_step(
    stage_fn: Callable,
    loss_head: Callable,
    chunk_params,
    x_micro: jax.Array,
    y_micro: jax.Array,
    axis: str,
    n_stages: int,
    v_chunks: int,
):
    """Interleaved (virtual-stage) 1F1B: (loss, per-chunk grads) for this device.

    SPMD body (call inside shard_map over ``axis`` of size n_stages).
    chunk_params: THIS device's v chunks stacked on axis 0 — chunk c is global
    stage c*S + d (reshape a (v*S, ...)-stacked model to (v, S, ...) and shard
    axis 1 over ``axis``). The whole schedule is precomputed host-side
    (interleaved_schedule) and baked into constant tables; the traced loop only
    gathers its per-tick op and runs it, so XLA sees a fixed-shape fori_loop with
    one stage eval (F) or one explicit-remat vjp (B) per tick — the same
    compute-per-tick as one_f1b_step, with the bubble cut ~v-fold.

    Reference anchor: the SendRecvList p2p primitive (src/comm.hpp:212-248);
    schedule shape follows Megatron-LM's interleaved 1F1B (PAPERS.md), rebuilt
    as a static table + ring ppermute pair for the TPU's fixed SPMD program.
    """
    m_count, mb, d_wire = x_micro.shape
    S, V = int(n_stages), int(v_chunks)
    sched = interleaved_schedule(S, V, m_count)
    tb = {k: jnp.asarray(v) for k, v in sched["tables"].items()}
    k_f, k_b, k_s = sched["k_f"], sched["k_b"], sched["k_s"]
    me = lax.axis_index(axis)
    fwd_perm = [(i, (i + 1) % S) for i in range(S)]
    bwd_perm = [(i, (i - 1) % S) for i in range(S)]

    probe = jax.eval_shape(
        stage_fn,
        jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape[1:], p.dtype), chunk_params),
        x_micro[0],
    )
    assert probe.shape[-1] == d_wire, (
        f"pipeline boundary width mismatch: {d_wire} -> {probe.shape[-1]}"
    )
    dt = probe.dtype

    like = (probe, x_micro)
    fwd_in = _pvary(jnp.zeros((V * k_f, mb, d_wire), dt), axis, like)
    bwd_in = _pvary(jnp.zeros((V * k_b, mb, d_wire), dt), axis, like)
    x_saved = _pvary(jnp.zeros((V * k_s, mb, d_wire), dt), axis, like)
    grads0 = jax.tree.map(lambda p: jnp.zeros_like(p), chunk_params)
    zero_wire = jnp.zeros((mb, d_wire), dt)

    def tick(t, state):
        fwd_in, bwd_in, x_saved, grads, loss_acc = state
        kind = tb["kind"][t, me]
        c = tb["chunk"][t, me]
        i = tb["micro"][t, me]
        params_c = jax.tree.map(
            lambda p: lax.dynamic_index_in_dim(p, c, 0, keepdims=False), chunk_params
        )
        save_idx = c * k_s + i % k_s

        def f_branch(args):
            fwd_in, bwd_in, x_saved, grads, loss_acc = args
            active = kind == 1
            inp = jnp.where(
                tb["first"][t, me] == 1,
                lax.dynamic_index_in_dim(x_micro, i, 0, keepdims=False),
                lax.dynamic_index_in_dim(fwd_in, c * k_f + i % k_f, 0, keepdims=False),
            )
            y = stage_fn(params_c, inp)
            x_saved = jnp.where(
                active,
                lax.dynamic_update_index_in_dim(x_saved, inp, save_idx, axis=0),
                x_saved,
            )
            send_f = jnp.where(active, y, jnp.zeros_like(y))
            return x_saved, grads, loss_acc, send_f, zero_wire

        def b_branch(args):
            fwd_in, bwd_in, x_saved, grads, loss_acc = args
            active = kind == 2
            x_in = lax.dynamic_index_in_dim(x_saved, save_idx, 0, keepdims=False)
            y, vjp = jax.vjp(stage_fn, params_c, x_in)
            target = lax.dynamic_index_in_dim(y_micro, i, 0, keepdims=False)
            loss_val, dy_last = jax.value_and_grad(loss_head)(y, target)
            dy = jnp.where(
                tb["last"][t, me] == 1,
                dy_last,
                lax.dynamic_index_in_dim(bwd_in, c * k_b + i % k_b, 0, keepdims=False),
            )
            dp, dx = vjp(dy)
            grads = jax.tree.map(
                lambda G, dd: lax.dynamic_update_index_in_dim(
                    G,
                    lax.dynamic_index_in_dim(G, c, 0, keepdims=False)
                    + jnp.where(active, dd, jnp.zeros_like(dd)),
                    c,
                    axis=0,
                ),
                grads,
                dp,
            )
            loss_acc = loss_acc + jnp.where(
                jnp.logical_and(tb["last"][t, me] == 1, active),
                loss_val.astype(jnp.float32),
                0.0,
            )
            send_b = jnp.where(active, dx, jnp.zeros_like(dx))
            return x_saved, grads, loss_acc, zero_wire, send_b

        x_saved, grads, loss_acc, send_f, send_b = lax.cond(
            kind == 2, b_branch, f_branch,
            (fwd_in, bwd_in, x_saved, grads, loss_acc),
        )
        recv_f = lax.ppermute(send_f, axis, fwd_perm)  # mlsl-lint: disable=A201 -- boundary primitive
        recv_b = lax.ppermute(send_b, axis, bwd_perm)  # mlsl-lint: disable=A201 -- boundary primitive
        fwd_in = jnp.where(
            tb["fstore_valid"][t, me] == 1,
            lax.dynamic_update_index_in_dim(
                fwd_in, recv_f, tb["fstore_idx"][t, me], axis=0
            ),
            fwd_in,
        )
        bwd_in = jnp.where(
            tb["bstore_valid"][t, me] == 1,
            lax.dynamic_update_index_in_dim(
                bwd_in, recv_b, tb["bstore_idx"][t, me], axis=0
            ),
            bwd_in,
        )
        return fwd_in, bwd_in, x_saved, grads, loss_acc

    _, _, _, grads, loss_acc = lax.fori_loop(
        0, sched["ticks"], tick,
        (fwd_in, bwd_in, x_saved, grads0, jnp.float32(0.0)),
    )
    return algos.inline_allreduce(loss_acc, axis), grads


def pipeline_loss(
    stage_fn: Callable,
    loss_head: Callable,
    stage_params,
    x_micro: jax.Array,
    y_micro: jax.Array,
    axis: str,
    n_stages: int,
    remat: bool = False,
):
    """Pipelined forward + loss on the last stage, psum'd so every stage holds the
    scalar (ready for jax.grad: the backward replays the schedule in reverse)."""
    outs = gpipe_forward(stage_fn, stage_params, x_micro, axis, n_stages, remat=remat)
    me = lax.axis_index(axis)
    per_micro = jax.vmap(loss_head)(outs, y_micro)          # (M,)
    local = jnp.where(me == n_stages - 1, jnp.sum(per_micro), 0.0)
    return algos.inline_allreduce(local, axis)


def reduce_microbatch_grads(
    group,
    counts,
    *,
    config=None,
    compression=None,
    algo=None,
    stages=None,
    block=None,
):
    """Data-parallel reduction of pipeline stage gradients THROUGH the
    collective engine: -> (fn, plan) from comm/overlap.build_multi_reduce.

    After a 1F1B step each stage holds its microbatch-accumulated stage
    grads; replicating the pipeline across a data axis leaves one reduction
    to run — this builds it as the engine's staged multi-tensor program, so
    the selection table applies per tensor (on a two-tier world that is the
    hierarchical 'hier' lowering, with the compressed DCN hop when
    ``compression=QUANTIZATION``), the emission is staged newest-first, and
    error-feedback residuals ride the returned-state convention. ``fn``
    takes the flattened per-stage grad tensors as standard distributed
    buffers (reversed start order = backward emission order), exactly
    build_multi_reduce's contract."""
    from mlsl_tpu.comm import overlap
    from mlsl_tpu.types import CompressionType

    kw = {}
    if stages is not None:
        kw["stages"] = stages
    if block is not None:
        kw["block"] = block
    return overlap.build_multi_reduce(
        group, list(counts),
        compression=(compression if compression is not None
                     else CompressionType.NONE),
        algo=algo, config=config, **kw,
    )
