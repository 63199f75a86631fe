"""Sequence-parallel attention: ppermute ring and all-to-all (Ulysses) schedules.

Both functions are SPMD bodies — call them inside ``shard_map`` over a mesh that has
the given sequence axis. Inputs are the device-local shards:
    q, k, v: (batch, heads_local, seq_local, head_dim)

ring_attention: k/v blocks rotate around the ring via lax.ppermute while each device
keeps its query block, accumulating with the numerically-stable online-softmax
(flash-attention) update. Wire cost per step: one k+v block over the neighbor link —
the TPU-native realization of the reference's unimplemented SendRecvList
neighbor-exchange CommOp (src/comm.hpp:212-248). Supports causal masking via global
position arithmetic.

ulysses_attention: two all-to-alls switch sharding seq->heads and back (the reference's
redistribution-AlltoAll pattern, src/mlsl_impl.cpp:203-226, applied to the sequence
axis): attention itself runs with the full sequence but a head subset per device.
Requires heads_local divisible by the axis size.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from mlsl_tpu.log import mlsl_assert
from mlsl_tpu.sysinfo import on_tpu, pallas_interpret

_NEG = -1e30


def _pvary(x, axis, like=()):
    """Mark x as device-varying over ``axis`` and over every manual axis a
    leaf of ``like`` varies over: a loop carry must enter with the varying
    type its body produces. ``like`` takes arrays and ``jax.eval_shape``
    results alike — the abstract output of a stage function names every axis
    its body varies over, closures included (a pipeline stage inside a
    data x seq x model grid varies over all of them)."""
    axes = {axis}
    for leaf in jax.tree.leaves(like):
        vma = (leaf if isinstance(leaf, jax.ShapeDtypeStruct)
               else jax.typeof(leaf)).vma
        axes |= set(vma or ())
    return lax.pcast(x, tuple(sorted(axes)), to="varying")


def _attn_block_update(q, k_blk, v_blk, acc, m, l, q_pos, k_pos, causal, scale):
    """One online-softmax accumulation step.

    q: (B, H, Sq, D); k_blk/v_blk: (B, H, Sk, D); acc: (B, H, Sq, D);
    m, l: (B, H, Sq); q_pos: (Sq,), k_pos: (Sk,) global positions.
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k_blk) * scale
    if causal:
        valid = (k_pos[None, :] <= q_pos[:, None])  # (Sq, Sk)
        s = jnp.where(valid[None, None], s, _NEG)
    s_max = jnp.max(s, axis=-1)                      # (B, H, Sq)
    m_new = jnp.maximum(m, s_max)
    # exp of masked entries: s = _NEG << m_new -> exp underflows to 0 exactly
    p = jnp.exp(s - m_new[..., None])
    p = jnp.where(s <= _NEG / 2, 0.0, p)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1)
    acc_new = acc * corr[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, v_blk)
    return acc_new, m_new, l_new


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis: str,
    axis_size: int,
    causal: bool = False,
    use_flash: Optional[bool] = None,
) -> jax.Array:
    """Exact attention over the full (sharded) sequence via a k/v ring.

    use_flash: None = auto (fused Pallas block kernel on TPU when the tiling
    admits); True/False forces the choice (True on a chosen CPU platform runs
    the kernel under the interpreter — sysinfo.pallas_interpret)."""
    if axis_size == 1:
        return _dense_attention(q, k, v, causal, 0)
    b, h, sl, d = q.shape
    if use_flash is None:
        use_flash = _use_flash(sl, sl, d)
    if use_flash:
        from mlsl_tpu.ops.attention_kernels import supports

        mlsl_assert(
            supports(sl, sl, d),
            "flash ring requires local seq %% 128 == 0 and head_dim %% 8 == 0 "
            "(got seq=%d, head_dim=%d); use use_flash=False",
            sl, d,
        )
        return _ring_flash(q, k, v, axis, axis_size, causal)
    scale = 1.0 / jnp.sqrt(d).astype(q.dtype)
    me = lax.axis_index(axis)
    q_pos = me * sl + jnp.arange(sl)

    init = (
        _pvary(jnp.zeros((b, h, sl, d), jnp.float32), axis),
        _pvary(jnp.full((b, h, sl), _NEG, jnp.float32), axis),
        _pvary(jnp.zeros((b, h, sl), jnp.float32), axis),
    )

    def step_fn(carry, k_cur, v_cur, src):
        acc, m, l = carry
        k_pos = src * sl + jnp.arange(sl)
        return _attn_block_update(
            q.astype(jnp.float32), k_cur.astype(jnp.float32),
            v_cur.astype(jnp.float32), acc, m, l, q_pos, k_pos, causal, scale
        )

    acc, m, l = _ring_schedule(k, v, axis, axis_size, init, step_fn)
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.astype(q.dtype)


def _ring_schedule(k, v, axis: str, axis_size: int, init_carry, step_fn):
    """The shared k/v rotation loop: at hop t every device folds the block
    originally owned by rank (me - t) into its carry, then passes it right."""
    me = lax.axis_index(axis)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def step(t, state):
        carry, k_cur, v_cur = state
        src = (me - t) % axis_size          # original owner of the current block
        carry = step_fn(carry, k_cur, v_cur, src)
        # mlsl-lint: disable=A201 -- the ring-attention KV rotation is the
        # algorithm itself (per-hop ppermute fused with the attention math),
        # not a request collective the engine could serve
        return carry, lax.ppermute(k_cur, axis, perm), lax.ppermute(v_cur, axis, perm)

    carry, _, _ = lax.fori_loop(0, axis_size, step, (init_carry, k, v))
    return carry


def _ring_flash(q, k, v, axis: str, axis_size: int, causal: bool) -> jax.Array:
    """Ring attention with the fused Pallas block kernel as the inner step: each
    hop folds the visiting k/v block into the carried (acc, m, l) state without
    materializing scores (mlsl_tpu.ops.attention_kernels.flash_block_update)."""
    from mlsl_tpu.ops.attention_kernels import NEG, flash_block_update

    b, h, sl, d = q.shape
    bh = b * h
    interpret = pallas_interpret()
    qf = q.reshape(bh, sl, d)
    # The scalar-prefetch offsets only matter for the causal mask / DMA-skip
    # maps. Non-causal, feed constants: an axis_index-derived operand that the
    # kernel never reads still lowers to a PartitionId instruction, which
    # XLA:CPU's SPMD partitioner rejects (the interpret-mode CI path).
    if causal:
        me = lax.axis_index(axis)
        q_off = jnp.full((1,), me * sl, jnp.int32)
    else:
        q_off = jnp.zeros((1,), jnp.int32)

    init = (
        _pvary(jnp.zeros((bh, sl, d), jnp.float32), axis),
        _pvary(jnp.full((bh, sl, 128), NEG, jnp.float32), axis),
        _pvary(jnp.zeros((bh, sl, 128), jnp.float32), axis),
    )

    def step_fn(carry, k_cur, v_cur, src):
        acc, m, l = carry
        k_off = (jnp.full((1,), src * sl, jnp.int32) if causal
                 else jnp.zeros((1,), jnp.int32))
        return flash_block_update(
            qf, k_cur, v_cur, acc, m, l, q_off, k_off, causal, interpret
        )

    acc, m, l = _ring_schedule(
        k.reshape(bh, sl, d), v.reshape(bh, sl, d), axis, axis_size, init, step_fn
    )
    out = acc / jnp.maximum(l[:, :, :1], 1e-30)
    return out.reshape(b, h, sl, d).astype(q.dtype)


def zigzag_perm(seq_len: int, axis_size: int):
    """Permutation putting a sequence into ZIGZAG layout: device r's contiguous
    shard holds global chunks r and 2G-1-r (chunk = seq_len / (2G)).

    Returns ``perm`` with ``x_zigzag = x[..., perm, :]``; invert with
    ``x[..., inv, :] = x_zigzag`` where ``inv = zigzag_perm_inverse(...)``.
    """
    import numpy as np

    g = axis_size
    mlsl_assert(
        seq_len % (2 * g) == 0,
        "zigzag needs seq_len %% (2 * axis_size) == 0 (got %d, %d)",
        seq_len, g,
    )
    c = seq_len // (2 * g)
    chunks = np.arange(seq_len).reshape(2 * g, c)
    order = [x for r in range(g) for x in (r, 2 * g - 1 - r)]
    return chunks[order].reshape(-1)


def zigzag_perm_inverse(seq_len: int, axis_size: int):
    import numpy as np

    perm = zigzag_perm(seq_len, axis_size)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(seq_len)
    return inv


def zigzag_ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis: str,
    axis_size: int,
    use_flash: Optional[bool] = None,
) -> jax.Array:
    """Load-balanced CAUSAL ring attention over zigzag-sharded sequences.

    With contiguous block sharding, causal ring attention computes the full
    (2c x 2c) score block every hop and masks half of it away on average —
    ~2x wasted MXU work at large ring sizes, and SPMD lockstep means nobody
    can skip ahead. Zigzag layout (device r holds global chunks r and
    2G-1-r; see zigzag_perm) makes every hop exactly TWO unmasked (c x c)
    block updates on every device:

      - visiting kv from an earlier rank (src < me): both my chunks see the
        visitor's first chunk -> (q0, k0), (q1, k0);
      - visiting kv from a later rank (src > me): my second chunk sees both
        visitor chunks -> (q1, k0), (q1, k1);

    and chunk-level visibility is all-or-nothing, so the off-diagonal
    updates need NO mask at all. Only the self-hop touches masked diagonals.
    Total block-FLOPs: ~2Gc^2 vs the contiguous schedule's 4Gc^2 — the
    schedule used by production context-parallel trainers, absent from the
    reference (its sequence dimension does not exist; SURVEY §5.7).

    Inputs are zigzag-sharded device-local (B, H, 2c, D) shards; call inside
    shard_map like ring_attention. Non-causal attention gains nothing from
    zigzag — use ring_attention for it.

    use_flash: None = auto (the fused Pallas block kernel on TPU when the
    chunk tiling admits — no (c x c) score materialization); True forces it
    (interpreted on a chosen CPU platform), False forces the einsum fallback.
    """
    if axis_size == 1:
        return _dense_attention(q, k, v, True, 0)
    b, h, sl, d = q.shape
    mlsl_assert(sl % 2 == 0, "zigzag shard length must be even (got %d)", sl)
    c = sl // 2
    g = axis_size
    bh = b * h
    me = lax.axis_index(axis)
    if use_flash is None:
        use_flash = _use_flash(c, c, d)

    # Both modes share the schedule below on (bh, 2, c, ...) chunked carries;
    # they differ only in the per-chunk update and the m/l carry layout.
    if use_flash:
        from mlsl_tpu.ops.attention_kernels import (
            NEG, flash_block_update, supports,
        )

        mlsl_assert(
            supports(c, c, d),
            "flash zigzag requires chunk length (local seq / 2) %% 128 == 0 "
            "and head_dim %% 8 == 0 (got chunk=%d, head_dim=%d); use "
            "use_flash=False",
            c, d,
        )
        interpret = pallas_interpret()
        zoff = jnp.zeros((1,), jnp.int32)

        def _update(causal):
            # causal=False: chunk fully visible (no mask, offsets irrelevant);
            # causal=True: equal offsets = within-chunk lower triangle
            def u(qc, kc, vc, ac, mc, lc):
                return flash_block_update(
                    qc, kc, vc, ac, mc, lc, zoff, zoff, causal, interpret
                )
            return u

        full_update, diag_update = _update(False), _update(True)
        as_chunks = lambda x: x.reshape(bh, 2, c, d)
        qz = as_chunks(q)
        m = _pvary(jnp.full((bh, 2, c, 128), NEG, jnp.float32), axis)
        l = _pvary(jnp.zeros((bh, 2, c, 128), jnp.float32), axis)
        denom = lambda l: jnp.maximum(l[..., :1], 1e-30)
    else:
        scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)

        def _update(causal):
            """(c x c) online-softmax update; causal=True applies the
            within-chunk lower triangle (self-hop diagonals only)."""
            def u(qc, kc, vc, ac, mc, lc):
                s = jnp.einsum("bqd,bkd->bqk", qc, kc) * scale
                if causal:
                    tri = jnp.arange(c)[None, :] <= jnp.arange(c)[:, None]
                    s = jnp.where(tri[None], s, _NEG)
                s_max = jnp.max(s, axis=-1)
                m_new = jnp.maximum(mc, s_max)
                p = jnp.exp(s - m_new[..., None])
                if causal:
                    p = jnp.where(s <= _NEG / 2, 0.0, p)
                corr = jnp.exp(mc - m_new)
                l_new = lc * corr + jnp.sum(p, axis=-1)
                a_new = ac * corr[..., None] + jnp.einsum("bqk,bkd->bqd", p, vc)
                return a_new, m_new, l_new
            return u

        full_update, diag_update = _update(False), _update(True)
        as_chunks = lambda x: x.astype(jnp.float32).reshape(bh, 2, c, d)
        qz = as_chunks(q)
        m = _pvary(jnp.full((bh, 2, c), _NEG, jnp.float32), axis)
        l = _pvary(jnp.zeros((bh, 2, c), jnp.float32), axis)
        denom = lambda l: jnp.maximum(l[..., None], 1e-30)

    acc = _pvary(jnp.zeros((bh, 2, c, d), jnp.float32), axis)

    # self hop: q0*k0 (diag), q1*k0 (full: chunk 2G-1-me is after chunk me),
    # q1*k1 (diag)
    kz, vz = as_chunks(k), as_chunks(v)
    a0, m0, l0 = diag_update(
        qz[:, 0], kz[:, 0], vz[:, 0], acc[:, 0], m[:, 0], l[:, 0]
    )
    a1, m1, l1 = full_update(
        qz[:, 1], kz[:, 0], vz[:, 0], acc[:, 1], m[:, 1], l[:, 1]
    )
    a1, m1, l1 = diag_update(qz[:, 1], kz[:, 1], vz[:, 1], a1, m1, l1)
    acc = jnp.stack([a0, a1], axis=1)
    m = jnp.stack([m0, m1], axis=1)
    l = jnp.stack([l0, l1], axis=1)

    perm = [(i, (i + 1) % g) for i in range(g)]

    def hop(t, state):
        (acc, m, l), k_cur, v_cur = state
        src = (me - t) % g          # original owner of the visiting kv
        early = src < me            # visitor's chunks precede mine
        qsel = (jnp.where(early, 0, 1), jnp.int32(1))
        ksel = (jnp.int32(0), jnp.where(early, 0, 1))
        for u in range(2):
            qi, ki = qsel[u], ksel[u]
            qc = lax.dynamic_index_in_dim(qz, qi, axis=1, keepdims=False)
            kc = lax.dynamic_index_in_dim(k_cur, ki, axis=1, keepdims=False)
            vc = lax.dynamic_index_in_dim(v_cur, ki, axis=1, keepdims=False)
            ac = lax.dynamic_index_in_dim(acc, qi, axis=1, keepdims=False)
            mc = lax.dynamic_index_in_dim(m, qi, axis=1, keepdims=False)
            lc = lax.dynamic_index_in_dim(l, qi, axis=1, keepdims=False)
            ac, mc, lc = full_update(qc, kc, vc, ac, mc, lc)
            acc = lax.dynamic_update_index_in_dim(acc, ac, qi, axis=1)
            m = lax.dynamic_update_index_in_dim(m, mc, qi, axis=1)
            l = lax.dynamic_update_index_in_dim(l, lc, qi, axis=1)
        return (
            (acc, m, l),
            lax.ppermute(k_cur, axis, perm),  # mlsl-lint: disable=A201
            lax.ppermute(v_cur, axis, perm),  # mlsl-lint: disable=A201
        )

    (acc, m, l), _, _ = lax.fori_loop(
        1, g, hop,
        # mlsl-lint: disable=A201 -- zigzag ring rotation, as above
        ((acc, m, l), lax.ppermute(kz, axis, perm), lax.ppermute(vz, axis, perm)),
    )
    out = acc / denom(l)
    return out.reshape(b, h, sl, d).astype(q.dtype)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis: str,
    axis_size: int,
    causal: bool = False,
) -> jax.Array:
    """Exact attention by re-sharding seq->heads with all-to-all, attending, and
    re-sharding back."""
    b, h, sl, d = q.shape
    if axis_size == 1:
        return _dense_attention(q, k, v, causal, 0)
    assert h % axis_size == 0, (
        f"heads_local {h} must be divisible by seq axis size {axis_size}"
    )

    def to_heads(x):  # (B, H, Sl, D) -> (B, H/G, S, D)
        # mlsl-lint: disable=A201 -- head/sequence re-sharding transposes
        # inside the attention body (DeepSpeed-Ulysses layout), in-graph
        return lax.all_to_all(x, axis, split_axis=1, concat_axis=2, tiled=True)

    def to_seq(x):    # (B, H/G, S, D) -> (B, H, Sl, D)
        # mlsl-lint: disable=A201 -- as above
        return lax.all_to_all(x, axis, split_axis=2, concat_axis=1, tiled=True)

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)
    out = _dense_attention(qh, kh, vh, causal, 0)
    return to_seq(out)


def _dense_attention(q, k, v, causal: bool, pos_offset: int) -> jax.Array:
    b, h, s, d = q.shape
    if _use_flash(s, s, d):
        from mlsl_tpu.ops.attention_kernels import flash_attention

        off = jnp.full((1,), pos_offset, jnp.int32)
        out = flash_attention(
            q.reshape(b * h, s, d), k.reshape(b * h, s, d), v.reshape(b * h, s, d),
            off, off, causal, pallas_interpret(),
        )
        return out.reshape(b, h, s, d)
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    s_mat = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if causal:
        pos = jnp.arange(s) + pos_offset
        s_mat = jnp.where((pos[None, :] <= pos[:, None])[None, None], s_mat, _NEG)
    p = jax.nn.softmax(s_mat, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


def _use_flash(sq: int, sk: int, d: int) -> bool:
    """Route through the fused Pallas kernel on TPU when the tiling admits it
    (O(S*D) HBM instead of the einsum's O(S^2))."""
    if not on_tpu():
        return False
    from mlsl_tpu.ops.attention_kernels import supports

    return supports(sq, sk, d)
