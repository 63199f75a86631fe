"""Flash attention forward kernel (Pallas TPU).

Fused online-softmax attention: scores, exp, and the weighted-value accumulation all
happen in VMEM tile by tile, so the (Sq, Sk) score matrix never touches HBM — the
memory win that matters for the long sequences the sequence-parallel schedules target
(HBM traffic O(S*D) instead of O(S^2)).

Autodiff: a custom VJP with fused Pallas backward kernels — dq accumulates over key
tiles, dk/dv over query tiles, with the tile probabilities recomputed from the saved
per-row log-sum-exp, so the O(S*D) memory property holds in the backward too. On
fully-masked rows the kernel's gradients are exactly zero (consistent with its zero
forward output), unlike a dense softmax which would leak uniform-distribution
gradients.

Grid: (batch*heads, Sq tiles, Sk tiles), Sk innermost and "arbitrary" so the VMEM
scratch (acc, row-max, row-sum) carries across k tiles; outputs are written on the
last k tile (the canonical TPU flash pattern).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


#: Sk innermost and "arbitrary": the VMEM scratch carries across it. The
#: (512, 2048) f32 score tiles fit Mosaic's default scoped VMEM on v5e at
#: head_dim 64 and 128, forward and backward (PERF.md, PR 21 chip run), so no
#: vmem_limit_bytes is set.
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
)


def _pick_tiles(sq: int, sk: int):
    """Largest tiles that divide the shapes (tuned on v5e: big k tiles win —
    fewer scratch-carry round trips per query tile)."""
    tq = next((t for t in (512, 256, 128) if sq % t == 0), None)
    tk = next((t for t in (2048, 1024, 512, 256, 128) if sk % t == 0), None)
    return tq, tk


def _tile_visible(q_off_ref, k_off_ref, qi, ki, tq, tk, causal: bool):
    """Whole-tile causal visibility: skip k tiles entirely in this q tile's future."""
    if not causal:
        return True
    q_pos_max = q_off_ref[0] + (qi + 1) * tq - 1
    k_pos_min = k_off_ref[0] + ki * tk
    return k_pos_min <= q_pos_max


def _kv_idx(causal: bool, tq: int, tk: int, k_tiles: int):
    """k/v BlockSpec index map for a (b, q-tile, k-tile) grid.

    For causal, k tiles past the diagonal CLAMP to the last visible tile:
    pl.when already skips their compute, but the pipeline would still DMA every
    block — repeating the previous index makes Pallas skip the copy, so the
    causal walk does ~half the memory traffic of the full one (this was
    measured slower than the full kernel before the clamp)."""
    if not causal:
        return lambda b, i, j, *_: (b, j, 0)

    def idx(b, i, j, q_off_ref, k_off_ref):
        last = (q_off_ref[0] + (i + 1) * tq - 1 - k_off_ref[0]) // tk
        last = jnp.clip(last, 0, k_tiles - 1)
        return (b, jnp.minimum(j, last), 0)

    return idx


def _q_idx_for_dkv(causal: bool, tq: int, tk: int, q_tiles: int):
    """q-side BlockSpec index map for the (b, k-tile, q-tile) dk/dv grid:
    q tiles BEFORE the diagonal clamp up to the first visible tile (same
    DMA-skip trick as _kv_idx, mirrored)."""
    if not causal:
        return lambda b, i, j, *_: (b, j, 0)

    def idx(b, i, j, q_off_ref, k_off_ref):
        first = -((q_off_ref[0] - k_off_ref[0] - i * tk + tq - 1) // tq)
        first = jnp.clip(first, 0, q_tiles - 1)
        return (b, jnp.maximum(j, first), 0)

    return idx


def _tile_accumulate(q_off_ref, k_off_ref, q_ref, k_ref, v_ref,
                     acc_prev, m_prev, l_prev,
                     qi, ki, tq, tk, scale, causal: bool):
    """The online-softmax tile update (shared by both kernels): fold the (tq, tk)
    score tile into (acc, m, l). Returns the updated triple as values."""
    q = q_ref[0].astype(jnp.float32)              # (tq, D)
    k = k_ref[0].astype(jnp.float32)              # (tk, D)
    v = v_ref[0].astype(jnp.float32)              # (tk, D)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = q_off_ref[0] + qi * tq + lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
        k_pos = k_off_ref[0] + ki * tk + lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        s = jnp.where(k_pos <= q_pos, s, NEG)
    s_max = jnp.max(s, axis=1)
    m_new = jnp.maximum(m_prev, s_max)
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(s <= NEG / 2, 0.0, p)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=1)
    acc_new = acc_prev * corr[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32
    )
    return acc_new, m_new, l_new


def _flash_kernel(q_off_ref, k_off_ref, q_ref, k_ref, v_ref, *refs,
                  causal: bool, k_tiles: int, scale: float, tq: int, tk: int,
                  want_lse: bool):
    if want_lse:
        o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    else:
        o_ref, acc_ref, m_ref, l_ref = refs
        lse_ref = None
    ki = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG)
        l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(_tile_visible(q_off_ref, k_off_ref, qi, ki, tq, tk, causal))
    def _accumulate():
        acc, m_new, l_new = _tile_accumulate(
            q_off_ref, k_off_ref, q_ref, k_ref, v_ref,
            acc_ref[:], m_ref[:, 0], l_ref[:, 0],
            qi, ki, tq, tk, scale, causal,
        )
        acc_ref[:] = acc
        m_ref[:] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(ki == k_tiles - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0] = (acc_ref[:] / denom[:, None]).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[0] = jnp.broadcast_to(
                (m_ref[:, 0] + jnp.log(denom))[:, None], lse_ref[0].shape
            )


@functools.partial(
    jax.jit, static_argnames=("causal", "interpret", "want_lse")
)
def _flash_fwd(q, k, v, q_offset, k_offset, causal=False, interpret=False,
               want_lse=True):
    """q: (BH, Sq, D), k/v: (BH, Sk, D); shapes must satisfy supports().
    -> (out, lse (BH, Sq, 128) lane-broadcast f32) when want_lse, else (out, None)
    — the inference path skips the lse allocation/write entirely."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    tq, tk = _pick_tiles(sq, sk)
    k_tiles = sk // tk
    scale = 1.0 / (d ** 0.5)
    grid = (bh, sq // tq, k_tiles)
    o_spec = pl.BlockSpec((1, tq, d), lambda b, i, j, *_: (b, i, 0))
    lse_spec = pl.BlockSpec((1, tq, 128), lambda b, i, j, *_: (b, i, 0))
    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, causal=causal, k_tiles=k_tiles, scale=scale,
            tq=tq, tk=tk, want_lse=want_lse,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, tq, d), lambda b, i, j, *_: (b, i, 0)),
                pl.BlockSpec((1, tk, d), _kv_idx(causal, tq, tk, k_tiles)),
                pl.BlockSpec((1, tk, d), _kv_idx(causal, tq, tk, k_tiles)),
            ],
            out_specs=[o_spec, lse_spec] if want_lse else [o_spec],
            scratch_shapes=[
                pltpu.VMEM((tq, d), jnp.float32),
                pltpu.VMEM((tq, 128), jnp.float32),
                pltpu.VMEM((tq, 128), jnp.float32),
            ],
        ),
        out_shape=(
            [
                jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
                jax.ShapeDtypeStruct((bh, sq, 128), jnp.float32),
            ]
            if want_lse
            else [jax.ShapeDtypeStruct((bh, sq, d), q.dtype)]
        ),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(q_offset, k_offset, q, k, v)
    if want_lse:
        return out[0], out[1]
    return out[0], None


def _reference_attention(q, k, v, q_offset, k_offset, causal):
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum(
        "bqd,bkd->bqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if causal:
        q_pos = q_offset[0] + jnp.arange(q.shape[1])
        k_pos = k_offset[0] + jnp.arange(k.shape[1])
        s = jnp.where((k_pos[None, :] <= q_pos[:, None])[None], s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)


# ---------------------------------------------------------------------------
# Flash backward: two fused passes (dq over k tiles; dk/dv over q tiles), the
# score probabilities recomputed per tile from the saved log-sum-exp — the
# (Sq, Sk) matrices never materialize in the backward either.
# ---------------------------------------------------------------------------


def _bwd_p_tile(q_off_ref, k_off_ref, q, kk, lse, qi, ki, tq, tk, scale, causal):
    """Recompute P = exp(s*scale - lse) for one (tq, tk) tile, masked."""
    s = jnp.dot(q, kk.T, preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = q_off_ref[0] + qi * tq + lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
        k_pos = k_off_ref[0] + ki * tk + lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        s = jnp.where(k_pos <= q_pos, s, NEG)
    p = jnp.exp(s - lse[:, None])
    return jnp.where(s <= NEG / 2, 0.0, p)


def _bwd_dq_kernel(q_off_ref, k_off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   dd_ref, dq_ref, dq_acc, *, causal, k_tiles, scale, tq, tk):
    ki = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(_tile_visible(q_off_ref, k_off_ref, qi, ki, tq, tk, causal))
    def _accumulate():
        q = q_ref[0].astype(jnp.float32)
        kk = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        p = _bwd_p_tile(q_off_ref, k_off_ref, q, kk, lse_ref[0, :, 0],
                        qi, ki, tq, tk, scale, causal)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)   # (tq, tk)
        ds = p * (dp - dd_ref[0, :, 0][:, None])
        dq_acc[:] = dq_acc[:] + scale * jnp.dot(
            ds, kk, preferred_element_type=jnp.float32
        )

    @pl.when(ki == k_tiles - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_off_ref, k_off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    dd_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                    *, causal, q_tiles, scale, tq, tk):
    qi = pl.program_id(2)   # q innermost in this pass
    ki = pl.program_id(1)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(_tile_visible(q_off_ref, k_off_ref, qi, ki, tq, tk, causal))
    def _accumulate():
        q = q_ref[0].astype(jnp.float32)
        kk = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        p = _bwd_p_tile(q_off_ref, k_off_ref, q, kk, lse_ref[0, :, 0],
                        qi, ki, tq, tk, scale, causal)
        dv_acc[:] = dv_acc[:] + jnp.dot(p.T, do, preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - dd_ref[0, :, 0][:, None])
        dk_acc[:] = dk_acc[:] + scale * jnp.dot(
            ds.T, q, preferred_element_type=jnp.float32
        )

    @pl.when(qi == q_tiles - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "interpret"))
def _flash_bwd(q, k, v, do, out, lse, q_offset, k_offset, causal, interpret):
    bh, sq, d = q.shape
    sk = k.shape[1]
    tq, tk = _pick_tiles(sq, sk)
    k_tiles, q_tiles = sk // tk, sq // tq
    scale = 1.0 / (d ** 0.5)
    # lse arrives 2-D (residual memory: see _fwd); rebroadcast for the kernels'
    # (tq, 128) tiles, as is D_i = rowsum(dO * O)
    lse = jnp.broadcast_to(lse[..., None], (bh, sq, 128))
    dd = jnp.broadcast_to(
        jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)[..., None],
        (bh, sq, 128),
    )

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal=causal, k_tiles=k_tiles,
                          scale=scale, tq=tq, tk=tk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, q_tiles, k_tiles),
            in_specs=[
                pl.BlockSpec((1, tq, d), lambda b, i, j, *_: (b, i, 0)),
                pl.BlockSpec((1, tk, d), _kv_idx(causal, tq, tk, k_tiles)),
                pl.BlockSpec((1, tk, d), _kv_idx(causal, tq, tk, k_tiles)),
                pl.BlockSpec((1, tq, d), lambda b, i, j, *_: (b, i, 0)),
                pl.BlockSpec((1, tq, 128), lambda b, i, j, *_: (b, i, 0)),
                pl.BlockSpec((1, tq, 128), lambda b, i, j, *_: (b, i, 0)),
            ],
            out_specs=pl.BlockSpec((1, tq, d), lambda b, i, j, *_: (b, i, 0)),
            scratch_shapes=[pltpu.VMEM((tq, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(q_offset, k_offset, q, k, v, do, lse, dd)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, causal=causal, q_tiles=q_tiles,
                          scale=scale, tq=tq, tk=tk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, k_tiles, q_tiles),
            in_specs=[
                pl.BlockSpec((1, tq, d), _q_idx_for_dkv(causal, tq, tk, q_tiles)),
                pl.BlockSpec((1, tk, d), lambda b, i, j, *_: (b, i, 0)),
                pl.BlockSpec((1, tk, d), lambda b, i, j, *_: (b, i, 0)),
                pl.BlockSpec((1, tq, d), _q_idx_for_dkv(causal, tq, tk, q_tiles)),
                pl.BlockSpec((1, tq, 128), _q_idx_for_dkv(causal, tq, tk, q_tiles)),
                pl.BlockSpec((1, tq, 128), _q_idx_for_dkv(causal, tq, tk, q_tiles)),
            ],
            out_specs=[
                pl.BlockSpec((1, tk, d), lambda b, i, j, *_: (b, i, 0)),
                pl.BlockSpec((1, tk, d), lambda b, i, j, *_: (b, i, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((tk, d), jnp.float32),
                pltpu.VMEM((tk, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(q_offset, k_offset, q, k, v, do, lse, dd)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def flash_attention(q, k, v, q_offset, k_offset, causal=False, interpret=False):
    """Fused attention. q: (BH, Sq, D); k, v: (BH, Sk, D); offsets: (1,) int32
    global position bases (for causal masking across sequence shards)."""
    out, _ = _flash_fwd(
        q, k, v, q_offset, k_offset, causal=causal, interpret=interpret,
        want_lse=False,
    )
    return out


def _fwd(q, k, v, q_offset, k_offset, causal, interpret):
    out, lse = _flash_fwd(
        q, k, v, q_offset, k_offset, causal=causal, interpret=interpret
    )
    # keep the lse as a 2-D (BH, Sq) array so Sq packs into the lane dimension —
    # a (BH, Sq, 1) slice would still be lane-padded to 128, keeping the 128x
    # residual bloat this is meant to remove
    return out, (q, k, v, out, lse[:, :, 0], q_offset, k_offset)


def _bwd(causal, interpret, res, g):
    q, k, v, out, lse, q_offset, k_offset = res
    dq, dk, dv = _flash_bwd(
        q, k, v, g, out, lse, q_offset, k_offset, causal, interpret
    )
    return dq, dk, dv, None, None


flash_attention.defvjp(_fwd, _bwd)


def supports(sq: int, sk: int, d: int) -> bool:
    """Whether the kernel's tiling constraints admit these shapes."""
    tq, tk = _pick_tiles(sq, sk)
    return tq is not None and tk is not None and d % 8 == 0 and d >= 8


# ---------------------------------------------------------------------------
# Carried-state block update: the ring-attention inner step.
# One k/v block is folded into a running (acc, m, l) online-softmax state that
# persists across ppermute hops (so it lives in HBM between calls; the kernel
# fuses score/exp/accumulate for the block without materializing scores).
# ---------------------------------------------------------------------------


def _block_kernel(q_off_ref, k_off_ref, q_ref, k_ref, v_ref,
                  acc_in_ref, m_in_ref, l_in_ref,
                  acc_out_ref, m_out_ref, l_out_ref,
                  *, causal: bool, k_tiles: int, scale: float, tq: int, tk: int):
    ki = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _load_carry():
        acc_out_ref[0] = acc_in_ref[0]
        m_out_ref[0] = m_in_ref[0]
        l_out_ref[0] = l_in_ref[0]

    @pl.when(_tile_visible(q_off_ref, k_off_ref, qi, ki, tq, tk, causal))
    def _accumulate():
        acc, m_new, l_new = _tile_accumulate(
            q_off_ref, k_off_ref, q_ref, k_ref, v_ref,
            acc_out_ref[0], m_out_ref[0, :, 0], l_out_ref[0, :, 0],
            qi, ki, tq, tk, scale, causal,
        )
        acc_out_ref[0] = acc
        m_out_ref[0] = jnp.broadcast_to(m_new[:, None], m_out_ref[0].shape)
        l_out_ref[0] = jnp.broadcast_to(l_new[:, None], l_out_ref[0].shape)


@functools.partial(jax.jit, static_argnames=("causal", "interpret"))
def _block_update_fwd(q, k, v, acc, m, l, q_offset, k_offset,
                      causal=False, interpret=False):
    """q: (BH, Sq, D); k/v: (BH, Sk, D); acc: (BH, Sq, D) f32;
    m, l: (BH, Sq, 128) f32 (lane-padded) -> (acc', m', l')."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    tq, tk = _pick_tiles(sq, sk)
    k_tiles = sk // tk
    scale = 1.0 / (d ** 0.5)
    grid = (bh, sq // tq, k_tiles)
    return pl.pallas_call(
        functools.partial(
            _block_kernel, causal=causal, k_tiles=k_tiles, scale=scale,
            tq=tq, tk=tk,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, tq, d), lambda b, i, j, *_: (b, i, 0)),
                pl.BlockSpec((1, tk, d), lambda b, i, j, *_: (b, j, 0)),
                pl.BlockSpec((1, tk, d), lambda b, i, j, *_: (b, j, 0)),
                pl.BlockSpec((1, tq, d), lambda b, i, j, *_: (b, i, 0)),
                pl.BlockSpec((1, tq, 128), lambda b, i, j, *_: (b, i, 0)),
                pl.BlockSpec((1, tq, 128), lambda b, i, j, *_: (b, i, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, tq, d), lambda b, i, j, *_: (b, i, 0)),
                pl.BlockSpec((1, tq, 128), lambda b, i, j, *_: (b, i, 0)),
                pl.BlockSpec((1, tq, 128), lambda b, i, j, *_: (b, i, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, sq, 128), jnp.float32),
            jax.ShapeDtypeStruct((bh, sq, 128), jnp.float32),
        ],
        # alias the carried state in place: operands (2 scalar-prefetch + q,k,v,
        # acc, m, l) -> acc/m/l reuse their input buffers, saving one HBM copy of
        # the dominant long-sequence state per ring hop
        input_output_aliases={5: 0, 6: 1, 7: 2},
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(q_offset, k_offset, q, k, v, acc, m, l)


def _block_update_ref(q, k, v, acc, m, l, q_offset, k_offset, causal):
    """jnp twin of the block kernel (used for the VJP and as the oracle)."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum(
        "bqd,bkd->bqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if causal:
        q_pos = q_offset[0] + jnp.arange(q.shape[1])
        k_pos = k_offset[0] + jnp.arange(k.shape[1])
        s = jnp.where((k_pos[None, :] <= q_pos[:, None])[None], s, NEG)
    m_prev = m[:, :, 0]
    s_max = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m_prev, s_max)
    p = jnp.exp(s - m_new[..., None])
    p = jnp.where(s <= NEG / 2, 0.0, p)
    corr = jnp.exp(m_prev - m_new)
    l_new = l[:, :, 0] * corr + jnp.sum(p, axis=-1)
    acc_new = acc * corr[..., None] + jnp.einsum(
        "bqk,bkd->bqd", p, v.astype(jnp.float32)
    )
    bcast = lambda x: jnp.broadcast_to(x[..., None], (*x.shape, 128))
    return acc_new, bcast(m_new), bcast(l_new)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def flash_block_update(q, k, v, acc, m, l, q_offset, k_offset,
                       causal=False, interpret=False):
    """Ring-attention inner step: fold one k/v block into (acc, m, l)."""
    return _block_update_fwd(
        q, k, v, acc, m, l, q_offset, k_offset, causal=causal, interpret=interpret
    )


def _bu_fwd(q, k, v, acc, m, l, q_offset, k_offset, causal, interpret):
    out = _block_update_fwd(
        q, k, v, acc, m, l, q_offset, k_offset, causal=causal, interpret=interpret
    )
    return out, (q, k, v, acc, m, l, q_offset, k_offset)


def _bu_bwd(causal, interpret, res, g):
    q, k, v, acc, m, l, q_offset, k_offset = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_, acc_, m_, l_: _block_update_ref(
            q_, k_, v_, acc_, m_, l_, q_offset, k_offset, causal
        ),
        q, k, v, acc, m, l,
    )
    dq, dk, dv, dacc, dm, dl = vjp(g)
    return dq, dk, dv, dacc, dm, dl, None, None


flash_block_update.defvjp(_bu_fwd, _bu_bwd)
