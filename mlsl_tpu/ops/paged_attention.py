"""Ragged paged attention: the serving engine's decode step (plain JAX).

One query a batch slot against the KV pages that slot holds, read where they
lie in the pool through one flat list of the live pages of all slots. The
list is walked a chunk of pages at a time with the running maximum,
denominator and accumulator that the flash kernels keep
(ops/attention_kernels.py: ``_tile_accumulate``, ``flash_block_update``), and
the number of chunks walked is data: the work of a step follows the pages
that hold a live token, not the batch's or the pool's capacity.

A module of its own, beside the Pallas kernels and not among them: importing
those costs every serving process a second of set-up for Pallas itself, and
this walk needs none of it (PERF.md section 6, PR 26).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

NEG = -1e30

#: pages one trip of the walk reads (64 KiB of K and of V a page at GPT-2
#: medium's widths). Chosen on the chip (PERF.md section 6, PR 26): with 306
#: pages held a decode step takes the same 10.3-10.9 ms at 16, 32, 64 and 128
#: pages a trip and 13.0 at 8, 17.9 at 4; 16 is the smallest that costs
#: nothing, so the last chunk's padding is the least.
PAGES_PER_CHUNK = 16


def live_masks(owners, bases, positions, page: int):
    """What of the live list no layer changes, worked out once a step:
    ``valid`` (capacity, page), whether row r of entry e holds a token its
    owner attends to (``owners[e] >= 0`` and ``bases[e] + r <=
    positions[owners[e]]``), and ``mine`` (B, capacity) f32, 1 where slot b
    owns entry e."""
    slot = jnp.maximum(owners, 0)
    valid = (owners >= 0)[:, None] & (
        bases[:, None] + jnp.arange(page)[None, :] <= positions[slot][:, None])
    mine = owners[None, :] == jnp.arange(positions.shape[0])[:, None]
    return valid, mine.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("chunk",))
def ragged_paged_attention(q, kpool, vpool, layer, pages, owners, valid, mine,
                           kscale=None, vscale=None, *, chunk: int):
    """Decode attention over a flat live-page list.

    q: (B, Hl, Dh) f32, one query a slot. kpool / vpool: the whole pools,
    (n_layers, Np, page, Hl*Dh), f32 or int8 with kscale / vscale
    (n_layers, Np, Hl*page) (the ``kv_block_quant`` codec: one scale a
    token and head, a page's scales head-major on one lane-dense row);
    ``layer`` picks the layer and nothing of the pool is copied. pages /
    owners: (capacity,) int32, capacity a whole number of chunks: entry e is
    pool page ``pages[e]``, held by slot ``owners[e]`` (-1 pads the list);
    valid / mine: ``live_masks`` of the list. Returns (B, Hl, Dh) f32; a slot
    that owns no entry reads zeros. Jitted so that a program of many layers
    traces the walk once.

    Heads stay merged with head_dim on the lane axis, as the pool stores
    them (a 64-wide minor axis would be padded to 128 lanes): the per-head
    sums over head_dim and the spreading of a head's weight over its lanes go
    through the constant 0/1 matrix ``seg`` on the MXU at full precision.
    """
    b, hl, dh = q.shape
    page, hd, c = kpool.shape[2], hl * dh, chunk
    hi = lax.Precision.HIGHEST
    qf = (q * (1.0 / (dh ** 0.5))).reshape(b, hd)
    seg = (jnp.arange(hd)[:, None] // dh
           == jnp.arange(hl)[None, :]).astype(jnp.float32)        # (HD, Hl)
    n_chunks = (jnp.sum(owners >= 0) + c - 1) // c

    def walk(ci, carry):
        acc, m, l = carry                      # (B, HD), (B, Hl), (B, Hl)
        at = ci * c
        ids = lax.dynamic_slice_in_dim(pages, at, c)
        slot = jnp.maximum(lax.dynamic_slice_in_dim(owners, at, c), 0)
        ok = lax.dynamic_slice_in_dim(valid, at, c)[..., None]    # (C, page, 1)
        own = lax.dynamic_slice_in_dim(mine, at, c, axis=1)       # (B, C)
        kc = kpool[layer, ids].astype(jnp.float32)                # (C, page, HD)
        vc = vpool[layer, ids].astype(jnp.float32)
        s = jnp.einsum("crj,jh->crh", kc * qf[slot][:, None, :], seg,
                       precision=hi)                              # (C, page, Hl)
        if kscale is not None:
            s = s * kscale[layer, ids].reshape(c, hl, page).swapaxes(1, 2)
        s = jnp.where(ok, s, NEG)
        m_new = jnp.maximum(m, jnp.max(
            jnp.where(own[..., None] > 0, jnp.max(s, axis=1)[None], NEG),
            axis=1))
        p = jnp.where(ok, jnp.exp(s - m_new[slot][:, None, :]), 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.dot(own, jnp.sum(p, axis=1), precision=hi)
        if vscale is not None:
            p = p * vscale[layer, ids].reshape(c, hl, page).swapaxes(1, 2)
        pv = jnp.sum(jnp.einsum("crh,jh->crj", p, seg, precision=hi) * vc,
                     axis=1)                                      # (C, HD)
        acc_new = acc * jnp.dot(corr, seg.T, precision=hi) \
            + jnp.dot(own, pv, precision=hi)
        return acc_new, m_new, l_new

    acc, _, l = lax.fori_loop(
        0, n_chunks, walk,
        (jnp.zeros((b, hd), jnp.float32), jnp.full((b, hl), NEG, jnp.float32),
         jnp.zeros((b, hl), jnp.float32)))
    out = acc / jnp.dot(jnp.maximum(l, 1e-30), seg.T, precision=hi)
    return out.reshape(b, hl, dh)
