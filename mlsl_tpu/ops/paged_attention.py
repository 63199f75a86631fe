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


# -- learned selection inside paged attention ---------------------------------
#
# A configuration with an indexer (``TransformerConfig.index_topk`` > 0)
# keeps a third pool of index keys under the same page tables. A query scores
# every earlier position of its sequence against the index keys, keeps the
# ``topk`` highest exactly (ties to the lower position), and attends over
# those positions only: the decode step gathers the selected rows of K and V
# from the pool where they lie, the prefill chunk masks a walk over the
# sequence's own pages. Plain JAX; what a Pallas kernel would fuse is in
# ROADMAP queue 2.


def _prec(dtype):
    """Float32 operands multiply at full precision (the tests' exactness);
    bfloat16 ones go to the MXU as they are."""
    return lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32 else None


def _ordered_bits(x):
    """float32 -> uint32 whose unsigned order is the floats' order (both
    zeros as +0: a sum of ``w * relu(.)`` terms comes out as either)."""
    bits = lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x), jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


#: the bytes of scores that are searched together, chosen on the chip (PERF.md
#: section 6, PR 33): with all of 2,048 x 33,280 at once every counting pass
#: reads the scores from HBM; 34 MB at a time they stay in fast memory across
#: the passes, at well under half the time (68 MB: half again as slow as 34;
#: 17 and 8.5 MB: a twentieth and a tenth slower).
GROUP_BYTES = 1024 * 8320 * 4


def exact_top_k_mask(scores, k):
    """The ``k[r]`` highest of each row of ``scores`` (R, S) f32, exactly,
    ties to the lower column: -> (R, S) bool. Positions that may not be
    chosen hold -inf, and ``k[r]`` is at most the number that may. No sort
    (``_top_k_rows``). ``lax.approx_max_k`` may miss a member and is a
    different result, not a faster one.

    Four rows or more with more than ``GROUP_BYTES`` of scores (a prefill
    chunk's) are searched one unit of that size after the other, so that a
    unit stays in fast memory while it is counted, and only as far as the
    last column that some row may choose: a row is cut into tiles of a
    quarter of S, a unit is the 1, 2 or 4 tiles that reach that far
    (``select_width`` is the same table for the host) of as many rows as
    fit, and its search adds up the counts of a row's tiles. One search in
    the program whatever the context held, at the cost of the context
    held."""
    rows, s = scores.shape
    if rows * s * 4 <= GROUP_BYTES or rows < 4:
        return _top_k_rows(scores, k)
    tile = _tile(s)
    unit = 4
    while 2 * unit <= rows and 2 * unit * tile * 4 <= GROUP_BYTES:
        unit *= 2
    chosen_from = jnp.any(scores > -jnp.inf, axis=0)
    live = jnp.max(jnp.where(chosen_from, jnp.arange(1, s + 1), 0))
    which = _reach(live, tile)
    parts = 1 << which
    whole = jnp.pad(scores, ((0, 0), (0, 4 * tile - s)),
                    constant_values=-jnp.inf)

    # unit g: the first p tiles of unit / p rows (the last unit starts where
    # it still fits, and searches some rows again)
    def cut(p):
        return lambda g: (
            lax.dynamic_slice(whole, (g * (unit // p), 0),
                              (unit // p, p * tile)).reshape(unit, tile),
            jnp.repeat(lax.dynamic_slice(k, (g * (unit // p),),
                                         (unit // p,)), p))

    def put(p):
        return lambda out, chosen, g: lax.dynamic_update_slice(
            out, chosen.reshape(unit // p, p * tile), (g * (unit // p), 0))

    def one_unit(g, out):
        chosen = _top_k_rows(
            *lax.switch(which, [cut(p) for p in (1, 2, 4)], g), parts)
        return lax.switch(which, [put(p) for p in (1, 2, 4)], out, chosen, g)

    out = lax.fori_loop(0, (rows * parts + unit - 1) // unit, one_unit,
                        jnp.zeros(whole.shape, bool))
    return out[:, :s]


def _tile(s: int) -> int:
    """A quarter of a row of ``s`` scores, in whole lanes of 128."""
    return -(-s // 512) * 128


def _reach(live, tile: int):
    """0, 1 or 2: a row's first 1, 2 or 4 tiles hold its ``live`` first
    columns (a traced value in the program, a plain number on the host; the
    0 makes it a sum of two traced booleans, not their ``or``)."""
    return (live > tile) + 0 + (live > 2 * tile)


def select_width(n_keys: int, s: int, topk: int) -> int:
    """The columns of a row of ``s`` scores that the selection of a chunk
    searches when its context is ``n_keys`` long (``exact_top_k_mask`` under
    ``select_in_context``): one, two or four quarters, and 0 where the
    context is no longer than ``topk`` and nothing is searched."""
    if n_keys <= topk:
        return 0
    return min(s, _tile(s) << int(_reach(n_keys, _tile(s))))


def _over_runs(x, member):
    """x (rows,) int32 in fours; member (4, 4) bool: -> (rows,), row j of a
    four gets the sum of the rows i of its four with ``member[i, j]``."""
    return jnp.sum(jnp.where(member, x.reshape(-1, 4, 1), 0),
                   axis=1).reshape(-1)


@jax.jit
def _top_k_rows(scores, k, parts=None):
    """``exact_top_k_mask`` of rows that are searched together (jitted, so
    that a program of many layers traces and lowers it once). The k-th
    highest value is found bit by bit, the highest first: 32 counting passes
    over the scores. Then the row keeps what lies above that value and the
    first of its equals, up to the column of the last one there is room for,
    which the counts of equals a block of 128 columns and one block a row
    give: no cumulative sum over the scores (10.9 of the 24.3 ms that 2,048
    x 33,280 took; some row of a wide chunk has a tie at its k-th value in
    every second layer, so skipping it when no row has one would not do).
    With ``parts`` (1, 2 or 4, a traced number) every aligned run of that
    many rows is one row cut into tiles, each with the row's ``k``."""
    rows, s = scores.shape
    u = _ordered_bits(scores)
    k = k.astype(jnp.int32)
    if parts is not None:
        j = jnp.arange(4)
        same = j[:, None] // parts == j[None, :] // parts
        whole_row = functools.partial(_over_runs, member=same)
        tiles_before = functools.partial(
            _over_runs, member=same & (j[:, None] < j[None, :]))

    def one_bit(i, thr):
        cand = thr | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        n = jnp.sum(u >= cand[:, None], axis=1, dtype=jnp.int32)
        if parts is not None:
            n = whole_row(n)
        return jnp.where(n >= k, cand, thr)

    thr = lax.fori_loop(0, 32, one_bit, jnp.zeros((rows,), jnp.uint32))
    # the column of the last equal that is kept: its block from the running
    # count of equals a block, its place from that block alone
    lanes = 128 if s % 128 == 0 else s
    blocks = u.reshape(rows, s // lanes, lanes)
    n_above = jnp.sum(blocks > thr[:, None, None], axis=2, dtype=jnp.int32)
    n_equal = jnp.sum(blocks == thr[:, None, None], axis=2, dtype=jnp.int32)
    room = k - jnp.sum(n_above, axis=1)
    if parts is not None:       # of the whole row, after its tiles before
        room = k - whole_row(jnp.sum(n_above, axis=1)) \
            - tiles_before(jnp.sum(n_equal, axis=1))
    upto = jnp.cumsum(n_equal, axis=1)
    at = jnp.minimum(jnp.sum(upto < room[:, None], axis=1, dtype=jnp.int32),
                     s // lanes - 1)
    before = jnp.take_along_axis(upto - n_equal, at[:, None], axis=1)
    block = jnp.take_along_axis(blocks, at[:, None, None], axis=1)[:, 0]
    within = before + jnp.cumsum(block == thr[:, None], axis=1,
                                 dtype=jnp.int32)
    last = at * lanes + jnp.sum(within < room[:, None], axis=1,
                                dtype=jnp.int32)
    last = jnp.where(room > 0, last, -1)
    column = jnp.arange(s, dtype=jnp.int32)
    return (u > thr[:, None]) | (
        (u == thr[:, None]) & (column[None, :] <= last[:, None]))


def index_scores(qi, wi, ki):
    """The indexer's score of each key for each query: ``sum_j w_j *
    relu(qI_j . kI_s)``. qi: (..., J, Di), wi: (..., J) f32, ki: (..., S, Di)
    with the same leading axes -> (..., S) f32."""
    s = jnp.einsum("...jd,...sd->...js", qi, ki,
                   preferred_element_type=jnp.float32,
                   precision=_prec(qi.dtype))
    return jnp.sum(jax.nn.relu(s) * wi[..., None], axis=-2)


def compact_selected(sel, table, k: int):
    """Where the selected tokens lie in the pool, without a sort or a
    scatter. sel: (B, P, page) bool, the selection over a slot's page table;
    table: (B, P) int32 pool pages. -> (rows (B, k) int32 into the pool seen
    as (pages * page) token rows, ok (B, k) bool): the j-th selected token of
    each slot in order of position, ``ok`` false past the slot's count. Two
    levels: the page of the j-th token from the running count of selected
    tokens a page, then its row from the running count inside that page;
    the look-ups are products with 0/1 matrices."""
    b, p, page = sel.shape
    hi = lax.Precision.HIGHEST
    incl = jnp.cumsum(jnp.sum(sel, axis=-1, dtype=jnp.int32), axis=-1)
    j = jnp.arange(k, dtype=jnp.int32)
    before = incl[:, None, :] <= j[None, :, None]               # (B, k, P)
    page_of = jnp.sum(before, axis=-1, dtype=jnp.int32)         # P = none left
    skipped = jnp.max(jnp.where(before, incl[:, None, :], 0), axis=-1)
    rank = j[None, :] - skipped + 1                             # within the page
    onehot = (page_of[:, :, None] == jnp.arange(p)[None, None, :]
              ).astype(jnp.float32)
    within = (jnp.cumsum(sel, axis=-1, dtype=jnp.int32) * sel
              ).astype(jnp.float32)                             # 0 = not chosen
    ranks = jnp.einsum("bkp,bpr->bkr", onehot, within, precision=hi)
    row = jnp.argmax(ranks == rank[:, :, None].astype(jnp.float32), axis=-1)
    pool_page = jnp.einsum("bkp,bp->bk", onehot, table.astype(jnp.float32),
                           precision=hi)
    ok = j[None, :] < incl[:, -1:]
    rows = jnp.round(pool_page).astype(jnp.int32) * page + row.astype(jnp.int32)
    return jnp.where(ok, rows, 0), ok


def selected_attention(q, kpool, vpool, layer, rows, ok, groups: int):
    """Decode attention over selected rows, read where they lie. q: (B, H,
    Dh); kpool / vpool: (n_layers, Np, page, G*Dh) with G key-value heads,
    query head h reading head ``h // (H // G)``; rows / ok: ``compact_
    selected``. Softmax in float32. -> (B, H, Dh) f32."""
    b, h, dh = q.shape
    n_l, n_p, page, gd = kpool.shape
    k = rows.shape[1]
    ksel = kpool.reshape(n_l, n_p * page, gd)[layer, rows].reshape(
        b, k, groups, dh)
    vsel = vpool.reshape(n_l, n_p * page, gd)[layer, rows].reshape(
        b, k, groups, dh)
    qg = (q * (1.0 / dh ** 0.5)).astype(kpool.dtype).reshape(
        b, groups, h // groups, dh)
    s = jnp.einsum("bgqx,bkgx->bgqk", qg, ksel,
                   preferred_element_type=jnp.float32,
                   precision=_prec(kpool.dtype))
    s = jnp.where(ok[:, None, None, :], s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgqk,bkgx->bgqx", p.astype(kpool.dtype), vsel,
                   preferred_element_type=jnp.float32,
                   precision=_prec(kpool.dtype))
    return o.reshape(b, h, dh)


def masked_context_attention(q, kctx, vctx, allowed, n_keys, block: int):
    """A prefill chunk's attention over its sequence's context, each query
    reading the keys ``allowed`` lets it. q: (C, H, Dh); kctx / vctx: (S, G,
    Dh), the sequence's pages side by side; allowed: (C, S) bool (selection
    and causality; every query allows at least one key under ``n_keys``);
    the walk reads ``block`` keys a trip and stops at ``n_keys``, so its
    work follows the context held, not the longest one. One pass: the
    exponent is shifted by a bound on the score (|q| * the longest key /
    sqrt(Dh)) in place of the running maximum, which a softmax does not
    notice. -> (C, H, Dh) f32."""
    c, h, dh = q.shape
    s_max, g, _ = kctx.shape
    per = h // g
    scale = 1.0 / dh ** 0.5
    dt = kctx.dtype
    prec = _prec(dt)
    live = (jnp.arange(s_max) < n_keys)[:, None]
    k_long = jnp.sqrt(jnp.max(jnp.where(
        live, jnp.sum(jnp.square(kctx.astype(jnp.float32)), axis=-1), 0.0),
        axis=0))                                                  # (G,)
    qf = q.astype(jnp.float32).reshape(c, g, per, dh)
    bound = jnp.sqrt(jnp.sum(jnp.square(qf), axis=-1)) \
        * k_long[None, :, None] * scale                           # (C, G, per)
    qg = (qf * scale).astype(dt)

    def walk(i, carry):
        acc, den = carry
        at = i * block
        kb = lax.dynamic_slice_in_dim(kctx, at, block)            # (block, G, Dh)
        vb = lax.dynamic_slice_in_dim(vctx, at, block)
        ok = lax.dynamic_slice_in_dim(allowed, at, block, axis=1)  # (C, block)
        s = jnp.einsum("cgqx,kgx->cgqk", qg, kb, precision=prec,
                       preferred_element_type=jnp.float32)
        p = jnp.where(ok[:, None, None, :],
                      jnp.exp(s - bound[..., None]), 0.0)
        den = den + jnp.sum(p, axis=-1)
        acc = acc + jnp.einsum("cgqk,kgx->cgqx", p.astype(dt), vb,
                               precision=prec,
                               preferred_element_type=jnp.float32)
        return acc, den

    acc, den = lax.fori_loop(
        0, (n_keys + block - 1) // block, walk,
        (jnp.zeros((c, g, per, dh), jnp.float32),
         jnp.zeros((c, g, per), jnp.float32)))
    return (acc / jnp.maximum(den, 1e-37)[..., None]).reshape(c, h, dh)


def context_index_scores(qi, wi, kictx, may, n_keys, block: int):
    """A chunk's index scores over its sequence's context. qi: (C, J, Di),
    wi: (C, J) f32, kictx: (S, Di), may: (C, S) bool (what a query may read
    at all). -> (C, S) f32 with -inf where ``may`` is false; the walk reads
    ``block`` keys a trip and stops at ``n_keys``."""
    c, s_max = may.shape

    def walk(i, out):
        at = i * block
        kb = lax.dynamic_slice_in_dim(kictx, at, block)
        ok = lax.dynamic_slice_in_dim(may, at, block, axis=1)
        sc = jnp.where(ok, index_scores(qi, wi, kb[None]), -jnp.inf)
        return lax.dynamic_update_slice_in_dim(out, sc, at, axis=1)

    return lax.fori_loop(0, (n_keys + block - 1) // block, walk,
                         jnp.full((c, s_max), -jnp.inf, jnp.float32))


def select_in_context(scores, room, n_keys, topk: int):
    """``exact_top_k_mask`` of a chunk's scores (C, S): nothing to choose
    while the context is no longer than ``topk`` (every position a query
    may read is selected)."""
    return lax.cond(n_keys > topk, lambda sc: exact_top_k_mask(sc, room),
                    lambda sc: sc > -jnp.inf, scores)
