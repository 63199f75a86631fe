"""Pallas latency-class allreduce: recursive halving/doubling in ONE kernel.

The fused ring (ops/ring_kernels.py) is bandwidth-optimal: 2(G-1) hops, each
carrying 1/G of the payload. Decode-shaped allreduces — the
``msg_priority_threshold`` class — are the opposite regime: the payload is a
few KiB and per-hop LATENCY dominates, so the winning schedule is the one
with the fewest serialized wire rounds. That is recursive halving/doubling
(eplib/allreduce_pr.c, the rhd lowering's pair math): ceil(log2 G) halving
rounds (each exchanging half the current window with a partner and
reducing), mirrored doubling rounds reassembling the full vector, plus one
pre/post fold pair for non-power-of-two groups — 2*log2(G) rounds total
instead of 2(G-1).

This module is that schedule as ONE Pallas kernel: every round is a single
symmetric ``make_async_remote_copy`` exchange between VMEM comm slots
(payloads this small never round-trip HBM between rounds), with the same
double-buffered slot + remote-capacity-handshake machinery as the ring
family and the same ``static_accounting`` mirror for the A130-A132 plan
verifier.

Uniform SPMD round schedule (no in-kernel predication): every member
executes every round. In a fold round, members without a partner RDMA to
THEMSELVES (their own logical id — a local loopback the DMA engine serves
without touching the wire) and the combine masks their contribution with a
``jnp.where`` on the member's traced group position — the same masking idiom
the ring kernel uses for direction splits. For power-of-two groups (every
proof-mesh and most production rings) no fold rounds exist and no self-copy
is ever emitted.

Addressing mirrors the ring: per-member scalar-prefetch operands — the group
position and a per-ROUND partner table of world ranks (= LOGICAL device
ids) — so one cached kernel serves every mesh. CPU testability, interpret
gating (``MLSL_PALLAS_INTERPRET``) and the flat-mesh host program follow
ring_kernels exactly.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mlsl_tpu.comm.mesh import ProcessGroup
from mlsl_tpu.log import mlsl_assert
from mlsl_tpu.ops import ring_kernels as rk

#: window alignment (elements): 8 sublane rows x 128 lanes — every halving
#: slice stays an f32-tile-legal row block
UNIT = 8 * 128


def _split(g: int) -> Tuple[int, int, int]:
    """-> (c, k, r): the largest power-of-two core c = 2**k <= g and the
    folded remainder r = g - c (rhd.steps' exact decomposition)."""
    c = 1 << (int(g).bit_length() - 1)
    return c, c.bit_length() - 1, int(g) - c


def rounds(g: int) -> int:
    """Total exchange rounds one build emits: pre-fold + k halvings +
    k doublings + post-fold."""
    c, k, r = _split(g)
    return 2 * k + (2 if r else 0)


def geometry(g: int, count: int) -> Tuple[int, int]:
    """-> (m, m_rows): the padded working size. m is ``count`` rounded up so
    every one of the k halvings splits on a UNIT boundary (m a multiple of
    c * UNIT) — the same align-up-then-slice placement the ring's chunks
    use."""
    c, _k, _r = _split(g)
    m = -(-int(count) // (c * UNIT)) * (c * UNIT)
    return m, m // 128


def eligible(kind: str, group: ProcessGroup, op=None) -> bool:
    """Engine eligibility: SUM allreduce on a uniform axis-aligned group of
    tractable size, on a backend that can run the kernel. Unlike the ring
    there is no single-live-axis restriction — partners are addressed by
    world rank, so any axis-aligned sub-grid works (the pairwise schedule
    does not care which physical links it crosses; at these payload sizes
    the wire is not the bottleneck)."""
    from mlsl_tpu.types import ReductionType

    if kind != "allreduce":
        return False
    if op not in (None, ReductionType.SUM):
        return False
    if not rk.available():
        return False
    if group.colors is not None or not group.axes or not group.is_uniform:
        return False
    return 1 < int(group.size) <= rk.MAX_GROUP


def inline_ok(group: ProcessGroup) -> bool:
    """In-graph (compiled overlap) emission: compiled-on-TPU only, the same
    interpreter restriction as the ring family."""
    return (not rk.interpret_mode() and rk._on_tpu()
            and group.colors is None and bool(group.axes))


def env_max_bytes(config=None) -> int:
    """The payload band (bytes) below which the selection table's heuristic
    rung prefers this kernel when ``MLSL_PALLAS_RHD`` armed it: an explicit
    ``MLSL_PALLAS_RHD_MAX_BYTES`` wins, else the existing small-message
    class boundary (msg_priority_threshold elements of f32)."""
    v = int(getattr(config, "pallas_rhd_max_bytes", 0) or 0)
    if v > 0:
        return v
    return 4 * int(getattr(config, "msg_priority_threshold", 10000))


def describe_plan(g: int, m_elems: int, slots: int) -> str:
    """The ``pallas.hop`` span argument (ring_kernels.describe_plan format):
    round count, the widest per-round transfer, codec and slot depth."""
    c, _k, r = _split(g)
    widest = m_elems if r else m_elems // 2
    return (f"hops={rounds(g)} slot_bytes={widest * 4} codec=rhd/f32 "
            f"slots={slots}")


def static_accounting(g: int, slots: int):
    """-> (events, total_hops, ndirs): the capacity-semaphore event trace,
    mirroring ``_rhd_kernel`` exactly — every round's recv slot is consumed
    (added/placed) the round it arrives and never re-read, so the trace is
    the ring's reduce-scatter shape over ``rounds(g)`` symmetric exchanges
    in one direction. The A130/A131 verifier replays this (analysis/plan.py)
    — keep it next to the emission."""
    total = rounds(g)
    events = []
    for t in range(total):
        if t >= slots:
            events.append(("wait", 0, t))
        if t + slots <= total - 1:
            events.append(("free", 0, t))
    return events, total, 1


def _rhd_kernel_factory(
    *, G: int, m_rows: int, slots: int, handshake: bool, barrier: bool,
) -> Callable:
    """Build the kernel body: the full pre-fold / halving / doubling /
    post-fold schedule unrolled in Python (G <= MAX_GROUP => at most
    2*log2(64)+2 = 14 rounds). Window offsets are traced (they depend on the
    member's position bits); window LENGTHS are static per round."""
    c, k, r = _split(G)
    R = rounds(G)

    def kernel(pos_ref, peers_ref, x_ref, out_ref, acc, rbuf, csem,
               psend, precv, *rest):
        cap = rest[0] if handshake else None
        pos = pos_ref[0]
        rel = lax.rem(pos, c)
        active = pos < c
        if barrier:
            # one signal per round partner (self in a masked fold round):
            # the pairing is symmetric, so R signals arrive here too
            rk.entry_barrier([peers_ref[h] for h in range(R)])

        cin = pltpu.make_async_copy(x_ref, acc, csem.at[0])
        cin.start()
        cin.wait()

        def slot_wait(h):
            if handshake and h >= slots:
                pltpu.semaphore_wait(cap.at[0], 1)

        def slot_free(use_h):
            # the slot used at round use_h is consumed: free it on the
            # device that produces its NEXT use — my partner at round
            # use_h + slots (whose slot_wait there blocks on MY signal,
            # the ring handshake's exact routing)
            if handshake and use_h + slots <= R - 1:
                pltpu.semaphore_signal(
                    cap.at[0], inc=1,
                    device_id=peers_ref[use_h + slots],
                    device_id_type=pltpu.DeviceIdType.LOGICAL,
                )

        def exchange(h, src_off, len_rows):
            """One symmetric round: send my [src_off, +len) window to this
            round's partner; its mirrored send lands in my slot h%slots."""
            slot = h % slots
            slot_wait(h)
            cx = pltpu.make_async_remote_copy(
                src_ref=acc.at[pl.ds(src_off, len_rows)],
                dst_ref=rbuf.at[slot, pl.ds(0, len_rows)],
                send_sem=psend.at[slot],
                recv_sem=precv.at[slot],
                device_id=peers_ref[h],
                device_id_type=pltpu.DeviceIdType.LOGICAL,
            )
            cx.start()
            cx.wait()
            return slot

        h = 0
        if r:
            # pre-fold: (c+j, j) pairs fold the remainder into the core;
            # only pos < r accumulates (everyone exchanges — unpaired
            # members loop back to themselves and mask)
            slot = exchange(h, 0, m_rows)
            got = rbuf[slot, pl.ds(0, m_rows)]
            acc[...] = acc[...] + jnp.where(pos < r, got, 0.0)
            slot_free(h)
            h += 1

        # halving: shrink the window log2(c) times, reducing as we go
        off = jnp.int32(0)
        for t in range(k):
            half = m_rows >> (t + 1)
            bit0 = ((rel >> (k - 1 - t)) & 1) == 0
            send_off = off + jnp.where(bit0, half, 0)
            new_off = off + jnp.where(bit0, 0, half)
            slot = exchange(h, send_off, half)
            got = rbuf[slot, pl.ds(0, half)]
            acc[pl.ds(new_off, half)] = acc[pl.ds(new_off, half)] + \
                jnp.where(active, got, 0.0)
            slot_free(h)
            off = new_off
            h += 1

        # doubling: mirror the halvings in reverse, reassembling the vector
        for s in range(k):
            cur = m_rows >> (k - s)
            bit0 = ((rel >> s) & 1) == 0
            slot = exchange(h, off, cur)
            recv_off = jnp.where(bit0, off + cur, off - cur)
            got = rbuf[slot, pl.ds(0, cur)]
            acc[pl.ds(recv_off, cur)] = jnp.where(
                active, got, acc[pl.ds(recv_off, cur)])
            slot_free(h)
            off = jnp.where(bit0, off, off - cur)
            h += 1

        if r:
            # post-fold: the core hands the finished vector back to the
            # folded members (pos >= c replaces; everyone else keeps acc)
            slot = exchange(h, 0, m_rows)
            got = rbuf[slot, pl.ds(0, m_rows)]
            acc[...] = jnp.where(pos >= c, got, acc[...])
            slot_free(h)
            h += 1

        cout = pltpu.make_async_copy(acc, out_ref, csem.at[0])
        cout.start()
        cout.wait()

    return kernel


@functools.lru_cache(maxsize=64)
def _rhd_call(G: int, m_rows: int, slots: int, interpret: bool) -> Callable:
    """The compiled-or-interpreted pallas_call for one rhd configuration
    (pure geometry — addressing arrives as scalar-prefetch operands)."""
    R = rounds(G)
    c, _k, r = _split(G)
    if interpret:
        # no remote semaphore_signal in the interpreter: one slot per round
        slots_eff = max(R, 1)
        handshake = False
    else:
        slots_eff = min(max(slots, 2), max(R, 1))
        handshake = slots_eff < R
    buf_rows = m_rows if r else max(m_rows // 2, 8)

    kern = _rhd_kernel_factory(
        G=G, m_rows=m_rows, slots=slots_eff, handshake=handshake,
        barrier=not interpret,
    )
    scratch = [
        pltpu.VMEM((m_rows, 128), jnp.float32),              # acc
        pltpu.VMEM((slots_eff, buf_rows, 128), jnp.float32),  # recv slots
        pltpu.SemaphoreType.DMA((1,)),                        # local copies
        pltpu.SemaphoreType.DMA((slots_eff,)),                # send
        pltpu.SemaphoreType.DMA((slots_eff,)),                # recv
    ]
    if handshake:
        scratch.append(pltpu.SemaphoreType.REGULAR((1,)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,           # pos, per-round partner ranks
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((m_rows, 128), jnp.float32),
        grid_spec=grid_spec,
        compiler_params=rk._compiler_params(
            ("rhd", G, m_rows, slots_eff, handshake), interpret,
        ),
        interpret=interpret,
    )


def _rhd_tables(group: ProcessGroup):
    """Per-world-rank addressing: ``pos`` (W,) group positions and
    ``peers`` (W, R) per-round partner WORLD ranks — self where the round's
    pairing leaves the member out (the masked loopback)."""
    from mlsl_tpu.comm import collectives

    g = int(group.size)
    c, k, r = _split(g)
    R = rounds(g)
    rows = collectives._axis_groups_tbl(group)
    w = group.topology.world_size
    pos = np.zeros((w,), dtype=np.int32)
    peers = np.zeros((w, max(R, 1)), dtype=np.int32)
    for row in rows:
        mlsl_assert(len(row) == g,
                    "pallas_rhd needs uniform group instances (got %d vs %d)",
                    len(row), g)
        for i, p in enumerate(row):
            pos[p] = i
            rr = []
            if r:
                rr.append(row[i + c] if i < r else
                          (row[i - c] if i >= c else p))
            for t in range(k):
                rr.append(row[i ^ (c >> (t + 1))] if i < c else p)
            for s in range(k):
                rr.append(row[i ^ (1 << s)] if i < c else p)
            if r:
                rr.append(row[i + c] if i < r else
                          (row[i - c] if i >= c else p))
            peers[p, :R] = rr
    return pos, peers


def _scalars(group: ProcessGroup, world_rank: Callable):
    pos_t, peers_t = _rhd_tables(group)
    wr = world_rank()
    pos = jnp.take(jnp.asarray(pos_t), wr)[None]
    peers = jnp.take(jnp.asarray(peers_t), wr, axis=0)
    return pos, peers


def allreduce_body(
    group: ProcessGroup,
    count: int,
    *,
    slots: Optional[int] = None,
    world_rank: Optional[Callable] = None,
) -> Callable:
    """-> local body ``(x) -> out`` (both (count,) f32) — the standard
    collectives calling convention, like ring_kernels.dense_ring_body."""
    g = int(group.size)
    mlsl_assert(g > 1, "pallas_rhd needs a group with >1 member")
    mlsl_assert(group.colors is None,
                "pallas_rhd needs an axis-aligned group")
    m, m_rows = geometry(g, count)
    call = _rhd_call(g, m_rows, rk.env_slots(slots), rk.interpret_mode())
    wr = world_rank or rk._world_rank_flat

    def body(x):
        pos, peers = _scalars(group, wr)
        xp = jnp.pad(x.astype(jnp.float32).reshape(-1), (0, m - count))
        out = call(pos, peers, xp.reshape(m_rows, 128))
        return out.reshape(-1)[:count]

    return body


def steps(
    kind: str,
    group: ProcessGroup,
    count: int,
    *,
    op=None,
    recv_count=None,
    slots: Optional[int] = None,
) -> Tuple[Callable, list, Callable]:
    """Compiled-overlap phase form: ONE phase (one kernel = one launch),
    the ring_kernels.steps convention. TPU-only in-graph (``inline_ok``)."""
    from mlsl_tpu.types import ReductionType

    mlsl_assert(kind == "allreduce",
                "pallas_rhd lowers allreduce only (got %s)", kind)
    mlsl_assert(op in (None, ReductionType.SUM),
                "pallas_rhd supports SUM only (got %s)", op)
    body = allreduce_body(
        group, count, slots=slots, world_rank=rk._world_rank_grid(group),
    )

    def phase(carry):
        cur, mypos = carry
        return body(cur), mypos

    return (lambda x, mypos: (x, mypos)), [phase], (lambda carry: carry[0])
