"""Pallas-native fused ring collectives: in-kernel int8 codec + RDMA hops.

The ``quant_ring`` lowering composes its compressed ring from ``lax`` ops —
separate quantize / ``ppermute`` / dequantize programs with XLA deciding the
buffering — so every hop round-trips HBM and the codec never overlaps the
DMA. This module is the hand-written alternative (ROADMAP #1, the EQuARX
design from PAPERS.md): ONE Pallas kernel owns the whole ring —

- per-hop inter-chip transfers are explicit ``pltpu.make_async_remote_copy``
  RDMA between VMEM comm slots, double-buffered (``MLSL_PALLAS_RING_SLOTS``
  recv slots per direction, a remote-capacity semaphore handshake guarding
  slot reuse) so hop t+1's wire time can hide behind hop t's codec work;
- the blockwise int8 quantize sits at the VMEM exit (the send slot is
  *written quantized*) and the dequantize is fused into the accumulate at
  the VMEM entry, so the wire stays int8 + per-block f32 scales across all
  G-1 hops and the f32 payload never leaves the chip;
- scales ride the same hop as their payload (a second RDMA per hop on the
  same link) — the THC observation that the compressed representation must
  survive the whole route, not be re-expanded per step;
- an optional bidirectional variant splits the payload's block-rows in two
  and runs opposite-rotation rings concurrently, putting both directions of
  each full-duplex ICI link to work (``MLSL_PALLAS_RING_BIDIR``).

The *entry* quantization (error feedback: ``xq = x + err`` → ``new_err =
xq - deq(q(xq))``) deliberately stays in the wrapper body and reuses
``quant_ring``'s exact helpers: on TPU that is already the fused Pallas
quantize kernel (ops/quant_kernels.py), and sharing the code is what makes
the error-feedback residual bit-exact with the ``quant_ring`` oracle — the
parity contract tests/test_pallas_ring.py pins.

Mesh/addressing: ring neighbors are *world-rank tables* (one row per group
instance, like rhd's member rows) looked up by this member's world rank and
handed to the kernel as scalar-prefetch operands; the RDMA targets them as
LOGICAL device ids (= position in the mesh's flattened device array, which
is grid-major world-rank order for both the 4-axis grid mesh and the flat
'world' mesh). One kernel therefore serves the standalone host-dispatch
program AND the compiled-overlap in-graph emission.

CPU testability: off-TPU the kernels run under the Pallas interpreter
(``interpret=True``), which this jax version executes with true cross-shard
remote-DMA semantics — with two restrictions the module works around:

- the interpreter resolves LOGICAL device ids only under a SINGLE named
  mesh axis, so host-dispatch programs compile over ``topology.flat_mesh``
  (the ``_build_flat`` convention rhd already uses); the in-graph overlap
  form — which must live inside the trainer's 4-axis shard_map — is
  TPU-only (``inline-eligibility`` gates it off the interpreter);
- a *remote* semaphore signal is not implemented, so interpret-mode kernels
  allocate one comm slot per hop (no slot reuse → the capacity handshake is
  statically elided); on TPU the handshake compiles in.

Gate: ``MLSL_PALLAS_INTERPRET`` (``1`` force-interpret, ``0``
force-compiled, unset = compiled on TPU, interpreted where the platform was
chosen to be the CPU — sysinfo.pallas_interpret).

A compiled kernel opens with ``entry_barrier`` (the barrier semaphore that
``collective_id`` allocates): no device starts its first remote DMA before
both ring neighbors have entered the kernel.
"""

from __future__ import annotations

import functools
import os
from typing import Callable, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mlsl_tpu.comm.mesh import GRID_AXES, ProcessGroup
from mlsl_tpu.log import mlsl_assert

#: dense ring chunk alignment (elements): 32 sublane rows x 128 lanes keeps
#: every per-chunk VMEM buffer tile-legal for f32/bf16/i32 alike
DENSE_UNIT = 32 * 128

#: widest group the unrolled hop schedule compiles for (2*(G-1) inline hop
#: bodies; past this the program size stops paying for itself — larger rings
#: belong to the hierarchical lowerings)
MAX_GROUP = 64

#: default comm slots per direction (the double buffer); overridden by
#: MLSL_PALLAS_RING_SLOTS / the builders' ``slots`` argument
DEFAULT_SLOTS = 2

#: kernel-config key -> collective id. Sequential allocation (no modular
#: hash: a hash collision between two ring geometries concurrently in
#: flight would share Mosaic barrier state and deadlock/corrupt on-chip).
#: Deterministic across hosts because SPMD hosts trace identical programs
#: in identical order — the same assumption every shard_map program makes.
_collective_ids: dict = {}


def _compiler_params(key: tuple, interpret: bool):
    """Mosaic parameters for one cross-device kernel configuration. A
    compiled kernel opens with ``entry_barrier`` on the barrier semaphore,
    which Mosaic allocates by ``collective_id``: the id must (a) agree across
    every device running THIS kernel and (b) differ between distinct kernels
    that may be in flight concurrently (the overlap engine can interleave
    several ring units) — allocated sequentially per kernel configuration
    from the registry above. Interpreted kernels run in lockstep and emit no
    barrier, and this jax refuses a collective_id on a kernel without one.
    has_side_effects: a DMA kernel must not be CSE'd or DCE'd."""
    if interpret:
        return pltpu.CompilerParams(has_side_effects=True)
    cid = _collective_ids.setdefault(key, len(_collective_ids))
    return pltpu.CompilerParams(collective_id=cid, has_side_effects=True)


def entry_barrier(peers) -> None:
    """Open a compiled cross-device kernel: tell every peer this device has
    entered, then wait until every peer said the same. Without it the first
    remote DMA can land in the VMEM scratch of a device that is still
    running the previous program. ``peers`` are LOGICAL device ids; the
    pairing is symmetric in every kernel of the family (each device hears
    from exactly the peers it signals), so the semaphore drains to zero."""
    sem = pltpu.get_barrier_semaphore()
    for dev in peers:
        pltpu.semaphore_signal(
            sem, inc=1, device_id=dev,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
    pltpu.semaphore_wait(sem, len(peers))


# ---------------------------------------------------------------------------
# Platform / knob gates
# ---------------------------------------------------------------------------


def _on_tpu() -> bool:
    from mlsl_tpu.sysinfo import on_tpu

    return on_tpu()


def interpret_mode() -> bool:
    """Whether kernel builds run under the Pallas interpreter
    (sysinfo.pallas_interpret: ``MLSL_PALLAS_INTERPRET=1`` or a chosen CPU
    platform — never a quiet slide off a backend that failed to start)."""
    from mlsl_tpu.sysinfo import pallas_interpret

    return pallas_interpret()


def available() -> bool:
    """Can the pallas_ring family serve requests on this backend? On TPU:
    always. Elsewhere only when the operator explicitly armed interpret mode
    (``MLSL_PALLAS_INTERPRET=1``) — the interpreter is a correctness
    vehicle, never a performance win, so plain CPU runs must not select it."""
    return _on_tpu() or os.environ.get("MLSL_PALLAS_INTERPRET", "").strip() == "1"


def env_slots(slots: Optional[int] = None) -> int:
    """Comm-slot count per direction: explicit argument > exported
    MLSL_PALLAS_RING_SLOTS > the Config default."""
    if slots is not None:
        return max(int(slots), 2)
    v = os.environ.get("MLSL_PALLAS_RING_SLOTS")
    return max(int(v), 2) if v not in (None, "") else DEFAULT_SLOTS


def env_bidir(bidir: Optional[bool] = None) -> bool:
    if bidir is not None:
        return bool(bidir)
    v = os.environ.get("MLSL_PALLAS_RING_BIDIR", "").strip().lower()
    return v not in ("", "0", "false", "no", "off")


def ring_axis(group: ProcessGroup) -> Optional[str]:
    """The single live mesh axis a pallas ring can ride, or None when the
    group does not reduce to one physical ring (color groups, true
    multi-axis sub-tori — those keep the lax/rhd/ring2d lowerings)."""
    if group.colors is not None or not group.axes:
        return None
    from mlsl_tpu.comm.collectives import _axis_sizes

    sizes = _axis_sizes(group.topology.mesh)
    live = [a for a in group.axes if sizes[a] > 1]
    if len(live) != 1:
        return None
    return live[0]


def ring_axes2(group: ProcessGroup) -> Optional[Tuple[str, str]]:
    """The (major, minor) live mesh axis pair a 2D-torus snake ring can ride,
    or None when the group is not an axis-aligned 2-axis sub-torus. The snake
    (boustrophedon) Hamiltonian cycle built over this pair alternates minor-
    axis hops within a row with major-axis hops between rows, so the one ring
    keeps BOTH axes' ICI links in flight (the PR 10 bidir split then rides
    each link's two directions on top)."""
    if group.colors is not None or not group.axes:
        return None
    from mlsl_tpu.comm.collectives import _axis_sizes

    sizes = _axis_sizes(group.topology.mesh)
    live = [a for a in group.axes if sizes[a] > 1]
    if len(live) != 2:
        return None
    return live[0], live[1]


def eligible_dense(kind: str, group: ProcessGroup, op=None) -> bool:
    """Engine eligibility for the dense f32/bf16/i32 variant: SUM-reduction
    ring math on a single-live-axis group of tractable size, on a backend
    that can actually run the kernel (TPU, or the explicit interpret gate)."""
    from mlsl_tpu.types import ReductionType

    if kind not in ("allreduce", "reduce_scatter"):
        return False
    if op not in (None, ReductionType.SUM):
        return False
    if not available():
        return False
    ax = ring_axis(group)
    if ax is None:
        return False
    return 1 < int(group.size) <= MAX_GROUP


def eligible_dense2d(kind: str, group: ProcessGroup, op=None) -> bool:
    """Eligibility for the 2D-torus snake-ring variant: the same dense ring
    math, but over an axis-aligned TWO-live-axis sub-torus (where the 1D ring
    is ineligible and ring2d's composed phases were the only topology-aware
    option)."""
    from mlsl_tpu.types import ReductionType

    if kind not in ("allreduce", "reduce_scatter"):
        return False
    if op not in (None, ReductionType.SUM):
        return False
    if not available():
        return False
    if ring_axes2(group) is None:
        return False
    return 1 < int(group.size) <= MAX_GROUP


def eligible_allgather(group: ProcessGroup) -> bool:
    """Eligibility for the all-gather phase kernel (the ZeRO-1 increment
    exchange): same ring shape constraints, no reduction op to restrict."""
    if not available():
        return False
    if ring_axis(group) is None:
        return False
    return 1 < int(group.size) <= MAX_GROUP


#: why the int8-fused variants (this ring's and pallas_a2a's) are parked on
#: the compiled backend: Mosaic (jax 0.9.0, libtpu 0.0.34, v5e) refuses the
#: (rows, 1) f32 scale buffers as RDMA operands (KNOWN_FAILURES.md)
QUANT_PARKED = (
    "Mosaic failed to compile TPU kernel: Slice shape along dimension 1 "
    "must be aligned to tiling (128), but is 1 — the per-row scale slots "
    "(rows, 1) cannot be sliced for a DMA"
)


def eligible_quant(group: ProcessGroup, block: int) -> bool:
    """Eligibility for the int8-fused variant: dense eligibility plus the
    codec's lane constraint (the quant block rides the VMEM lane dim).
    Interpreted builds only — the compiled kernel is parked (QUANT_PARKED),
    so on a TPU a forced or tuned pallas_ring keeps the composed quant
    ring, decided at selection and never at dispatch."""
    if block % 128 != 0 or not available() or not interpret_mode():
        return False
    ax = ring_axis(group)
    return ax is not None and 1 < int(group.size) <= MAX_GROUP


def inline_ok(group: ProcessGroup) -> bool:
    """Can the kernel be emitted IN-GRAPH (inside the compiled overlap
    engine's 4-axis shard_map)? Compiled-on-TPU only: the interpreter
    resolves remote DMA only under a single named axis, so both off-chip
    AND force-interpret-on-chip (MLSL_PALLAS_INTERPRET=1 debugging) the
    overlap plan falls back to the baseline (loudly, via the engine's
    eligibility gate)."""
    return (_on_tpu() and not interpret_mode()
            and ring_axis(group) is not None)


def inline_ok2d(group: ProcessGroup) -> bool:
    """inline_ok for the 2D snake ring: compiled-on-TPU over a 2-live-axis
    sub-torus (same interpreter restriction as the 1D form)."""
    return (_on_tpu() and not interpret_mode()
            and ring_axes2(group) is not None)


# ---------------------------------------------------------------------------
# Geometry
# ---------------------------------------------------------------------------


def dense_geometry(kind: str, group: ProcessGroup, count: int) -> Tuple[int, int, int]:
    """-> (g, rc, chunk): per-rank logical slice rc and the DENSE_UNIT-aligned
    ring chunk (the same slice-at-chunk-start placement as quant_ring)."""
    g = 1 if group.is_self else int(group.size)
    if kind == "reduce_scatter":
        mlsl_assert(count % g == 0,
                    "reduce_scatter count %d %% group %d != 0", count, g)
        rc = count // g
    elif kind == "all_gather":
        # count is the PER-MEMBER shard (the ZeRO-1 owned slice); the ring
        # circulates one chunk per member and the output is g * count
        rc = count
    else:
        rc = -(-count // g)
    chunk = -(-rc // DENSE_UNIT) * DENSE_UNIT
    return g, rc, chunk


def quant_geometry(
    kind: str, group: ProcessGroup, count: int, block: int
) -> Tuple[int, int, int, int]:
    """-> (g, rc, chunk, err_len) for the fused int8 ring. Mirrors
    quant_ring.ring_geometry with the *pallas* chunk units unconditionally
    (block*ROW_TILE, block*PACK_ROWS past the same threshold) — on TPU this
    IS ring_geometry's answer, and off-TPU using the pallas units keeps the
    interpret-mode kernel's layout identical to what the chip will run."""
    from mlsl_tpu.comm import quant_ring
    from mlsl_tpu.ops import quant_kernels as qk

    g = 1 if group.is_self else int(group.size)
    mlsl_assert(group.colors is None,
                "quantized collectives require axis-aligned groups")
    if kind == "reduce_scatter":
        mlsl_assert(count % g == 0,
                    "reduce_scatter count %d %% group %d != 0", count, g)
        rc = count // g
    else:
        rc = -(-count // g)
    unit = max(quant_ring._chunk_unit(rc, True, block), block * qk.ROW_TILE)
    chunk = -(-rc // unit) * unit
    return g, rc, chunk, g * chunk


def describe_plan(g: int, chunk_elems: int, quantized: bool, block: int,
                  bidir: bool, slots: int, dense_dtype="float32",
                  programs: int = 1) -> str:
    """The ``pallas.hop`` trace/span argument: hops, per-hop slot bytes and
    the codec, so a dispatch span names the wire plan it launched.
    ``dense_dtype`` is the dense wire dtype (f32/bf16/i32 — sizes the
    slot bytes); ``programs`` > 1 marks a large-message request split into
    independent per-chunk ring programs (the plan describes ONE chunk)."""
    dt = jnp.dtype(dense_dtype)
    hops = (g - 1) * (2 if bidir else 1)
    wire = chunk_elems + 4 * (chunk_elems // max(block, 1)) if quantized \
        else chunk_elems * dt.itemsize
    codec = f"int8/b{block}" if quantized else dt.name
    tail = f" programs={programs}" if programs > 1 else ""
    return (f"hops={hops} slot_bytes={wire} codec={codec} "
            f"slots={slots}{' bidir' if bidir else ''}{tail}")


def static_accounting(mode: str, g: int, slots: int, *, bidir: bool = False):
    """-> (events, total_hops, ndirs): the ordered capacity-semaphore event
    trace ONE kernel build emits — ``('wait', dir, hop)`` for slot_wait,
    ``('free', dir, use_hop)`` for slot_free — mirroring the guards in
    ``_ring_kernel_factory`` exactly (slot_wait fires for hops >= slots;
    slot_free only when a later hop reuses the slot, RS slots freed the hop
    they arrive, AG slots one hop later because the forward re-reads them).

    This is the statically-balanced accounting contract the kernel's
    docstrings promise ("sems drain to zero"): the plan verifier
    (mlsl_tpu/analysis/plan.py, MLSL-A130/A131) replays this trace and
    checks that every wait's matching free precedes it in program order and
    that signals == waits per direction at kernel exit. Kept HERE, next to
    the kernel, so the mirror and the emission evolve together — a change
    to slot_wait/slot_free that forgets this function fails the verifier's
    healthy-graph sweep."""
    hops = int(g) - 1
    total_hops = hops * (2 if mode == "allreduce" else 1)
    ndirs = 2 if bidir else 1
    events = []

    def slot_wait(h):
        if h >= slots:
            for d in range(ndirs):
                events.append(("wait", d, h))

    def slot_free(use_h):
        if use_h + slots <= total_hops - 1:
            for d in range(ndirs):
                events.append(("free", d, use_h))

    if mode == "all_gather":       # gather-only: the AG phase stands alone
        for k in range(hops):
            slot_wait(k)
            if k >= 1:
                slot_free(k - 1)   # an AG slot is re-read by the forward
        return events, total_hops, ndirs
    for t in range(hops):          # phase 1: ring reduce-scatter
        slot_wait(t)
        slot_free(t)               # an RS slot is consumed the hop it arrives
    if mode == "allreduce":        # phase 2: ring all-gather
        for k in range(hops):
            h = hops + k
            slot_wait(h)
            if k >= 1:
                slot_free(h - 1)   # an AG slot is re-read by the forward
    return events, total_hops, ndirs


def _ring_tables(group: ProcessGroup):
    """Per-world-rank ring addressing: ``(pos, right, left)`` int32 arrays of
    shape (W,) — this member's group position and its ring neighbors' WORLD
    ranks (= LOGICAL device ids in both mesh forms). One row per group
    instance, so one table set serves every instance of a subgroup ring."""
    from mlsl_tpu.comm import collectives

    rows = collectives._axis_groups_tbl(group)
    w = group.topology.world_size
    pos = np.zeros((w,), dtype=np.int32)
    right = np.zeros((w,), dtype=np.int32)
    left = np.zeros((w,), dtype=np.int32)
    for row in rows:
        g = len(row)
        for i, p in enumerate(row):
            pos[p] = i
            right[p] = row[(i + 1) % g]
            left[p] = row[(i - 1) % g]
    return pos, right, left


def _snake_order(row, a: int, b: int):
    """Reorder one group instance's member row (major-axis-major, length
    a*b) along the boustrophedon Hamiltonian cycle of the (a, b) torus:
    even major rows walk the minor axis ascending, odd rows descending, and
    the final wraparound hop closes the cycle on the major axis. Every
    minor-axis link inside a row and the major-axis links between rows are
    ring edges, so the one ring drives both axes' ICI concurrently."""
    return [row[i * b + (j if i % 2 == 0 else b - 1 - j)]
            for i in range(a) for j in range(b)]


def _ring_tables_2d(group: ProcessGroup):
    """``_ring_tables`` over the snake cycle of a 2-live-axis sub-torus:
    the SAME kernel runs unchanged — only the neighbor addressing differs."""
    from mlsl_tpu.comm import collectives

    axes2 = ring_axes2(group)
    mlsl_assert(axes2 is not None,
                "pallas_ring2d needs a 2-live-axis group (got axes=%s)",
                group.axes)
    sizes = collectives._axis_sizes(group.topology.mesh)
    a, b = int(sizes[axes2[0]]), int(sizes[axes2[1]])
    rows = collectives._axis_groups_tbl(group)
    w = group.topology.world_size
    pos = np.zeros((w,), dtype=np.int32)
    right = np.zeros((w,), dtype=np.int32)
    left = np.zeros((w,), dtype=np.int32)
    for row in rows:
        mlsl_assert(len(row) == a * b,
                    "pallas_ring2d group instance has %d members, torus is "
                    "%dx%d", len(row), a, b)
        cyc = _snake_order(row, a, b)
        g = len(cyc)
        for i, p in enumerate(cyc):
            pos[p] = i
            right[p] = cyc[(i + 1) % g]
            left[p] = cyc[(i - 1) % g]
    return pos, right, left


def _snake_perm(group: ProcessGroup) -> np.ndarray:
    """Ring-slot -> group-position chunk permutation for the snake cycle:
    the kernel scatters/gathers chunks by RING position, so the wrapper
    feeds kernel-chunk i = logical chunk ``perm[i]`` (the group position of
    the member at ring slot i). With that input order, reduce_scatter lands
    each member its OWN group-position chunk (the lax placement convention)
    and allreduce undoes the permutation on the way out."""
    from mlsl_tpu.comm import collectives

    axes2 = ring_axes2(group)
    sizes = collectives._axis_sizes(group.topology.mesh)
    a, b = int(sizes[axes2[0]]), int(sizes[axes2[1]])
    idx = list(range(a * b))
    return np.asarray(_snake_order(idx, a, b), dtype=np.int32)


# ---------------------------------------------------------------------------
# The kernel
# ---------------------------------------------------------------------------


def _quantize_rows(x):
    """(rows, block) f32 -> (int8 q, (rows, 1) f32 scales): the exact
    blockwise transform of quant_kernels.quantize_blocks_ref, emitted inside
    the kernel so the send slot is written already-compressed."""
    amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = jnp.where(amax == 0.0, 1.0, amax / 127.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _ring_kernel_factory(
    *,
    mode: str,            # 'allreduce' | 'reduce_scatter' | 'all_gather'
    G: int,
    rows: int,            # block-rows per chunk
    cols: int,            # lanes per row (the quant block, or 128 dense)
    quantized: bool,
    slots: int,
    dirs: Tuple[Tuple[int, int, int], ...],  # (sign, row_lo, row_len)
    handshake: bool,
    barrier: bool,
) -> Callable:
    """Build the kernel body. Hops are unrolled in Python (G <= MAX_GROUP):
    every hop's send slot is quantized on the way out of VMEM, RDMA'd with
    its scales, and dequantize-accumulated on the way in; slot reuse is
    guarded by the remote capacity handshake when compiled for the chip,
    and a compiled kernel opens with the neighbor ``entry_barrier``."""
    hops = G - 1
    total_hops = hops * (2 if mode == "allreduce" else 1)
    ndirs = len(dirs)
    mlsl_assert(not (mode == "all_gather" and quantized),
                "the all_gather phase kernel is dense-only (the ZeRO-1 "
                "increment exchange carries f32)")

    def kernel(pos_ref, right_ref, left_ref, x_ref, out_ref, *scr):
        if quantized:
            (acc, loc, qsend, ssend, qbuf, sbuf,
             csem, psend, precv, ssend_sem, srecv_sem) = scr[:11]
            cap = scr[11] if handshake else None
        else:
            acc, loc, fbuf, csem, psend, precv = scr[:6]
            cap = scr[6] if handshake else None

        pos = pos_ref[0]
        right = right_ref[0]
        left = left_ref[0]
        if barrier:
            entry_barrier((left, right))

        def copy_in(idx, dst, r0, rl, sem):
            c = pltpu.make_async_copy(
                x_ref.at[pl.ds(idx * rows + r0, rl)],
                dst.at[pl.ds(r0, rl)],
                sem,
            )
            c.start()
            return c

        def copy_out(src, r0, rl, idx, sem):
            c = pltpu.make_async_copy(
                src.at[pl.ds(r0, rl)],
                out_ref.at[pl.ds(idx * rows + r0, rl)],
                sem,
            )
            c.start()
            return c

        def rdma(src, dst, send_sem, recv_sem, dst_dev):
            c = pltpu.make_async_remote_copy(
                src_ref=src, dst_ref=dst, send_sem=send_sem,
                recv_sem=recv_sem, device_id=dst_dev,
                device_id_type=pltpu.DeviceIdType.LOGICAL,
            )
            c.start()
            return c

        def dmod(v):
            return lax.rem(v + 4 * G, G)

        def slot_wait(h):
            """Before sending into slot h%slots: wait until its previous use
            (hop h-slots) was freed by the consumer on the other end."""
            if handshake and h >= slots:
                for d in range(ndirs):
                    pltpu.semaphore_wait(cap.at[d], 1)

        def slot_free(use_h):
            """The slot used at hop ``use_h`` is fully consumed on this end:
            free it on its producer. Emitted only when some later hop will
            reuse the slot, so every wait has exactly one matching signal
            and the semaphore drains to zero at kernel exit."""
            if handshake and use_h + slots <= total_hops - 1:
                for d, (sign, _r0, _rl) in enumerate(dirs):
                    pltpu.semaphore_signal(
                        cap.at[d], inc=1,
                        device_id=left if sign > 0 else right,
                        device_id_type=pltpu.DeviceIdType.LOGICAL,
                    )

        # ---- init: each direction's travelling partial --------------------
        # (all_gather: x_ref holds only THIS member's shard — chunk index 0)
        pend = []
        for d, (sign, r0, rl) in enumerate(dirs):
            idx = 0 if mode == "all_gather" else dmod(pos - sign)
            pend.append(copy_in(idx, acc, r0, rl, csem.at[d]))
        for c in pend:
            c.wait()

        def hop_send(d, sign, r0, rl, slot, src_q, src_s, src_f):
            """One direction's hop transfer out of VMEM: the already-
            compressed payload plus its scales (or the dense chunk)."""
            dev = right if sign > 0 else left
            if quantized:
                cq = rdma(src_q, qbuf.at[slot, pl.ds(r0, rl)],
                          psend.at[d, slot], precv.at[d, slot], dev)
                cs = rdma(src_s, sbuf.at[slot, pl.ds(r0, rl)],
                          ssend_sem.at[d, slot], srecv_sem.at[d, slot], dev)
                return (cq, cs)
            cf = rdma(src_f, fbuf.at[slot, pl.ds(r0, rl)],
                      psend.at[d, slot], precv.at[d, slot], dev)
            return (cf,)

        # ---- phase 1: ring reduce-scatter (skipped by the gather-only mode)
        for t in ([] if mode == "all_gather" else range(hops)):
            slot = t % slots
            if quantized:
                # quantize on the way out of VMEM: the send buffer holds the
                # compressed form, never the f32 partial
                for d, (sign, r0, rl) in enumerate(dirs):
                    q, s = _quantize_rows(acc[pl.ds(r0, rl)])
                    qsend[pl.ds(r0, rl)] = q
                    ssend[pl.ds(r0, rl)] = s
            slot_wait(t)
            inflight = []
            for d, (sign, r0, rl) in enumerate(dirs):
                # prefetch this hop's local chunk while the wire is busy
                inflight.append(
                    copy_in(dmod(pos - sign * (2 + t)), loc, r0, rl,
                            csem.at[d])
                )
                inflight.extend(hop_send(
                    d, sign, r0, rl, slot,
                    qsend.at[pl.ds(r0, rl)] if quantized else None,
                    ssend.at[pl.ds(r0, rl)] if quantized else None,
                    None if quantized else acc.at[pl.ds(r0, rl)],
                ))
            for c in inflight:
                c.wait()
            for d, (sign, r0, rl) in enumerate(dirs):
                if quantized:
                    # dequantize fused into the accumulate on the way in
                    got = (qbuf[slot, pl.ds(r0, rl)].astype(jnp.float32)
                           * sbuf[slot, pl.ds(r0, rl)])
                else:
                    got = fbuf[slot, pl.ds(r0, rl)]
                acc[pl.ds(r0, rl)] = got + loc[pl.ds(r0, rl)]
            # an RS slot is never re-read: consumed the hop it arrives
            slot_free(t)

        if mode == "reduce_scatter":
            done = []
            for d, (sign, r0, rl) in enumerate(dirs):
                c = pltpu.make_async_copy(
                    acc.at[pl.ds(r0, rl)], out_ref.at[pl.ds(r0, rl)],
                    csem.at[d],
                )
                c.start()
                done.append(c)
            for c in done:
                c.wait()
            return

        # ---- phase 2: ring all-gather -------------------------------------
        # own chunk: (re)quantize once; the SAME compressed payload then
        # circulates all G-1 hops (no per-hop requantization — the wire
        # stays what the owner produced, the quant_ring contract)
        done = []
        for d, (sign, r0, rl) in enumerate(dirs):
            if quantized:
                q, s = _quantize_rows(acc[pl.ds(r0, rl)])
                qsend[pl.ds(r0, rl)] = q
                ssend[pl.ds(r0, rl)] = s
                loc[pl.ds(r0, rl)] = q.astype(jnp.float32) * s
                done.append(copy_out(loc, r0, rl, pos, csem.at[d]))
            else:
                done.append(copy_out(acc, r0, rl, pos, csem.at[d]))
        for c in done:
            c.wait()

        prev_slot = None
        base = 0 if mode == "all_gather" else hops
        for k in range(hops):
            h = base + k
            slot = h % slots
            slot_wait(h)
            inflight = []
            for d, (sign, r0, rl) in enumerate(dirs):
                if k == 0:
                    src_q = qsend.at[pl.ds(r0, rl)] if quantized else None
                    src_s = ssend.at[pl.ds(r0, rl)] if quantized else None
                    src_f = None if quantized else acc.at[pl.ds(r0, rl)]
                elif quantized:
                    src_q = qbuf.at[prev_slot, pl.ds(r0, rl)]
                    src_s = sbuf.at[prev_slot, pl.ds(r0, rl)]
                    src_f = None
                else:
                    src_q = src_s = None
                    src_f = fbuf.at[prev_slot, pl.ds(r0, rl)]
                inflight.extend(
                    hop_send(d, sign, r0, rl, slot, src_q, src_s, src_f)
                )
            for c in inflight:
                c.wait()
            if k >= 1:
                # the forward of prev_slot just completed (send waited):
                # ONLY NOW is an AG slot free for its producer to overwrite —
                # an AG slot is read twice, dequant+copy-out at its own hop
                # and the forward at the next
                slot_free(h - 1)
            done = []
            for d, (sign, r0, rl) in enumerate(dirs):
                idx = dmod(pos - sign * (1 + k))
                if quantized:
                    loc[pl.ds(r0, rl)] = (
                        qbuf[slot, pl.ds(r0, rl)].astype(jnp.float32)
                        * sbuf[slot, pl.ds(r0, rl)]
                    )
                    done.append(copy_out(loc, r0, rl, idx, csem.at[d]))
                else:
                    done.append(copy_out(fbuf.at[slot], r0, rl, idx,
                                         csem.at[d]))
            for c in done:
                c.wait()
            prev_slot = slot

    return kernel


@functools.lru_cache(maxsize=64)
def _ring_call(
    mode: str,
    G: int,
    rows: int,
    cols: int,
    dtype_str: str,
    quantized: bool,
    slots: int,
    bidir: bool,
    interpret: bool,
) -> Callable:
    """The compiled-or-interpreted pallas_call for one ring configuration.
    Cached per configuration (pure geometry — device addressing arrives as
    scalar-prefetch operands, so one call object serves every mesh)."""
    dtype = jnp.dtype(dtype_str)
    total_hops = (G - 1) * (2 if mode == "allreduce" else 1)
    if interpret:
        # no remote semaphore_signal in the interpreter: one slot per hop,
        # statically eliding the capacity handshake (no reuse, no hazard)
        slots_eff = max(total_hops, 1)
        handshake = False
    else:
        slots_eff = min(max(slots, 2), max(total_hops, 1))
        handshake = slots_eff < total_hops

    # bidirectional split: halve the block-rows on a tile boundary; rings
    # whose chunks cannot split cleanly run unidirectional
    row_tile = 32 if quantized else 8
    if bidir and rows >= 2 * row_tile:
        ra = (rows // 2 // row_tile) * row_tile
        dirs = ((1, 0, ra), (-1, ra, rows - ra))
    else:
        dirs = ((1, 0, rows),)
    ndirs = len(dirs)

    kern = _ring_kernel_factory(
        mode=mode, G=G, rows=rows, cols=cols, quantized=quantized,
        slots=slots_eff, dirs=dirs, handshake=handshake,
        barrier=not interpret,
    )

    out_rows = rows if mode == "reduce_scatter" else G * rows
    out_dtype = jnp.float32 if quantized else dtype
    if quantized:
        scratch = [
            pltpu.VMEM((rows, cols), jnp.float32),           # acc
            pltpu.VMEM((rows, cols), jnp.float32),           # loc / staging
            pltpu.VMEM((rows, cols), jnp.int8),              # qsend
            pltpu.VMEM((rows, 1), jnp.float32),              # ssend
            pltpu.VMEM((slots_eff, rows, cols), jnp.int8),   # qbuf
            pltpu.VMEM((slots_eff, rows, 1), jnp.float32),   # sbuf
            pltpu.SemaphoreType.DMA((ndirs,)),               # local copies
            pltpu.SemaphoreType.DMA((ndirs, slots_eff)),     # payload send
            pltpu.SemaphoreType.DMA((ndirs, slots_eff)),     # payload recv
            pltpu.SemaphoreType.DMA((ndirs, slots_eff)),     # scale send
            pltpu.SemaphoreType.DMA((ndirs, slots_eff)),     # scale recv
        ]
    else:
        scratch = [
            pltpu.VMEM((rows, cols), dtype),                 # acc
            pltpu.VMEM((rows, cols), dtype),                 # loc
            pltpu.VMEM((slots_eff, rows, cols), dtype),      # fbuf
            pltpu.SemaphoreType.DMA((ndirs,)),
            pltpu.SemaphoreType.DMA((ndirs, slots_eff)),
            pltpu.SemaphoreType.DMA((ndirs, slots_eff)),
        ]
    if handshake:
        scratch.append(pltpu.SemaphoreType.REGULAR((ndirs,)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,           # pos, right, left (world ranks)
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((out_rows, cols), out_dtype),
        grid_spec=grid_spec,
        compiler_params=_compiler_params(
            (mode, G, rows, cols, dtype_str, quantized, slots_eff,
             bidir, ndirs), interpret,
        ),
        interpret=interpret,
    )


# ---------------------------------------------------------------------------
# Wrapper bodies
# ---------------------------------------------------------------------------


def _world_rank_flat():
    return lax.axis_index("world")


def _world_rank_grid(group: ProcessGroup):
    from mlsl_tpu.comm.collectives import _axis_sizes, _group_rank

    sizes = _axis_sizes(group.topology.mesh)
    return lambda: _group_rank(GRID_AXES, sizes)


def _scalars(group: ProcessGroup, world_rank: Callable, snake: bool = False):
    """(pos, right, left) scalar-prefetch operands for this member. ``snake``
    addresses the boustrophedon cycle of a 2-live-axis sub-torus instead of
    the single-axis ring."""
    pos_t, right_t, left_t = (_ring_tables_2d(group) if snake
                              else _ring_tables(group))
    w = world_rank()
    take = lambda t: jnp.take(jnp.asarray(t), w)[None]
    return take(pos_t), take(right_t), take(left_t)


def dense_ring_body(
    kind: str,
    group: ProcessGroup,
    count: int,
    dtype,
    *,
    recv_count: Optional[int] = None,
    slots: Optional[int] = None,
    bidir: Optional[bool] = None,
    world_rank: Optional[Callable] = None,
    snake: bool = False,
) -> Callable:
    """-> local body ``(x) -> out`` for the dense (uncompressed) pallas ring,
    with the standard collectives calling convention: x is the squeezed
    per-member (count,) buffer, out the allreduce result (count,), the
    reduce_scatter slice (recv_count,), or the gathered (G*count,) buffer
    for ``kind='all_gather'`` (where x is this member's shard).
    ``world_rank`` supplies this member's world rank as a traced value —
    ``lax.axis_index('world')`` by default (the flat-mesh host program); the
    overlap engine passes the grid-mesh form. ``snake`` rides the 2D-torus
    boustrophedon cycle (pallas_ring2d) instead of the single-axis ring."""
    from mlsl_tpu.comm.quant_ring import _to_chunks

    if snake:
        mlsl_assert(ring_axes2(group) is not None,
                    "pallas_ring2d needs a 2-live-axis group (got axes=%s)",
                    group.axes)
    else:
        mlsl_assert(ring_axis(group) is not None,
                    "pallas_ring needs a single-live-axis group (got axes=%s)",
                    group.axes)
    g, rc, chunk = dense_geometry(kind, group, count)
    mlsl_assert(g > 1, "pallas_ring needs a group with >1 member")
    if kind == "reduce_scatter" and recv_count is not None:
        mlsl_assert(recv_count == rc,
                    "pallas_ring reduce_scatter recv_count %s != count//G %d",
                    recv_count, rc)
    rows, cols = chunk // 128, 128
    dt = jnp.dtype(dtype)
    call = _ring_call(kind, g, rows, cols, dt.name, False,
                      env_slots(slots), env_bidir(bidir), interpret_mode())
    wr = world_rank or _world_rank_flat

    perm = _snake_perm(group) if snake else None

    def body(x):
        pos, right, left = _scalars(group, wr, snake)
        if kind == "all_gather":
            xc = _to_chunks(x, 1, rc, chunk)        # (1, chunk) own shard
            out2d = call(pos, right, left, xc.reshape(rows, cols))
            outc = out2d.reshape(g, chunk)
            if perm is not None:
                # gathered chunks land by RING position: row i holds member
                # perm[i]'s shard — reorder to group-position (lax) order
                inv = np.argsort(perm).astype(np.int32)
                outc = jnp.take(outc, jnp.asarray(inv), axis=0)
            return outc[:, :rc].reshape(-1)
        xc = _to_chunks(x, g, rc, chunk)            # (g, chunk), dtype kept
        if perm is not None:
            # snake cycle: feed chunks in ring order (see _snake_perm)
            xc = jnp.take(xc, jnp.asarray(perm), axis=0)
        out2d = call(pos, right, left, xc.reshape(g * rows, cols))
        if kind == "reduce_scatter":
            return out2d.reshape(-1)[:rc]
        outc = out2d.reshape(g, chunk)
        if perm is not None:
            # undo the ring-order scatter: logical chunk perm[i] sits at row i
            inv = np.argsort(perm).astype(np.int32)
            outc = jnp.take(outc, jnp.asarray(inv), axis=0)
        return outc[:, :rc].reshape(-1)[:count]

    return body


def quant_ring_body(
    kind: str,
    group: ProcessGroup,
    count: int,
    block: int,
    *,
    slots: Optional[int] = None,
    bidir: Optional[bool] = None,
    world_rank: Optional[Callable] = None,
) -> Tuple[Callable, int]:
    """-> (local body ``(x, err) -> (out, new_err)``, error-feedback length)
    for the fused int8 pallas ring — the drop-in alternative to
    quant_ring._ring_body with identical entry error-feedback math (shared
    helpers, shared geometry units) so the residual is bit-exact with the
    composed ring and the supervisor's degrade flush
    (quant_ring.logical_residual) applies unchanged."""
    from mlsl_tpu.comm import quant_ring

    mlsl_assert(ring_axis(group) is not None,
                "pallas_ring needs a single-live-axis group (got axes=%s)",
                group.axes)
    mlsl_assert(block % 128 == 0,
                "pallas_ring int8 codec needs block %% 128 == 0 (got %d)",
                block)
    g, rc, chunk, err_len = quant_geometry(kind, group, count, block)
    mlsl_assert(g > 1, "pallas_ring needs a group with >1 member")
    rows, cols = chunk // block, block
    use_pallas = quant_ring.use_pallas_for(group, block)
    call = _ring_call(kind, g, rows, cols, "float32", True,
                      env_slots(slots), env_bidir(bidir), interpret_mode())
    wr = world_rank or _world_rank_flat

    def body(x, err):
        # entry quantization + error feedback: quant_ring's exact helpers
        # (the Pallas quantize kernel on TPU), so the residual the request
        # carries is bit-for-bit the composed ring's
        pos, right, left = _scalars(group, wr)
        xq = quant_ring._to_chunks(
            x.astype(jnp.float32), g, rc, chunk
        ).reshape(-1) + err
        q0, s0 = quant_ring._quant(xq.reshape(-1, block), use_pallas)
        xhat = quant_ring._dequant(
            q0.reshape(-1, block), s0, use_pallas
        ).reshape(-1)
        new_err = xq - xhat
        out2d = call(pos, right, left, xhat.reshape(g * rows, cols))
        if kind == "reduce_scatter":
            return out2d.reshape(-1)[:rc], new_err
        return (
            out2d.reshape(g, chunk)[:, :rc].reshape(-1)[:count],
            new_err,
        )

    return body, err_len


def build_flat_program(body: Callable, group: ProcessGroup, kind: str,
                       stateful: bool = False) -> Callable:
    """Compile a pallas-ring body over the flat 'world' mesh, accepting and
    returning standard (R, D, S, M, n) distributed buffers — the
    collectives._build_flat convention with replication checking off (a
    pallas_call output carries no VMA annotation). ``stateful`` wraps the
    ``(x, err) -> (out, new_err)`` error-feedback form."""
    from mlsl_tpu.comm.collectives import smap
    from jax.sharding import PartitionSpec as P

    topo = group.topology
    w = topo.world_size
    grid = topo.grid_shape

    if stateful:
        def local_fn(x, e):
            with jax.named_scope(f"mlsl_{kind}_pallas_ring"):
                out, new_err = body(x.reshape(x.shape[1:]),
                                    e.reshape(e.shape[1:]))
            return out[None], new_err[None]

        sm = smap(local_fn, topo.flat_mesh,
                  in_specs=(P("world", None), P("world", None)),
                  out_specs=(P("world", None), P("world", None)),
                  check=False)

        def fn(buf, err):
            out, new_err = sm(buf.reshape(w, buf.shape[-1]),
                              err.reshape(w, err.shape[-1]))
            return (out.reshape(*grid, out.shape[-1]),
                    new_err.reshape(*grid, new_err.shape[-1]))

        return jax.jit(fn)

    def local_fn(x):
        with jax.named_scope(f"mlsl_{kind}_pallas_ring"):
            out = body(x.reshape(x.shape[1:]))
        return out[None]

    sm = smap(local_fn, topo.flat_mesh,
              in_specs=P("world", None), out_specs=P("world", None),
              check=False)

    def fn(buf):
        out = sm(buf.reshape(w, buf.shape[-1]))
        return out.reshape(*grid, out.shape[-1])

    return jax.jit(fn)


def steps(
    kind: str,
    group: ProcessGroup,
    count: int,
    *,
    op=None,
    recv_count=None,
    slots: Optional[int] = None,
    bidir: Optional[bool] = None,
    snake: bool = False,
) -> Tuple[Callable, List[Callable], Callable]:
    """The compiled-overlap phase form (rhd.steps/ring2d.steps convention):
    ``(prep, phases, finish)`` with ONE phase — the whole fused ring is a
    single kernel launch, which is exactly the point: the overlap scheduler
    interleaves kernels between layers, and Mosaic owns the intra-kernel
    DMA/codec overlap. Bodies run inside the engine's 4-axis grid shard_map,
    so the world rank comes from the grid axes (TPU-only: ``inline_ok``).
    ``kind='all_gather'`` is the ZeRO-1 increment-exchange phase (no
    reduction op); ``snake`` selects the 2D-torus cycle (pallas_ring2d)."""
    from mlsl_tpu.types import ReductionType

    mlsl_assert(op in (None, ReductionType.SUM),
                "pallas_ring supports SUM only (got %s)", op)
    body = dense_ring_body(
        kind, group, count, jnp.float32, recv_count=recv_count,
        slots=slots, bidir=bidir, world_rank=_world_rank_grid(group),
        snake=snake,
    )

    def phase(carry):
        cur, mypos = carry
        return body(cur), mypos

    return (lambda x, mypos: (x, mypos)), [phase], (lambda carry: carry[0])
