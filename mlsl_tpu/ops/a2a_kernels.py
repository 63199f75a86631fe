"""Pallas fused all-to-all: the int8 blockwise wire for MoE dispatch/combine.

``models/moe.py``'s expert exchange rides ``algos.inline_alltoall`` — until
this PR a bare ``lax.all_to_all``: f32 on the wire, no engine selection, no
kernel path. This module is the EQuARX/THC wire applied to the exchange
shape (ROADMAP #5): ONE Pallas kernel owns all G-1 transfer steps of the
shifted-permutation all-to-all —

- step t sends the chunk destined for member (pos+t)%G DIRECTLY to that
  device (one hop per chunk — an all-to-all has no reduction, so unlike the
  ring there is nothing to stage) and receives the chunk from (pos-t)%G into
  the double-buffered VMEM slot t%slots, capacity handshake guarding reuse;
- the blockwise int8 quantize sits at the VMEM exit (the send slot is
  written compressed; scales ride the same step) and the dequantize is fused
  at the VMEM entry on the receive side, so the wire carries
  1 byte + 4/block per element instead of 4 — the <= 1/3 wire-bytes contract
  the MoE latency row pins;
- the self chunk never touches the wire but STILL round-trips the codec
  locally, so every chunk of the result carries exactly one quantization
  hop — bit-identical to the composed lax oracle (quantize every chunk ->
  ``lax.all_to_all`` -> dequantize) the parity tests replay;
- entry error feedback stays in the wrapper with ``quant_ring``'s exact
  helpers (the stateful ``(x, err) -> (out, new_err)`` form), so a
  2-round EF-residual lockstep against the oracle is bit-exact — the same
  contract the fused ring pins.

The dense (f32, no codec) variant of the same kernel serves
``MLSL_PALLAS_A2A_QUANT=0`` and non-float payloads. Addressing, interpret
gating (``MLSL_PALLAS_INTERPRET``), scalar-prefetch tables and the
``static_accounting`` verifier mirror follow ops/ring_kernels.py exactly.
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


def eligible(kind: str, group: ProcessGroup, count: Optional[int] = None,
             op=None) -> bool:
    """Engine eligibility for the fused all-to-all: axis-aligned uniform
    groups of tractable size on a backend that can run the kernel. Chunks
    are addressed by world rank (LOGICAL ids), so multi-axis expert grids
    qualify like single rings do."""
    if kind != "alltoall" or op is not None:
        return False
    if not rk.available():
        return False
    if quant_enabled() and not rk.interpret_mode():
        # the int8 wire is parked on the compiled backend (rk.QUANT_PARKED):
        # a TPU runs this kernel dense (MLSL_PALLAS_A2A_QUANT=0) or not at all
        return False
    if group.colors is not None or not group.axes or not group.is_uniform:
        return False
    if not (1 < int(group.size) <= rk.MAX_GROUP):
        return False
    if count is not None and count % int(group.size) != 0:
        return False
    return True


def inline_ok(group: ProcessGroup) -> bool:
    """In-graph emission (inside models/moe.py's shard_map): compiled-on-TPU
    only — the interpreter's remote DMA needs the single flat axis, so
    off-chip the inline route falls back to lax LOUDLY (the engine logs)."""
    return (rk._on_tpu() and not rk.interpret_mode()
            and group.colors is None and bool(group.axes))


def quant_enabled(config=None) -> bool:
    """The a2a codec toggle: ``MLSL_PALLAS_A2A_QUANT`` (default ON — the
    compressed wire is the kernel's point; selecting the algo at all is
    already an explicit operator/tuner choice)."""
    if config is not None:
        return bool(getattr(config, "pallas_a2a_quant", True))
    import os

    v = os.environ.get("MLSL_PALLAS_A2A_QUANT", "").strip().lower()
    return v not in ("0", "false", "no", "off")


def geometry(g: int, count: int, block: int,
             quantized: bool) -> Tuple[int, int, int]:
    """-> (rc, chunk, rows): per-destination slice rc = count/G and its
    aligned chunk (slice-at-chunk-start, the quant_ring placement). The
    quantized chunk unit is block * ROW_TILE (int8 tile legality); dense
    chunks align to DENSE_UNIT."""
    mlsl_assert(count % g == 0,
                "alltoall count %d %% group %d != 0", count, g)
    rc = count // g
    if quantized:
        from mlsl_tpu.ops import quant_kernels as qk

        unit = block * qk.ROW_TILE
        chunk = -(-rc // unit) * unit
        return rc, chunk, chunk // block
    chunk = -(-rc // rk.DENSE_UNIT) * rk.DENSE_UNIT
    return rc, chunk, chunk // 128


def wire_bytes(g: int, count: int, block: int, quantized: bool) -> int:
    """Wire bytes ONE member puts on the fabric for one exchange (the G-1
    remote chunks; the self chunk stays local) — the analytic row the MoE
    latency bench reports against the f32 inline baseline."""
    rc, chunk, rows = geometry(g, count, block, quantized)
    per_chunk = chunk + 4 * rows if quantized else chunk * 4
    return (g - 1) * per_chunk


def describe_plan(g: int, count: int, block: int, quantized: bool,
                  slots: int) -> str:
    """The ``pallas.hop`` span argument, ring_kernels.describe_plan format."""
    rc, chunk, rows = geometry(g, count, block, quantized)
    wire = chunk + 4 * rows if quantized else chunk * 4
    codec = f"int8/b{block}" if quantized else "float32"
    return f"hops={g - 1} slot_bytes={wire} codec={codec} slots={slots}"


def static_accounting(g: int, slots: int):
    """-> (events, total_hops, ndirs): every step's recv slot is dequantized
    into the output the step it arrives and never re-read — the ring's
    reduce-scatter trace shape over G-1 steps, one direction. Mirrors
    ``_a2a_kernel_factory``'s slot_wait/slot_free guards for A130/A131."""
    hops = int(g) - 1
    events = []
    for t in range(hops):
        if t >= slots:
            events.append(("wait", 0, t))
        if t + slots <= hops - 1:
            events.append(("free", 0, t))
    return events, hops, 1


def _a2a_kernel_factory(
    *, G: int, rows: int, cols: int, quantized: bool, slots: int,
    handshake: bool, barrier: bool,
) -> Callable:
    """Build the kernel body: G-1 shifted-permutation steps unrolled in
    Python. Step t=1..G-1 (hop index h = t-1): quantize chunk (pos+t)%G out
    of VMEM, RDMA payload+scales to device (pos+t)%G's slot h%slots, fuse
    the dequantize into the receive placement at chunk (pos-t)%G."""
    hops = G - 1

    def kernel(pos_ref, to_ref, frm_ref, x_ref, out_ref, *scr):
        if quantized:
            loc, stg, qsend, ssend, qbuf, sbuf, csem, psend, precv, \
                ssend_sem, srecv_sem = scr[:11]
            cap = scr[11] if handshake else None
        else:
            loc, stg, fbuf, csem, psend, precv = scr[:6]
            cap = scr[6] if handshake else None

        pos = pos_ref[0]
        if barrier:
            # every other member is a send target exactly once, and sends
            # here exactly once: G-1 signals out, G-1 in
            rk.entry_barrier([to_ref[h] for h in range(hops)])

        def dmod(v):
            return lax.rem(v + 4 * G, G)

        def copy_in(idx, sem):
            c = pltpu.make_async_copy(
                x_ref.at[pl.ds(idx * rows, rows)], loc, sem)
            c.start()
            return c

        def copy_out(src, idx, sem):
            c = pltpu.make_async_copy(
                src, out_ref.at[pl.ds(idx * rows, rows)], sem)
            c.start()
            return c

        def slot_wait(h):
            if handshake and h >= slots:
                pltpu.semaphore_wait(cap.at[0], 1)

        def slot_free(use_h):
            # my slot used at step use_h is consumed: its next producer is
            # the device sending to me at step use_h + slots
            if handshake and use_h + slots <= hops - 1:
                pltpu.semaphore_signal(
                    cap.at[0], inc=1,
                    device_id=frm_ref[use_h + slots],
                    device_id_type=pltpu.DeviceIdType.LOGICAL,
                )

        # ---- self chunk: no wire, but the same single codec round-trip ----
        cin = copy_in(pos, csem.at[0])
        cin.wait()
        if quantized:
            q, s = rk._quantize_rows(loc[...])
            stg[...] = q.astype(jnp.float32) * s
            cs = copy_out(stg, pos, csem.at[0])
        else:
            cs = copy_out(loc, pos, csem.at[0])
        cs.wait()

        # ---- G-1 shifted-permutation steps --------------------------------
        for t in range(1, G):
            h = t - 1
            slot = h % slots
            cin = copy_in(dmod(pos + t), csem.at[0])
            cin.wait()
            if quantized:
                q, s = rk._quantize_rows(loc[...])
                qsend[...] = q
                ssend[...] = s
            slot_wait(h)
            dev = to_ref[h]
            if quantized:
                cq = pltpu.make_async_remote_copy(
                    src_ref=qsend, dst_ref=qbuf.at[slot],
                    send_sem=psend.at[slot], recv_sem=precv.at[slot],
                    device_id=dev,
                    device_id_type=pltpu.DeviceIdType.LOGICAL,
                )
                csc = pltpu.make_async_remote_copy(
                    src_ref=ssend, dst_ref=sbuf.at[slot],
                    send_sem=ssend_sem.at[slot], recv_sem=srecv_sem.at[slot],
                    device_id=dev,
                    device_id_type=pltpu.DeviceIdType.LOGICAL,
                )
                cq.start()
                csc.start()
                cq.wait()
                csc.wait()
                stg[...] = (qbuf[slot].astype(jnp.float32) * sbuf[slot])
                cdone = copy_out(stg, dmod(pos - t), csem.at[0])
            else:
                cf = pltpu.make_async_remote_copy(
                    src_ref=loc, dst_ref=fbuf.at[slot],
                    send_sem=psend.at[slot], recv_sem=precv.at[slot],
                    device_id=dev,
                    device_id_type=pltpu.DeviceIdType.LOGICAL,
                )
                cf.start()
                cf.wait()
                cdone = copy_out(fbuf.at[slot], dmod(pos - t), csem.at[0])
            cdone.wait()
            slot_free(h)

    return kernel


@functools.lru_cache(maxsize=64)
def _a2a_call(
    G: int, rows: int, cols: int, quantized: bool, slots: int,
    interpret: bool,
) -> Callable:
    """The compiled-or-interpreted pallas_call for one a2a configuration."""
    hops = G - 1
    if interpret:
        slots_eff = max(hops, 1)
        handshake = False
    else:
        slots_eff = min(max(slots, 2), max(hops, 1))
        handshake = slots_eff < hops

    kern = _a2a_kernel_factory(
        G=G, rows=rows, cols=cols, quantized=quantized, slots=slots_eff,
        handshake=handshake, barrier=not interpret,
    )
    if quantized:
        scratch = [
            pltpu.VMEM((rows, cols), jnp.float32),           # loc (f32 in)
            pltpu.VMEM((rows, cols), jnp.float32),           # staging out
            pltpu.VMEM((rows, cols), jnp.int8),              # qsend
            pltpu.VMEM((rows, 1), jnp.float32),              # ssend
            pltpu.VMEM((slots_eff, rows, cols), jnp.int8),   # qbuf
            pltpu.VMEM((slots_eff, rows, 1), jnp.float32),   # sbuf
            pltpu.SemaphoreType.DMA((1,)),                   # local copies
            pltpu.SemaphoreType.DMA((slots_eff,)),           # payload send
            pltpu.SemaphoreType.DMA((slots_eff,)),           # payload recv
            pltpu.SemaphoreType.DMA((slots_eff,)),           # scale send
            pltpu.SemaphoreType.DMA((slots_eff,)),           # scale recv
        ]
    else:
        scratch = [
            pltpu.VMEM((rows, cols), jnp.float32),           # loc
            pltpu.VMEM((rows, cols), jnp.float32),           # staging
            pltpu.VMEM((slots_eff, rows, cols), jnp.float32),  # fbuf
            pltpu.SemaphoreType.DMA((1,)),
            pltpu.SemaphoreType.DMA((slots_eff,)),
            pltpu.SemaphoreType.DMA((slots_eff,)),
        ]
    if handshake:
        scratch.append(pltpu.SemaphoreType.REGULAR((1,)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,      # pos, send-target table, recv-from table
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((G * rows, cols), jnp.float32),
        grid_spec=grid_spec,
        compiler_params=rk._compiler_params(
            ("a2a", G, rows, cols, quantized, slots_eff), interpret,
        ),
        interpret=interpret,
    )


def _a2a_tables(group: ProcessGroup):
    """Per-world-rank addressing: ``pos`` (W,), ``to`` (W, G-1) the step-t
    send target (pos+t)%G's world rank, ``frm`` (W, G-1) the step-t sender
    (pos-t)%G's world rank (the capacity handshake signals its successor)."""
    from mlsl_tpu.comm import collectives

    g = int(group.size)
    rows = collectives._axis_groups_tbl(group)
    w = group.topology.world_size
    pos = np.zeros((w,), dtype=np.int32)
    to = np.zeros((w, max(g - 1, 1)), dtype=np.int32)
    frm = np.zeros((w, max(g - 1, 1)), dtype=np.int32)
    for row in rows:
        mlsl_assert(len(row) == g,
                    "pallas_a2a needs uniform group instances (got %d vs %d)",
                    len(row), g)
        for i, p in enumerate(row):
            pos[p] = i
            for t in range(1, g):
                to[p, t - 1] = row[(i + t) % g]
                frm[p, t - 1] = row[(i - t) % g]
    return pos, to, frm


def _scalars(group: ProcessGroup, world_rank: Callable):
    pos_t, to_t, frm_t = _a2a_tables(group)
    wr = world_rank()
    take1 = lambda t: jnp.take(jnp.asarray(t), wr)[None]
    take2 = lambda t: jnp.take(jnp.asarray(t), wr, axis=0)
    return take1(pos_t), take2(to_t), take2(frm_t)


def alltoall_body(
    group: ProcessGroup,
    count: int,
    *,
    block: int = 256,
    quantized: bool = True,
    slots: Optional[int] = None,
    world_rank: Optional[Callable] = None,
) -> Callable:
    """-> local body ``(x) -> out`` (both (count,) f32): the stateless form
    (entry error feedback at zero — the inline MoE route, where no residual
    carries across calls). Chunk j of the output is the chunk member j sent
    here — ``lax.all_to_all``'s split_axis=0/concat_axis=0 layout on the
    flattened buffer."""
    body, _ = alltoall_body_ef(
        group, count, block=block, quantized=quantized, slots=slots,
        world_rank=world_rank,
    )

    def stateless(x):
        out, _new_err = body(x, None)
        return out

    return stateless


def alltoall_body_ef(
    group: ProcessGroup,
    count: int,
    *,
    block: int = 256,
    quantized: bool = True,
    slots: Optional[int] = None,
    world_rank: Optional[Callable] = None,
) -> Tuple[Callable, int]:
    """-> (body ``(x, err) -> (out, new_err)``, err_len): the stateful entry
    error-feedback form (quant_ring's exact helpers, so the residual is
    bit-exact with the composed oracle). ``err=None`` runs with a zero
    residual and returns the would-be residual."""
    from mlsl_tpu.comm import quant_ring

    g = int(group.size)
    mlsl_assert(g > 1, "pallas_a2a needs a group with >1 member")
    mlsl_assert(group.colors is None,
                "pallas_a2a needs an axis-aligned group")
    if quantized:
        mlsl_assert(block % 128 == 0,
                    "pallas_a2a int8 codec needs block %% 128 == 0 (got %d)",
                    block)
        mlsl_assert(rk.interpret_mode(),
                    "pallas_a2a int8 wire is parked on the compiled backend "
                    "(%s); set MLSL_PALLAS_A2A_QUANT=0", rk.QUANT_PARKED)
    rc, chunk, rows = geometry(g, count, block, quantized)
    cols = block if quantized else 128
    err_len = g * chunk if quantized else 0
    use_pallas = quant_ring.use_pallas_for(group, block) if quantized else False
    call = _a2a_call(g, rows, cols, quantized, rk.env_slots(slots),
                     rk.interpret_mode())
    wr = world_rank or rk._world_rank_flat

    def body(x, err):
        pos, to, frm = _scalars(group, wr)
        xc = quant_ring._to_chunks(
            x.astype(jnp.float32), g, rc, chunk
        ).reshape(-1)
        if quantized:
            xq = xc if err is None else xc + err
            q0, s0 = quant_ring._quant(xq.reshape(-1, block), use_pallas)
            xhat = quant_ring._dequant(
                q0.reshape(-1, block), s0, use_pallas
            ).reshape(-1)
            new_err = xq - xhat
            wire_in = xhat
        else:
            new_err = None
            wire_in = xc
        out2d = call(pos, to, frm, wire_in.reshape(g * rows, cols))
        out = out2d.reshape(g, chunk)[:, :rc].reshape(-1)
        return out, new_err

    return body, err_len


def steps(
    kind: str,
    group: ProcessGroup,
    count: int,
    *,
    block: int = 256,
    quantized: bool = True,
    slots: Optional[int] = None,
) -> Tuple[Callable, list, Callable]:
    """Compiled-overlap / inline phase form: ONE phase (one kernel launch),
    the ring_kernels.steps convention. TPU-only in-graph (``inline_ok``)."""
    mlsl_assert(kind == "alltoall",
                "pallas_a2a lowers alltoall only (got %s)", kind)
    body = alltoall_body(
        group, count, block=block, quantized=quantized, slots=slots,
        world_rank=rk._world_rank_grid(group),
    )

    def phase(carry):
        cur, mypos = carry
        return body(cur), mypos

    return (lambda x, mypos: (x, mypos)), [phase], (lambda carry: carry[0])
