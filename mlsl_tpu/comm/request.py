"""Asynchronous communication requests: the Start/Wait/Test engine.

Replaces the reference's CommRequest + eplib command queue (src/comm.hpp:368-409,
eplib/cqueue.c): where the reference hands a command to a shared-memory ring drained by
endpoint-server processes, here ``start`` dispatches an already-compiled XLA executable
— JAX's async dispatch returns immediately while the TPU runs the collective — and the
returned jax.Array is the completion handle (``block_until_ready`` = Wait,
``is_ready()`` = Test).

Also implements, as host-side scheduling policy:
- large-message chunking (reference splits >128 MiB allreduces, src/comm_ep.cpp:640-657):
  a big allreduce is dispatched as several independent chunk programs, so completion is
  incremental and chunks from different requests interleave;
- newest-first priority (reference eplib/allreduce_pr.c LIFO queue, :76-79): requests
  larger than the threshold are deferred onto a stack and dispatched LIFO at the next
  sync point, so the most recently produced gradients hit the wire first.
"""

# mlsl-lint: disable-file=A202 -- this module IS the dispatch engine: the
# Dispatcher's progress thread owns deferred dispatch, with explicit
# ordering/supersede invariants (see Dispatcher + flush docstrings). The
# A202 rule exists to keep dispatch OUT of every other background thread
# (the PR 6 loader contract); the engine itself is the sanctioned site.

from __future__ import annotations

import dataclasses
import enum
import threading
import time
from typing import Callable, List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from mlsl_tpu import chaos, checker, supervisor
from mlsl_tpu.obs import metrics as obs_metrics
from mlsl_tpu.obs import tracer as obs
from mlsl_tpu.comm.mesh import NUM_GRID_AXES, ProcessGroup
from mlsl_tpu.log import (
    MLSLError,
    MLSLTimeoutError,
    mlsl_assert,
    log_debug,
    log_error,
    log_warning,
)
from mlsl_tpu.comm import collectives
from mlsl_tpu.comm import algos
from mlsl_tpu.core import stats as stats_mod
from mlsl_tpu.types import (
    CompressionType,
    DataType,
    ReductionType,
    dtype_size,
    jnp_dtype,
)


class ComputeType(enum.IntEnum):
    """What a request carries (reference CommDesc src/comm.hpp:253-261)."""

    FPROP = 0
    BPROP = 1
    PARAM_GRAD = 2
    PARAM_INC = 3
    GENERIC = 4


@dataclasses.dataclass
class CommDesc:
    kind: str                      # 'allreduce' | 'bcast' | ... | 'barrier'
    group: ProcessGroup
    count: int                     # elements per rank (send side)
    data_type: DataType
    compute_type: ComputeType = ComputeType.GENERIC
    op: Optional[ReductionType] = None
    root: Optional[int] = None
    recv_count: Optional[int] = None
    recv_counts: Optional[tuple] = None
    send_counts: Optional[tuple] = None
    send_offsets: Optional[tuple] = None
    recv_offsets: Optional[tuple] = None
    pairs: Optional[tuple] = None  # sendrecv: ((src, dst), ...) member indices
    compression: CompressionType = CompressionType.NONE
    # registry codec pin (mlsl_tpu.codecs) for QUANTIZATION wires: '' = let
    # setup() resolve by request name (explicit MLSL_CODEC > calibrated
    # assignment > config.codec > int8); set by bucketing (members share one
    # codec) and by the guardrail demotion (pin to int8)
    codec: str = ""
    # per-set quant block override (0 = config.quant_block_elems)
    quant_block: int = 0

    def payload_bytes(self) -> int:
        return self.count * dtype_size(self.data_type)


class CommRequest:
    """One reusable communication request (the analog of a cached CommRequestImpl).

    Lifecycle: construct -> setup() (compile) -> start(buf) / wait() / test() any number
    of times. ``start`` never blocks; ``wait`` returns the result array.
    """

    _seq_lock = threading.Lock()
    _seq = 0

    def __init__(self, desc: CommDesc, dispatcher: "Dispatcher", name: str = ""):
        self.desc = desc
        self.dispatcher = dispatcher
        self.name = name
        self._fns: List[Callable] = []
        self._chunk_slices: List[slice] = []
        self._concat_fn: Optional[Callable] = None
        self._results: List[jax.Array] = []
        self._result: Optional[jax.Array] = None
        self._quant_fn: Optional[Callable] = None
        self._err: Optional[jax.Array] = None  # quantization error-feedback state
        self._quant_fns: Optional[List[Callable]] = None  # chunked quant programs
        self._err_lens: Optional[List[int]] = None
        self._errs: Optional[List[jax.Array]] = None
        self.is_started = False
        self.is_setup = False
        # which program family carries this request's collective: a comm/algos
        # registry name ('lax'/'rhd'/'ring2d') for the dense engine kinds, or
        # the compressed wire family ('quant_ring'/'custom_codec'/'topk').
        # Resolved at setup(); traces, stats, and describe() all report it.
        self.algo = algos.DEFAULT
        self._epoch = 0
        self._dlock = threading.Lock()  # serializes dispatch vs restart
        self._dispatch_error: Optional[BaseException] = None
        self._single_full = False  # hot path: one un-chunked program
        # recovery-ladder state (mlsl_tpu.supervisor). _breaker is None for
        # requests with no degradable subsystem (the plain 'lax' path) — the
        # hot dispatch then pays exactly one None test. Assigned at setup().
        self._breaker: Optional[supervisor.CircuitBreaker] = None
        self._degrade_subsys: Optional[str] = None
        self._degrade_fns: Optional[tuple] = None   # (flush jit, plain fn)
        self._degrade_geoms: Optional[List[tuple]] = None  # (count, err_len)/chunk
        self._err_layout: Optional[str] = None      # 'ring' | 'flat'
        self._lax_fns: Optional[List[Callable]] = None  # dense algo fallback
        self._lax_build: Optional[tuple] = None     # (dtype, kw) for it
        # last Start buffer: rung-2 wait retries re-dispatch it (a transient
        # wait failure leaves the in-flight round suspect). One reference —
        # comparable retention to the quant path's _err buffer.
        self._last_buf: Optional[jax.Array] = None
        # error-feedback state at Start (err, errs): any retry or degraded
        # re-attempt rewinds to this before re-dispatching — a failed (or
        # wait-failed) quantized dispatch has already advanced the residual,
        # and replaying from the advanced state would silently drop the
        # accumulated undelivered gradient
        self._ef_snapshot: tuple = (None, None)
        with CommRequest._seq_lock:
            CommRequest._seq += 1
            self.uid = CommRequest._seq
        # extra dispatch-span attribution (e.g. the pallas_ring 'pallas.hop'
        # wire plan), precomputed at setup so the hot path pays one **splat
        self._span_args: dict = {}
        # codec-lab state (mlsl_tpu.codecs): per-chunk registry geometry for
        # the verifier (A115/A116), the per-start wire accounting tuple
        # (codec label, compressed image bytes), the demotion latch, and the
        # pending exactly-once EF flush a demotion leaves for the next
        # successful dispatch
        self._codec_geoms: Optional[List[dict]] = None
        self._wire_rec: Optional[tuple] = None
        self._codec_demoted = False
        self._pending_flush: Optional[tuple] = None
        self.codec_name = ""      # resolved registry name ("" until setup)
        self.codec_source = ""    # env/calibrated/config/desc/demoted/...
        # effective int8 block (desc override > calibration cell > config):
        # the A112 geometry check must model THIS, not the session block
        self._eff_quant_block = 0
        # per-Start hot-path constants: keep the host dispatch floor low —
        # no per-dispatch string building / re-derivation
        self._trace_name = f"mlsl:{desc.kind}:{name or self.uid}"
        self._payload = desc.payload_bytes()
        # watchdog stamp: monotonic Start time of the current in-flight epoch
        self._started_at: Optional[float] = None

    # -- setup ------------------------------------------------------------

    def setup(self) -> None:
        """Build (and implicitly compile on first run) the collective programs."""
        d = self.desc
        if d.compression == CompressionType.TOPK:
            from mlsl_tpu.comm import sparse

            mlsl_assert(
                d.kind in ("allreduce", "reduce_scatter")
                and d.op in (None, ReductionType.SUM),
                "TOPK compression supports allreduce/reduce_scatter SUM only "
                "(got %s/%s)",
                d.kind, d.op,
            )
            _check_recv_count(d)
            ratio = self.dispatcher.config.topk_ratio
            self._quant_fn, self._err_len = sparse.build_sparse_collective(
                d.kind, d.group, d.count, ratio
            )
            self._chunk_slices = [slice(None)]
            self.algo = "topk"
            # per-codec wire accounting: the sparse image is k (value, index)
            # pairs of one full payload (core/stats CODEC_WIRE_BYTES)
            self._wire_rec = ("topk", 8 * max(1, int(d.count * ratio)))
            # ladder: the sparse wire rides the codec subsystem's breaker;
            # its residual is already in the logical layout ('flat')
            self._breaker = supervisor.breaker("quant")
            self._degrade_subsys = "quant"
            self._err_layout = "flat"
            self._degrade_geoms = [(d.count, self._err_len)]
            self.is_setup = True
            return
        if d.compression == CompressionType.QUANTIZATION and d.kind in (
            "allreduce",
            "reduce_scatter",
        ):
            mlsl_assert(
                d.op in (None, ReductionType.SUM),
                "quantized collectives support SUM only (got %s)",
                d.op,
            )
            _check_recv_count(d)
            from mlsl_tpu import codecs as codecs_mod

            cfg = self.dispatcher.config
            codec = getattr(cfg, "custom_codec", None)
            # registry resolution (mlsl_tpu.codecs.assigned): a user-plugged
            # CustomCodec wins outright (the dlopen contract predates the
            # registry); then an explicit desc pin (bucketing / demotion),
            # then MLSL_CODEC / the calibrated per-set assignment
            self._codec_geoms = None
            # setup() re-entry (calibration re-route at commit, guardrail
            # demotion): drop every stale program/geometry; residual state is
            # either virgin (pre-start) or was consumed by the caller
            # (demote_codec's exactly-once flush capture)
            self._quant_fn = None
            self._quant_fns = None
            self._err_lens = None
            self._err = None
            self._errs = None
            self._degrade_fns = None
            self._wire_rec = None
            self._span_args = {}
            reg_name, reg_cell, reg_src = "int8", None, "default"
            if codec is None:
                if self._codec_demoted:
                    reg_name, reg_src = "int8", "demoted"
                elif d.codec:
                    reg_name, reg_src = d.codec, "desc"
                else:
                    reg_name, reg_cell, reg_src = codecs_mod.assigned(
                        cfg, self.name
                    )
            # resolved identity for bucketing partitions / introspection
            self.codec_name = "custom" if codec is not None else reg_name
            self.codec_source = "custom" if codec is not None else reg_src
            block = int(
                d.quant_block or (reg_cell or {}).get("block", 0)
                or cfg.quant_block_elems
            )
            self._eff_quant_block = block
            if codec is None and reg_name == "topk":
                # registry route into the seed sparsifier: same wire, same
                # flat residual layout, ratio from the calibration cell
                from mlsl_tpu.comm import sparse

                ratio = float(
                    (reg_cell or {}).get("params", {}).get("ratio", 0)
                    or cfg.topk_ratio
                )
                self._quant_fn, self._err_len = sparse.build_sparse_collective(
                    d.kind, d.group, d.count, ratio
                )
                self._chunk_slices = [slice(None)]
                self.algo = "topk"
                self._breaker = supervisor.breaker("quant")
                self._degrade_subsys = "quant"
                self._err_layout = "flat"
                self._degrade_geoms = [(d.count, self._err_len)]
                self._wire_rec = ("topk", 8 * max(1, int(d.count * ratio)))
                if reg_src == "calibrated":
                    codecs_mod.guard_register(self)
                self.is_setup = True
                return
            self.algo = "custom_codec" if codec is not None else "quant_ring"
            reg_codec = None
            if codec is not None:
                # user-pluggable codec (reference dlopen contract,
                # quant/quant.c:96-133): compressed ring wire, framework-owned
                # error feedback
                from mlsl_tpu.comm import codec as codec_mod

                def build(n):
                    return codec_mod.build_custom_collective(
                        d.kind, d.group, n, codec
                    )
            elif reg_name != "int8":
                # registry codec ('vq'/'prune'/'f32'/plugins) on the SAME
                # compressed-ring transport as the dlopen contract: entry EF,
                # per-hop encode, compressed-domain aggregate when declared
                from mlsl_tpu.comm import codec as codec_mod

                reg_codec = codecs_mod.configure(reg_name, cfg, reg_cell)
                wrapped = reg_codec.as_custom()
                self.algo = f"codec:{reg_name}"

                def build(n):
                    return codec_mod.build_custom_collective(
                        d.kind, d.group, n, wrapped
                    )
            else:
                from mlsl_tpu.comm import quant_ring
                # hop-engine selection through the PR 4 table: a forced or
                # tuned 'pallas_ring' routes the SAME compressed wire family
                # through the fused kernel (identical entry error feedback,
                # identical residual layout — quant_ring ring='pallas');
                # 'hier' routes it through the two-tier decomposition (the
                # codec applies only on the DCN hop; per-shard residual
                # layout — quant_ring ring='hier')
                ring = "lax"
                ring_kw = {}
                sel = algos.select(d.kind, d.group, self._payload,
                                   d.compression, cfg, op=d.op)
                if sel == "pallas_ring":
                    ring = "pallas"
                    self.algo = "pallas_ring"
                    ring_kw = dict(
                        slots=int(getattr(cfg, "pallas_ring_slots", 2)),
                        bidir=bool(getattr(cfg, "pallas_ring_bidir", False)),
                    )
                elif sel == "hier":
                    ring = "hier"
                    self.algo = "hier"
                    ring_kw = dict(
                        dcn_codec=getattr(cfg, "hier_dcn_codec", None),
                        topk_ratio=float(getattr(cfg, "topk_ratio", 0.01)),
                    )

                def build(n):
                    return quant_ring.build_quantized_collective(
                        d.kind, d.group, n, block, ring=ring, **ring_kw
                    )

            chunks = self._plan_chunks(compressed_ok=True)
            if chunks is not None and d.kind == "allreduce":
                # large quantized allreduce: independent per-chunk ring programs,
                # each with its own error-feedback state (slices are disjoint)
                self._quant_fns = []
                self._err_lens = []
                for sl in chunks:
                    fn, el = build(sl.stop - sl.start)
                    self._quant_fns.append(fn)
                    self._err_lens.append(el)
                self._chunk_slices = chunks
                self._degrade_geoms = [
                    (sl.stop - sl.start, el)
                    for sl, el in zip(chunks, self._err_lens)
                ]
            else:
                self._quant_fn, self._err_len = build(d.count)
                self._chunk_slices = [slice(None)]
                self._degrade_geoms = [(d.count, self._err_len)]
            if self.algo == "pallas_ring":
                # span reflects the geometry of ONE dispatched program (a
                # chunked request splits into independent per-chunk rings)
                self._set_pallas_span(
                    d, block, quantized=True,
                    count=(self._chunk_slices[0].stop
                           - self._chunk_slices[0].start)
                    if self._chunk_slices[0] != slice(None) else d.count,
                    programs=len(self._chunk_slices), **ring_kw,
                )
            # ladder: codec faults count against the quant breaker; when it
            # trips, dispatch degrades to the plain f32 SUM program with the
            # residual flushed (_dispatch_degraded)
            self._breaker = supervisor.breaker("quant")
            self._degrade_subsys = "quant"
            if self.algo == "hier":
                # per-shard residual layout: each member owns its own 1/L
                # slice's error; the degrade flush re-places it at that
                # slice's logical offset (hier.flush_residual) via the
                # static intra-tier position table captured here
                from mlsl_tpu.comm.algos import hier

                self._err_layout = "hier"
                self._hier_meta = (
                    hier.tier_structure(d.group)[1],
                    hier.intra_positions(d.group),
                )
            else:
                self._err_layout = "ring"  # quant_ring AND custom_codec
            # codec-lab accounting: per-chunk registry geometry (the
            # verifier's A115/A116 anchor — what the programs were ACTUALLY
            # built from) and the per-start wire-byte record. Wire bytes are
            # the compressed image of one full payload — the codec-comparable
            # signal, not per-hop wire traffic (which varies by ring shape).
            g_sz = 1 if d.group.is_self else d.group.size
            rs = d.kind == "reduce_scatter"
            if reg_codec is not None:
                self._codec_geoms = []
                for n, el in self._degrade_geoms:
                    hop = n // g_sz if rs else -(-n // g_sz)
                    geom = reg_codec.geometry(hop)
                    geom["err_len"] = int(el)
                    geom["hops"] = g_sz
                    self._codec_geoms.append(geom)
                self._wire_rec = (reg_name, sum(
                    reg_codec.wire_len(n) for n, _ in self._degrade_geoms
                ))
            elif codec is not None:
                self._wire_rec = ("custom", _custom_wire_bytes(
                    codec, self._degrade_geoms
                ))
            else:
                int8_image = codecs_mod.get("int8", block=block)
                self._wire_rec = ("int8", sum(
                    int8_image.wire_len(n) for n, _ in self._degrade_geoms
                ))
            if reg_src == "calibrated" and reg_name != "int8":
                # calibrated non-int8 assignment: place this request under
                # the sentinel-fed convergence guardrail (demotes to int8 on
                # a sustained loss z-score breach)
                codecs_mod.guard_register(self)
            self.is_setup = True
            return
        if d.kind == "barrier":
            self._fns = [collectives.build_barrier(d.group)]
            self._chunk_slices = [slice(None)]
            self._single_full = True
            self.is_setup = True
            return

        kw = {}
        if d.op is not None:
            kw["op"] = ReductionType(d.op)
        if d.root is not None:
            kw["root"] = int(d.root)
        if d.recv_count is not None:
            kw["recv_count"] = int(d.recv_count)
        if d.recv_counts is not None and d.kind != "alltoallv":
            # alltoallv's recv_counts may be a full (G, G) matrix and is
            # consumed by _normalize_alltoallv below, not flattened here.
            kw["recv_counts"] = tuple(int(c) for c in d.recv_counts)
        if d.kind == "alltoall":
            kw["send_count"] = int(d.count)
        if d.kind == "sendrecv":
            kw["pairs"] = tuple((int(s), int(t)) for s, t in d.pairs)
        if d.kind == "alltoallv":
            kw.update(_normalize_alltoallv(d))

        dtype = jnp_dtype(d.data_type)
        # Algorithm selection (comm/algos): explicit config > tuned profile >
        # the 'lax' baseline. 'lax' routes through build_collective unchanged
        # — same cache entry, same program, bit-for-bit the untuned behavior.
        # Chunked requests select once on the FULL payload (the knob the
        # operator reasons about) and reuse one program across chunks.
        self.algo = algos.select(
            d.kind, d.group, self._payload, d.compression,
            self.dispatcher.config, op=kw.get("op"),
        )
        lax_kw = dict(kw)
        if self.algo in ("pallas_ring", "pallas_ring2d"):
            # kernel-geometry knobs ride the build kw (and so the program
            # cache key) — but never the 'lax' fallback build below
            cfg = self.dispatcher.config
            kw["slots"] = int(getattr(cfg, "pallas_ring_slots", 2))
            kw["bidir"] = bool(getattr(cfg, "pallas_ring_bidir", False))
        elif self.algo in ("pallas_rhd", "pallas_a2a"):
            cfg = self.dispatcher.config
            kw["slots"] = int(getattr(cfg, "pallas_ring_slots", 2))
            if self.algo == "pallas_a2a":
                from mlsl_tpu.ops import a2a_kernels
                kw["block"] = int(getattr(cfg, "quant_block_elems", 256))
                kw["quantized"] = a2a_kernels.quant_enabled(cfg)
        chunks = self._plan_chunks()
        span_count = ((chunks[0].stop - chunks[0].start) if chunks
                      else d.count)
        span_programs = len(chunks) if chunks else 1
        if self.algo in ("pallas_ring", "pallas_ring2d"):
            # the snake ring is the same kernel program over 2D neighbour
            # tables — the 1D describe_plan IS its wire plan
            self._set_pallas_span(
                d, None, quantized=False, slots=kw["slots"],
                bidir=kw["bidir"], count=span_count,
                programs=span_programs,
            )
        elif self.algo == "pallas_rhd":
            from mlsl_tpu.ops import rhd_kernels
            g = 1 if d.group.is_self else int(d.group.size)
            m, _ = rhd_kernels.geometry(g, span_count)
            self._span_args = {
                "pallas.hop": rhd_kernels.describe_plan(g, m, kw["slots"])
            }
        elif self.algo == "pallas_a2a":
            from mlsl_tpu.ops import a2a_kernels
            cfg = self.dispatcher.config
            g = 1 if d.group.is_self else int(d.group.size)
            # an alltoall desc's count is the PER-DESTINATION send_count;
            # the kernel's wire plan covers the g-chunk exchange
            self._span_args = {
                "pallas.hop": a2a_kernels.describe_plan(
                    g, g * span_count,
                    int(getattr(cfg, "quant_block_elems", 256)),
                    a2a_kernels.quant_enabled(cfg), kw["slots"],
                )
            }
        if chunks is None:
            self._fns = [algos.build(d.kind, d.group, dtype, self.algo, **kw)]
            self._chunk_slices = [slice(None)]
        else:
            fn = algos.build(d.kind, d.group, dtype, self.algo, **kw)
            self._fns = [fn] * len(chunks)
            self._chunk_slices = chunks
        if self.algo != algos.DEFAULT:
            # ladder: a tuned/forced algorithm can degrade to the 'lax'
            # baseline per dispatch; the baseline itself has no lower rung
            # (its failures escalate straight to supervised restart)
            self._breaker = supervisor.breaker("algo")
            self._degrade_subsys = "algo"
            self._lax_build = (dtype, lax_kw)
        # hot-path precomputation: the per-layer dispatch floor must stay in
        # single-digit µs, so nothing re-derived per Start
        self._single_full = (
            len(self._chunk_slices) == 1 and self._chunk_slices[0] == slice(None)
        )
        self.is_setup = True

    def _set_pallas_span(self, d: CommDesc, block: Optional[int], *,
                         quantized: bool, slots=None, bidir=None,
                         count: Optional[int] = None,
                         programs: int = 1) -> None:
        """Precompute the ``pallas.hop`` dispatch-span argument (hops, slot
        bytes, codec) for a request the table routed to the fused kernel —
        the wire plan belongs on the trace next to the algorithm name.
        ``count`` is the per-program element count (ONE chunk of a split
        large-message request), ``programs`` the number of chunk rings."""
        from mlsl_tpu.ops import ring_kernels as rk

        cfg = self.dispatcher.config
        slots = rk.env_slots(
            slots if slots is not None
            else getattr(cfg, "pallas_ring_slots", None)
        )
        bidir = rk.env_bidir(
            bidir if bidir is not None
            else getattr(cfg, "pallas_ring_bidir", None)
        )
        count = d.count if count is None else int(count)
        if quantized:
            g, _, chunk, _ = rk.quant_geometry(d.kind, d.group, count, block)
        else:
            g, _, chunk = rk.dense_geometry(d.kind, d.group, count)
        self._span_args = {
            "pallas.hop": rk.describe_plan(
                g, chunk, quantized, block or 0, bidir, slots,
                dense_dtype=jnp_dtype(d.data_type), programs=programs,
            )
        }

    def precompile(self) -> int:
        """Run every compiled program once on zero buffers so the jit caches
        are hot before the first timed step (Session.precompile_collectives /
        MLSL_PRECOMPILE). A warm CALL is required — jax's AOT
        lower().compile() does not populate the dispatch cache the normal
        call path consults, so only execution removes the step-0 stall (the
        isolation replay relies on the same fact). Request round state
        (_results / is_started / the error-feedback buffers) is untouched: a
        never-started request must not look completed afterwards, and a zero
        warm must not perturb _err. Returns the number of programs run."""
        mlsl_assert(self.is_setup, "request must be setup() before precompile()")
        d = self.desc
        topo = d.group.topology
        buf = topo.shard_buffer(
            np.zeros((*topo.grid_shape, max(d.count, 1)), dtype=jnp_dtype(d.data_type))
        )

        def zero_err(el):
            return topo.shard_buffer(
                np.zeros((*topo.grid_shape, el), dtype=np.float32)
            )

        n = 0
        seen: set = set()  # chunked requests repeat one program across
        # same-length chunks ([fn]*k, shared quant fns) — warm each distinct
        # (program, chunk length) once, not once per chunk

        def warm(fn, sl, *err):
            nonlocal n
            inner = _unwrap_chaos(fn)
            key = (id(inner), sl.stop - sl.start if sl.stop is not None else None)
            if key in seen:
                return
            seen.add(key)
            arg = buf if sl == slice(None) else buf[..., sl]
            jax.block_until_ready(inner(arg, *err))
            n += 1

        if self._quant_fns is not None:
            for fn, sl, el in zip(
                self._quant_fns, self._chunk_slices, self._err_lens
            ):
                warm(fn, sl, zero_err(el))
        elif self._quant_fn is not None:
            warm(self._quant_fn, slice(None), zero_err(self._err_len))
        elif self._single_full:
            warm(self._fns[0], slice(None))
        else:
            for fn, sl in zip(self._fns, self._chunk_slices):
                warm(fn, sl)
        return n

    def _plan_chunks(self, compressed_ok: bool = False):
        """Chunk only elementwise-decomposable hot collectives (allreduce)."""
        d = self.desc
        cfg = self.dispatcher.config
        if d.kind != "allreduce":
            return None
        if d.compression != CompressionType.NONE and not compressed_ok:
            return None
        threshold = cfg.large_msg_size_mb * 1024 * 1024
        if threshold <= 0 or d.payload_bytes() <= threshold or cfg.large_msg_chunks <= 1:
            return None
        k = min(cfg.large_msg_chunks, d.count)
        bounds = np.linspace(0, d.count, k + 1).astype(int)
        return [slice(int(a), int(b)) for a, b in zip(bounds[:-1], bounds[1:]) if b > a]

    # -- start/wait/test --------------------------------------------------

    def start(self, buf: jax.Array, *, _rewind_ef: bool = False) -> "CommRequest":
        """``_rewind_ef`` (internal, wait-retry only): rewind the
        error-feedback state to the previous Start's snapshot inside the
        epoch-bump critical section — after the bump a stale in-flight
        dispatch skips on the epoch check, and one that completed first is
        rewound here, so the replay always quantizes from the exact state
        the suspect round saw."""
        mlsl_assert(self.is_setup, "request must be setup() before start()")
        if chaos._plans:
            chaos.inject("request.start", request=self.name or self.uid,
                         kind=self.desc.kind)
        chkp = checker.level()
        if chkp:
            checker.check_buffer(buf, self.desc, chkp)
        # Bump the epoch under the dispatch lock: a stale dispatch of the
        # PREVIOUS start's buffer racing on the progress thread either sees the
        # new epoch and skips, or finishes writing _results before the reset
        # below — never after it (the clobber the supersede logic exists for).
        with self._dlock:
            self._epoch += 1
            if _rewind_ef:
                self._ef_restore()
            self._results = []
            self._result = None
            self._dispatch_error = None
            self.is_started = True
            self._started_at = time.monotonic()  # watchdog stamp
            self._last_buf = buf  # rung-2 wait retries re-dispatch this
            self._ef_snapshot = (
                self._err, list(self._errs) if self._errs is not None else None
            )
        tr = obs._tracer
        if tr is not None:
            tr.instant("submit", "req", track=self._trace_name,
                       req=self.name or self.uid, epoch=self._epoch,
                       bytes=self._payload)
        if self._wire_rec is not None:
            # per-codec wire accounting (one dict upsert, like the ALGO
            # dispatch line): compressed image bytes of this round's payload
            stats_mod.record_codec_wire(*self._wire_rec)
        self.dispatcher.submit(self, buf)
        return self

    def _dispatch(self, buf: jax.Array, epoch: Optional[int] = None) -> None:
        """Actually launch the XLA programs (called by the Dispatcher).

        ``epoch`` is the request epoch captured when the dispatch was queued; a
        mismatch means a later start() superseded this entry while it sat in the
        queue (or mid-flight on the progress thread) — drop it.

        The TraceAnnotation marks the host-side enqueue (request identity and
        dispatch ordering); the device-side span carries the collective's identity
        via the jax.named_scope baked into the compiled program
        (collectives.build_collective)."""
        with self._dlock:
            if epoch is not None and epoch != self._epoch:
                log_debug("dropping superseded dispatch of %s", self.name or self.uid)
                return
            tr = obs._tracer
            t0 = tr.now() if tr is not None else 0
            try:
                with jax.profiler.TraceAnnotation(self._trace_name):
                    # retry-in-place under _dlock IS the dispatch/restart
                    # serialization contract: the only other takers are this
                    # request's own wait()/test()/restart, which must see the
                    # ladder's outcome before touching round state
                    # mlsl-lint: disable=A211 -- deliberate hold across the retry ladder
                    self._dispatch_ladder(buf)
            except Exception as e:
                if tr is not None:
                    tr.instant("dispatch.error", "req", track=self._trace_name,
                               req=self.name or self.uid, error=repr(e))
                if epoch is None:
                    raise  # direct dispatch: fail the caller's start()
                # Queued dispatch: record the failure on the request while the
                # epoch is still known-current. Recording it after releasing
                # _dlock would race a fresh start() (which resets
                # _dispatch_error and bumps the epoch) and attach this stale
                # failure to the new start.
                self._dispatch_error = e
            else:
                if tr is not None:
                    # host-side enqueue span: XLA's async dispatch returns
                    # before the device finishes, so this measures launch
                    # cost; device completion lands in the wait span. The
                    # algo arg attributes the time to the program family the
                    # selection table chose (comm/algos).
                    tr.complete("dispatch", "req", t0, track=self._trace_name,
                                req=self.name or self.uid, epoch=self._epoch,
                                algo=self.algo, **self._span_args)

    def _dispatch_ladder(self, buf: jax.Array) -> None:
        """Rungs 2+3 of the recovery ladder around one dispatch (caller holds
        _dlock). TRANSIENT failures (supervisor.classify) retry in place with
        exponential backoff + jitter (``MLSL_COMM_RETRIES`` /
        ``MLSL_COMM_RETRY_BACKOFF_S``); CORRUPTION/PERSISTENT failures count
        against the request's subsystem breaker, and once it is OPEN — the
        tripping failure included — the dispatch is served by the degraded
        fallback path instead of raising. A healthy dispatch while the
        breaker is HALF_OPEN is the probe: its success re-closes the breaker
        and re-engages the fast path. FATAL failures raise untouched.

        The retry backoff sleeps in place — on the shared progress thread
        when dispatch is deferred. That stalls other queued dispatches for
        the backoff duration (bounded: ~retries x 1.5 x base, ~0.2s at the
        defaults — comparable to one chunked large-message dispatch);
        transients are rare by classification, and keeping the retry in
        line preserves the dispatch-order/supersede invariants a re-queue
        would have to re-prove. Keep retries x backoff well under the
        watchdog timeout (TUNING.md §11) so a backing-off request cannot
        cascade watchdog trips on the requests queued behind it."""
        br = self._breaker
        attempt = 0
        forced_degrade = False
        while True:
            degraded = forced_degrade or (br is not None and not br.allow())
            try:
                if degraded:
                    self._dispatch_degraded(buf)
                else:
                    self._dispatch_inner(buf)
            except Exception as e:
                # any re-attempt (retry, degrade, half-open probe loop)
                # replays the round from the Start residual state
                self._ef_restore()
                cfg = self.dispatcher.config
                cls = supervisor.classify(e)
                if cls is supervisor.ErrorClass.TRANSIENT:
                    if attempt < getattr(cfg, "comm_retries", 0):
                        delay = supervisor.jittered_backoff(
                            getattr(cfg, "comm_retry_backoff_s", 0.05), attempt
                        )
                        stats_mod.record_comm_retry(
                            "dispatch", self.name or str(self.uid), e,
                            attempt + 1, delay,
                        )
                        log_debug(
                            "transient dispatch failure of %s (%s); retry %d "
                            "in %.3fs", self.name or self.uid, e, attempt + 1,
                            delay,
                        )
                        attempt += 1
                        time.sleep(delay)
                        continue
                if cls is supervisor.ErrorClass.DEVICE_LOSS:
                    # capacity left the world: a breaker fallback would
                    # re-dispatch on the same (now partial) mesh and mask
                    # the loss — escalate straight to the elastic/restart
                    # rungs, without counting the subsystem as unhealthy
                    raise
                if (
                    not degraded
                    and br is not None
                    and cls is not supervisor.ErrorClass.FATAL
                    and br.record_failure(e)
                ):
                    # OPEN now (this failure tripped it, or a half-open probe
                    # failed): serve THIS dispatch degraded — rung 3's whole
                    # point is that the request succeeds instead of dying.
                    # forced: do not re-consult allow() (a zero cooldown must
                    # not ping-pong probe/fail forever inside one dispatch).
                    forced_degrade = True
                    continue
                raise
            else:
                if br is not None and not degraded:
                    br.record_success()  # no-op unless HALF_OPEN (the probe)
                return

    def _dispatch_degraded(self, buf: jax.Array) -> None:
        """The rung-3 fallback dispatch: compressed wire -> plain f32 SUM
        with the error-feedback residual flushed into the payload (delivered
        exactly once, not dropped); tuned algorithm -> the 'lax' baseline.
        Result shape/dtype match the healthy path exactly — callers cannot
        tell a degraded round from a healthy one except through stats."""
        d = self.desc
        topo0 = d.group.topology
        if hasattr(buf, "ndim") and (
            buf.ndim != NUM_GRID_AXES + 1
            or tuple(buf.shape[:NUM_GRID_AXES]) != topo0.grid_shape
        ):
            buf = topo0.adopt_buffer(buf)
        stats_mod.record_degrade(self._degrade_subsys or "?", "fallback")
        if self._quant_fn is not None or self._quant_fns is not None:
            pf = self._pending_flush
            if pf is not None:
                # a breaker degrade racing a codec demotion: the demoted
                # codec's captured residual still rides this round
                buf = pf[0](buf, *pf[1])
            flush, plain = self._degrade_programs()
            out = plain(flush(buf, *self._take_residuals()))
            self._results = [out]
            self._pending_flush = None
            stats_mod.record_algo_dispatch(d.kind, "degraded-plain")
            return
        # dense engine path: tuned/forced algorithm -> the 'lax' baseline
        if self._lax_fns is None:
            dtype, kw = self._lax_build
            fn = algos.build(d.kind, d.group, dtype, algos.DEFAULT, **kw)
            self._lax_fns = [fn] * len(self._chunk_slices)
        stats_mod.record_algo_dispatch(d.kind, algos.DEFAULT)
        if self._single_full:
            self._results = [self._lax_fns[0](buf)]
        else:
            self._results = [
                fn(buf[..., sl])
                for fn, sl in zip(self._lax_fns, self._chunk_slices)
            ]

    def _degrade_programs(self) -> tuple:
        """(flush jit, plain collective) for the degraded compressed path,
        built on first degrade and cached. flush casts to f32 and adds each
        chunk's un-chunked residual (quant_ring.logical_residual) at its
        slice; plain is the SAME cached build_collective program the
        uncompressed path uses — the parity anchor."""
        if self._degrade_fns is None:
            from mlsl_tpu.comm.quant_ring import logical_residual

            d = self.desc
            g = 1 if d.group.is_self else d.group.size
            plain = collectives.build_plain_fallback(d.kind, d.group, d.count)
            rs = d.kind == "reduce_scatter"
            slices = list(self._chunk_slices)
            geoms = list(self._degrade_geoms)
            layout = self._err_layout
            if layout == "hier":
                from mlsl_tpu.comm.algos import hier as hier_mod

                hier_L, l_np = self._hier_meta
                l_idx = jnp.asarray(l_np)

            def flush(b, *errs):
                x = b.astype(jnp.float32)
                for sl, (n, el), e in zip(slices, geoms, errs):
                    if layout == "flat":
                        res = e
                    elif layout == "hier":
                        res = hier_mod.flush_residual(e, l_idx, hier_L, el, n)
                    else:
                        res = logical_residual(
                            e, g, el // g, n // g if rs else -(-n // g), n
                        )
                    x = x + res if sl == slice(None) else x.at[..., sl].add(res)
                return x

            self._degrade_fns = (jax.jit(flush), plain)
        return self._degrade_fns

    def _ef_restore(self) -> None:
        """Rewind the error-feedback state to the Start snapshot before any
        re-attempt: a failed chunked dispatch may have advanced a prefix of
        the residuals, a wait-failed dispatch advanced all of them, and a
        failed degraded dispatch consumed them (_take_residuals) — in every
        case the replay must see the exact state the first attempt saw, or
        accumulated undelivered gradient is silently dropped (or flushed
        zero times). Arrays are immutable, so restoring references is a
        full rewind; the list is copied so the in-place chunk updates of
        the next attempt cannot corrupt the snapshot."""
        err, errs = self._ef_snapshot
        self._err = err
        self._errs = list(errs) if errs is not None else None

    def _take_residuals(self) -> List[jax.Array]:
        """Consume the error-feedback residual(s) for a degraded dispatch:
        lazily zeroed like the healthy path's first round, then RESET — the
        flush delivers the residual, and the next healthy round (the
        half-open probe) starts from virgin feedback state. Consumed BEFORE
        the plain dispatch runs; a transiently failed fallback dispatch is
        made safe by _ef_restore in the retry loop (the residual is flushed
        exactly once — by whichever attempt succeeds)."""
        topo = self.desc.group.topology

        def zeros(el):
            return topo.shard_buffer(
                np.zeros((*topo.grid_shape, el), dtype=np.float32)
            )

        if self._quant_fns is not None:
            errs = self._errs if self._errs is not None else [
                zeros(el) for el in self._err_lens
            ]
            self._errs = None
            return errs
        err = self._err if self._err is not None else zeros(self._err_len)
        self._err = None
        return [err]

    def demote_codec(self, reason: str = "") -> None:
        """Convergence-guardrail demotion (mlsl_tpu.codecs.guard_note): pin
        this request's compressed wire to the int8 seed codec. One
        DEGRADE-ladder rung: the demoted codec's EF residual is captured
        through the SAME flush program the breaker fallback uses and folded
        into the next successful dispatch exactly once; from then on the
        programs are bit-for-bit the plain int8 quant_ring build (the
        pinned-fallback contract every other rung honors)."""
        from mlsl_tpu import codecs as codecs_mod

        with self._dlock:
            if (
                self._codec_demoted
                or self.desc.compression != CompressionType.QUANTIZATION
                or (self._quant_fn is None and self._quant_fns is None)
            ):
                return
            label = self.algo
            # capture the OLD geometry's flush before setup() rebuilds:
            # residuals are consumed here (reset to virgin) and delivered by
            # whichever dispatch next succeeds (_dispatch_inner)
            flush, _ = self._degrade_programs()
            self._pending_flush = (flush, self._take_residuals())
            self._codec_demoted = True
            self._ef_snapshot = (None, None)
            self.setup()
        codecs_mod.guard_unregister(self)
        stats_mod.record_codec_demotion(
            self.name or str(self.uid), label, reason or "guardrail"
        )
        log_warning(
            "codec guardrail: %s demoted %s -> int8 (%s); residual flushes "
            "with the next round", self.name or self.uid, label,
            reason or "guardrail",
        )

    def _dispatch_inner(self, buf: jax.Array) -> None:
        # per-algorithm launch attribution (ALGO line in mlsl_stats.log);
        # one dict upsert — stays under the per-layer dispatch-floor budget
        stats_mod.record_algo_dispatch(self.desc.kind, self.algo)
        # Cross-distribution edges (redistribution cases 3-5) hand a buffer laid
        # out for the OTHER distribution's grid; re-view it onto this request's
        # group topology (device-local, no transfer — see Topology.adopt_buffer).
        topo0 = self.desc.group.topology
        if hasattr(buf, "ndim") and (
            buf.ndim != NUM_GRID_AXES + 1
            or tuple(buf.shape[:NUM_GRID_AXES]) != topo0.grid_shape
        ):
            buf = topo0.adopt_buffer(buf)
        if self._quant_fn is not None or self._quant_fns is not None:
            pf = self._pending_flush
            if pf is not None:
                # demotion's exactly-once EF flush: fold the demoted codec's
                # captured residual into this round's payload. Cleared only
                # after the dispatch succeeds — a transient failure replays
                # against the ORIGINAL buffer, so the residual lands in
                # exactly one delivered round, never zero, never two.
                buf = pf[0](buf, *pf[1])
            topo = self.desc.group.topology
            if self._quant_fns is not None:
                if self._errs is None:
                    self._errs = [
                        topo.shard_buffer(
                            np.zeros((*topo.grid_shape, el), dtype=np.float32)
                        )
                        for el in self._err_lens
                    ]
                self._results = []
                for i, (fn, sl) in enumerate(zip(self._quant_fns, self._chunk_slices)):
                    out, self._errs[i] = fn(buf[..., sl], self._errs[i])
                    self._results.append(out)
                self._pending_flush = None
                return
            if self._err is None:
                self._err = topo.shard_buffer(
                    np.zeros((*topo.grid_shape, self._err_len), dtype=np.float32)
                )
            out, self._err = self._quant_fn(buf, self._err)
            self._results = [out]
            self._pending_flush = None
            return
        if self._single_full:
            self._results = [self._fns[0](buf)]
        else:
            self._results = [
                fn(buf[..., sl]) for fn, sl in zip(self._fns, self._chunk_slices)
            ]

    def _assemble(self) -> jax.Array:
        if self._result is None:
            if len(self._results) == 1:
                self._result = self._results[0]
            else:
                self._result = jnp.concatenate(self._results, axis=-1)
        return self._result

    # -- watchdog ---------------------------------------------------------

    def _watchdog_deadline(self, timeout: Optional[float]) -> Optional[float]:
        """Absolute deadline for this wait, measured from the Start stamp (the
        watchdog bounds total in-flight time, not time inside wait())."""
        t = timeout
        if t is None:
            t = getattr(self.dispatcher.config, "watchdog_timeout_s", 0.0)
        if not t or t <= 0:
            return None
        return (self._started_at or time.monotonic()) + t

    def describe(self) -> str:
        """One-line stuck-request descriptor for the watchdog log."""
        d = self.desc
        s = (
            f"{d.kind} name={self.name or self.uid} algo={self.algo} "
            f"count={d.count} dtype={d.data_type.name} axes={d.group.axes} "
            f"payload={self._payload}B epoch={self._epoch}"
        )
        br = self._breaker
        if br is not None and br.state != supervisor.CLOSED:
            # the ladder's state is part of the request's identity while it
            # lasts: a watchdog report on a DEGRADED dispatch must say so
            s += f" breaker={br.name}:{br.state}"
        return s

    def _watchdog_trip(self, phase: str) -> None:
        """Log the stuck descriptor (core/stats.py keeps the event record) and
        raise the recoverable timeout."""
        waited = time.monotonic() - (self._started_at or time.monotonic())
        desc = self.describe()
        tr = obs._tracer
        if tr is not None:
            # on the stuck request's OWN track, before the flight record is
            # cut (record_watchdog_event) so the dump contains it
            tr.instant("watchdog.trip", "watchdog", track=self._trace_name,
                       req=self.name or self.uid, phase=phase,
                       waited_s=round(waited, 3), descriptor=desc)
        stats_mod.record_watchdog_event(desc, phase, waited)
        raise MLSLTimeoutError(
            f"watchdog: request stuck in {phase} for {waited:.2f}s: {desc}"
        )

    def _block_ready(self, out: jax.Array, deadline: Optional[float]) -> None:
        if deadline is None:
            jax.block_until_ready(out)
            return
        # exponential-backoff poll: fast completions (the common case) pay
        # ~10 µs over plain block_until_ready, a genuine hang converges to
        # 1 ms polls until the deadline trips
        delay = 1e-5
        while not out.is_ready():
            if time.monotonic() > deadline:
                self._watchdog_trip("wait")
            time.sleep(delay)
            delay = min(delay * 2, 1e-3)

    # -- wait/test --------------------------------------------------------

    def wait(self, timeout: Optional[float] = None) -> jax.Array:
        # A completed request can be wait()ed any number of times, whether it
        # completed via wait() or test() (MPI semantics: MPI_Wait on a completed
        # request returns immediately).
        if not self.is_started and self._result is not None:
            return self._result
        mlsl_assert(self.is_started, "request was not started")
        tr = obs._tracer
        t0 = tr.now() if tr is not None else 0
        attempt = 0
        while True:
            try:
                out = self._wait_inner(timeout)
            except Exception as e:
                # rung 2 for the wait side: a TRANSIENT failure surfacing at
                # wait (an injected fault at the wait site, a dispatch error
                # that exhausted ITS retries, a device read error) re-Starts
                # the stored buffer — the in-flight round is suspect, and a
                # fresh epoch supersedes anything still racing. Worst case
                # (permanently-transient fault) is (retries+1)^2 dispatch
                # attempts: both layers spend their own small budget.
                cfg = self.dispatcher.config
                if (
                    supervisor.classify(e)
                    is not supervisor.ErrorClass.TRANSIENT
                    or attempt >= getattr(cfg, "comm_retries", 0)
                    or self._last_buf is None
                ):
                    # the round is failing: drain its queued CHKP verdicts
                    # (logged, never raised here — the real error must stay
                    # primary) so a LATER healthy request's wait cannot
                    # inherit and mis-surface them
                    self._drain_chkp_logged()
                    raise
                delay = supervisor.jittered_backoff(
                    getattr(cfg, "comm_retry_backoff_s", 0.05), attempt
                )
                stats_mod.record_comm_retry(
                    "wait", self.name or str(self.uid), e, attempt + 1, delay
                )
                log_debug(
                    "transient wait failure of %s (%s); re-dispatching, "
                    "retry %d in %.3fs", self.name or self.uid, e,
                    attempt + 1, delay,
                )
                attempt += 1
                time.sleep(delay)
                self.start(self._last_buf, _rewind_ef=True)
                continue
            break
        self.is_started = False
        # the round is over: the retry buffer and residual snapshot are only
        # needed while in flight — release them or every request permanently
        # retains a gradient-sized device array between rounds
        self._last_buf = None
        self._ef_snapshot = (None, None)
        if checker._pending:
            # CHKP_VALUES round boundary: resolve every finiteness verdict
            # queued since the last completion with ONE device sync (raises
            # MLSLError naming all offending buffers of the round)
            checker.flush_values()
        if tr is not None:
            # the wait STALL: host time blocked for this request (dispatch
            # race + device completion) — the per-op overlap-loss signal
            # behind Statistics.overlap_report's p50/p95 fields. algo rides
            # along because THIS span holds the wire time the per-algorithm
            # trace summary (obs/export.summarize) attributes — the dispatch
            # span alone is only the async enqueue cost.
            tr.complete("wait", "req", t0, track=self._trace_name,
                        req=self.name or self.uid, epoch=self._epoch,
                        algo=self.algo)
        m = obs_metrics._registry
        if m is not None:
            self._record_done_metrics(m)
        return out

    def _wait_inner(self, timeout: Optional[float]) -> jax.Array:
        """One wait attempt: chaos site, dispatch drain, error surface,
        assemble, block. Split out so wait() can retry transients."""
        if chaos._plans:
            chaos.inject("request.wait", request=self.name or self.uid,
                         kind=self.desc.kind)
        deadline = self._watchdog_deadline(timeout)
        self.dispatcher.wait_dispatched(self, deadline)
        if self._dispatch_error is not None:
            err, self._dispatch_error = self._dispatch_error, None
            self.is_started = False
            raise err
        out = self._assemble()
        self._block_ready(out, deadline)
        return out

    def _drain_chkp_logged(self) -> None:
        """Resolve any queued CHKP_VALUES verdicts on a FAILING round without
        letting a CHKP violation replace the round's real error: the verdict
        outcome is logged (and counted), the queue is clean for the next
        round."""
        if not checker._pending:
            return
        try:
            checker.flush_values()
        except MLSLError as ce:
            log_warning(
                "CHKP verdicts from the failed round of %s: %s",
                self.name or self.uid, ce,
            )

    def test(self) -> tuple:
        """Non-blocking completion poll -> (is_completed, result_or_None)."""
        if not self.is_started:
            return True, self._result
        if chaos._plans:
            chaos.inject("request.test", request=self.name or self.uid,
                         kind=self.desc.kind)
        self.dispatcher.flush()
        if self._dispatch_error is not None:
            err, self._dispatch_error = self._dispatch_error, None
            self.is_started = False
            self._drain_chkp_logged()
            raise err
        # A dispatch racing on the progress thread builds _results incrementally;
        # check in-flight FIRST — once it clears, _results is fully built.
        if self.dispatcher.is_in_flight(self.uid) or not self._results:
            return False, None
        ready = all(r.is_ready() for r in self._results)
        if ready:
            out = self._assemble()
            jax.block_until_ready(out)
            self.is_started = False
            self._last_buf = None  # round over: release the retry buffer
            self._ef_snapshot = (None, None)
            if checker._pending:
                checker.flush_values()  # CHKP_VALUES round boundary
            tr = obs._tracer
            if tr is not None:
                tr.instant("test.done", "req", track=self._trace_name,
                           req=self.name or self.uid, epoch=self._epoch)
            m = obs_metrics._registry
            if m is not None:
                self._record_done_metrics(m)
            return True, out
        return False, None

    def _record_done_metrics(self, m) -> None:
        """Telemetry-plane feed at round completion (metrics armed only):
        the dispatch->wait in-flight latency histogram plus the achieved
        algbw (payload bytes over in-flight time — the algorithm-bandwidth
        definition) labeled by the algorithm the selection table chose and
        its tier shape, so /metrics exposes the per-algo/per-tier bandwidth
        distribution a tuned profile's effect shows up in."""
        started = self._started_at
        if not started:
            return
        waited_s = time.monotonic() - started
        m.observe("mlsl_dispatch_wait_ms", waited_s * 1e3,
                  kind=self.desc.kind)
        if waited_s > 0 and self._payload:
            m.observe(
                "mlsl_algbw_gbps", self._payload / waited_s / 1e9,
                buckets=obs_metrics.ALGBW_BUCKETS_GBPS,
                algo=self.algo,
                tier="two-tier" if self.algo == "hier" else "flat",
            )


def in_graph_descriptor(kind: str, name: str, algo: str, count: int,
                        data_type: DataType, group: ProcessGroup) -> str:
    """One-line descriptor for an IN-GRAPH collective round (the compiled
    overlap engine, comm/overlap.py). The rounds never construct a
    CommRequest — the whole comm segment is one compiled program — but
    stats/trace tooling reads ONE descriptor grammar, so this mirrors
    CommRequest.describe() field-for-field with an ``in_graph=1`` marker in
    place of the epoch (in-graph rounds have no per-round host state)."""
    payload = count * dtype_size(data_type)
    return (
        f"{kind} name={name} algo={algo} count={count} "
        f"dtype={data_type.name} axes={group.axes} "
        f"payload={payload}B in_graph=1"
    )


def _unwrap_chaos(fn):
    """The compiled program beneath the chaos instrumentation (the wrappers'
    ``_mlsl_inner`` — the same jit object the dispatch path calls, so the
    warm hits the same cache; NOT ``__wrapped__``, which on a bare jitted fn
    is the raw un-jitted Python callable). The precompile warm must NOT pass
    the chaos sites: it would spend one-shot fault budgets (and shift
    '@after N' schedules) inside Commit instead of the training step those
    faults target, and a 'hang' would wedge Commit where no watchdog is
    armed."""
    return getattr(fn, "_mlsl_inner", fn)


def _custom_wire_bytes(codec, geoms) -> int:
    """Compressed-image bytes of one full payload under a user CustomCodec,
    via shape-only tracing of its compress fn (0 when untraceable — the
    stats row then reads 'custom: 0' rather than lying)."""
    total = 0
    for n, _ in geoms:
        try:
            out = jax.eval_shape(
                codec.compress, jax.ShapeDtypeStruct((n,), jnp.float32)
            )
            total += int(np.prod(out.shape)) * np.dtype(out.dtype).itemsize
        except Exception:
            return 0
    return total


def _check_recv_count(d: CommDesc) -> None:
    """Compressed reduce_scatter derives recv_count as count // group_size; a
    caller-supplied value that disagrees would silently change placement."""
    if d.kind != "reduce_scatter" or d.recv_count is None:
        return
    g = d.group.size if not d.group.is_self else 1
    mlsl_assert(
        d.recv_count == d.count // g,
        "compressed reduce_scatter recv_count %d != count//group %d",
        d.recv_count,
        d.count // g,
    )


def _normalize_alltoallv(d: CommDesc) -> dict:
    """Expand user count/offset arrays into full static matrices.

    MPI semantics: S[i][j] = elements i->member j. 1-D arrays mean 'same on every
    rank' (S[i][j] = counts[j]); (G, G) arrays give the full instance-uniform
    matrix (every group instance exchanges the same geometry). Offsets default to
    the packed (cumulative) layout. The receive matrix is derived: R[i][j] = S[j][i].

    (W, G) arrays (world size x group size, W != G) select per-rank mode: row w is
    what world rank w sends to each member of ITS OWN group instance — the full
    generality of each MPI rank passing its own count vectors
    (reference src/comm_ep.cpp:1188-1265), so different instances of a subgroup
    may exchange different geometries.
    """
    g = d.group.size
    w = d.group.topology.world_size
    a = np.asarray(d.send_counts, dtype=int)
    if a.ndim == 2 and a.shape == (w, g) and w != g:
        return _normalize_alltoallv_per_rank(d, a)

    def packed(mat):
        return np.hstack([np.zeros((g, 1), int), np.cumsum(mat, axis=1)[:, :-1]])

    def expand(arr):
        a = np.asarray(arr, dtype=int)
        if a.ndim == 1:
            return np.tile(a, (g, 1))
        mlsl_assert(a.shape == (g, g), "counts/offsets matrix must be (%d,%d)", g, g)
        return a

    s = expand(d.send_counts)
    soff = packed(s) if d.send_offsets is None else expand(d.send_offsets)
    r = s.T
    if d.recv_counts is not None:
        # MPI requires recvcounts[i][j] == sendcounts[j][i]; a mismatch is a
        # usage error the reference would deadlock/corrupt on — raise instead.
        mlsl_assert(
            np.array_equal(expand(d.recv_counts), r),
            "alltoallv recv_counts do not match transposed send_counts",
        )
    roff = packed(r) if d.recv_offsets is None else expand(d.recv_offsets)
    recv_len = int(np.max(roff + r)) if g > 0 else 1
    to_t = lambda m: tuple(tuple(int(v) for v in row) for row in m)
    return dict(S=to_t(s), Soff=to_t(soff), Roff=to_t(roff), recv_len=max(recv_len, 1))


def _normalize_alltoallv_per_rank(d: CommDesc, s: np.ndarray) -> dict:
    """Per-rank mode: each world rank supplies its own (G,) count/offset rows,
    stacked into (W, G) arrays. The receive geometry is DERIVED from the send
    matrix via the member table (R[w][j] = S[member_j_of_w's_instance][pos(w)]);
    explicit recv_counts must match it — the MPI pairwise invariant
    (sendcounts[j]@i == recvcounts[i]@j), checked here at trace time instead of
    deadlocking/corrupting at run time like a mismatched MPI exchange would."""
    g = d.group.size
    w = d.group.topology.world_size
    mlsl_assert(
        d.group.is_uniform,
        "per-rank alltoallv requires equal-size groups (ragged partitions are "
        "spelled with zero counts on an equal-size group; docs/DESIGN.md)",
    )
    M = collectives._member_world_table(d.group)  # (W, G)
    pos = np.empty(w, dtype=int)
    for p in range(w):
        pos[p] = list(M[p]).index(p)

    def packed(mat):
        return np.hstack([np.zeros((w, 1), int), np.cumsum(mat, axis=1)[:, :-1]])

    def expand(arr, name):
        a = np.asarray(arr, dtype=int)
        if a.ndim == 1:
            a = np.tile(a, (w, 1))
        mlsl_assert(
            a.shape == (w, g),
            "per-rank alltoallv %s must be (world=%d, group=%d), got %s",
            name, w, g, a.shape,
        )
        return a

    soff = packed(s) if d.send_offsets is None else expand(d.send_offsets,
                                                           "send_offsets")
    r = s[M, pos[:, None]]  # R[w][j] = S[M[w][j]][pos[w]]
    if d.recv_counts is not None:
        mlsl_assert(
            np.array_equal(expand(d.recv_counts, "recv_counts"), r),
            "alltoallv recv_counts violate the MPI pairwise invariant: "
            "recv_counts[w][j] must equal member j's send count toward w",
        )
    roff = packed(r) if d.recv_offsets is None else expand(d.recv_offsets,
                                                           "recv_offsets")
    recv_len = int(np.max(roff + r)) if r.size else 1
    to_t = lambda m: tuple(tuple(int(v) for v in row) for row in m)
    return dict(Sw=to_t(s), Swoff=to_t(soff), Rwoff=to_t(roff),
                recv_len=max(recv_len, 1))


class Dispatcher:
    """Host-side dispatch policy: immediate async launch, or newest-first deferral.

    The reference's endpoint servers pull commands from a queue and (optionally) serve
    the newest large allreduce first (eplib/cqueue.c:1999-2012 routing to
    allreduce_pr.c LIFO). Here the queue is a host-side stack of not-yet-launched
    requests; flush() launches them LIFO. Small messages bypass the stack entirely.

    Progress is autonomous, as in the reference (eplib's servers drive the network
    without the app thread, eplib/allreduce_pr.c:69-278): a daemon thread flushes
    deferred requests after a short coalescing window
    (config.msg_priority_flush_ms), so a large deferred allreduce makes progress
    even if the app never calls wait()/test(). The window is what preserves
    newest-first ordering for back-to-back starts: requests deferred within it are
    launched together, LIFO.
    """

    def __init__(self, config):
        self.config = config
        self._pending: List[tuple] = []  # stack of (request, buf)
        self._by_id: dict = {}           # req uid -> (request, buf), native path
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._in_flight: set = set()     # uids popped from the queue, dispatch running
        self._thread: Optional[threading.Thread] = None
        self._deadline = 0.0
        self._stopped = False
        self._native = None
        self._native_tried = False

    def _ensure_native_locked(self):
        """Lazily bind the C++ priority queue (config may be toggled post-init).
        Caller must hold self._lock — the check-and-swap must not race submits."""
        cfg = self.config
        if not self._native_tried or (
            self._native is not None
            and self._native.params != (cfg.msg_priority_threshold, cfg.msg_priority_mode)
            and self._native.pending() == 0  # never strand deferred entries
        ):
            self._native_tried = True
            from mlsl_tpu import native

            # load() raising (a failed build beside a stale library) must
            # propagate; only "no library and no toolchain" selects the
            # pure-Python queue
            self._native = (
                native.NativeScheduler(
                    cfg.msg_priority_threshold, cfg.msg_priority_mode
                )
                if native.load() is not None else None
            )
        return self._native

    def submit(self, req: CommRequest, buf: jax.Array) -> None:
        cfg = self.config
        if not cfg.msg_priority or req.desc.kind == "barrier":
            if req.desc.kind == "barrier":
                # A barrier orders everything before it: launch any deferred
                # requests first so they are on the wire when the barrier lands.
                self.flush()
            req._dispatch(buf)
            return
        if req._payload <= cfg.msg_priority_threshold:
            # small message: below every deferral threshold in both the native
            # and Python schedulers — dispatch immediately without touching the
            # lock or the ctypes queue (the per-layer hot path)
            req._dispatch(buf)
            return
        native = None
        immediate = False
        with self._lock:
            native = self._ensure_native_locked()
            if native is not None:
                immediate = native.submit(req.uid, req.desc.payload_bytes())
                if not immediate:
                    self._by_id[req.uid] = (req, buf, req._epoch)
                    self._note_deferred_locked()
        if native is not None:
            if immediate:
                req._dispatch(buf)  # outside the lock: may trigger compilation
            else:
                tr = obs._tracer
                if tr is not None:
                    tr.instant("defer", "req", track=req._trace_name,
                               req=req.name or req.uid, bytes=req._payload,
                               scheduler="native")
                log_debug(
                    "deferred request %s (%d B)", req.name, req.desc.payload_bytes()
                )
            return
        # payload > threshold here (the small-message fast path returned above)
        with self._lock:
            # A restart of an already-deferred request supersedes the stale entry
            # (otherwise flush would re-dispatch the old buffer last and clobber
            # the fresh results). An entry already popped mid-flight is dropped
            # by the epoch check in _dispatch.
            self._pending = [e for e in self._pending if e[0] is not req]
            self._pending.append((req, buf, req._epoch))
            self._note_deferred_locked()
        tr = obs._tracer
        if tr is not None:
            tr.instant("defer", "req", track=req._trace_name,
                       req=req.name or req.uid, bytes=req._payload,
                       scheduler="python")
        log_debug("deferred request %s (%d B)", req.name, req._payload)

    def _note_deferred_locked(self) -> None:
        """Arm the progress thread: dispatch happens msg_priority_flush_ms from the
        LAST deferral (coalescing window), with no app poll required."""
        import time

        self._deadline = time.monotonic() + self.config.msg_priority_flush_ms / 1e3
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._progress_loop, daemon=True, name="mlsl-dispatch"
            )
            self._thread.start()
        self._cv.notify_all()

    def _progress_loop(self) -> None:
        import time

        while True:
            with self._cv:
                while not self._stopped and not (self._pending or self._by_id):
                    self._cv.wait()
                if self._stopped:
                    return
                deadline = self._deadline
            delay = deadline - time.monotonic()
            if delay > 0:
                time.sleep(min(delay, 0.05))
                continue
            try:
                self.flush()
            except Exception as e:  # pragma: no cover - defensive: keep daemon alive
                log_error("background flush failed: %r", e)

    def shutdown(self) -> None:
        """Launch anything still deferred and stop the progress thread."""
        self.flush()
        with self._cv:
            self._stopped = True
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=5)
            if self._thread.is_alive():
                # A still-alive progress thread means a dispatch is wedged (or
                # a chaos hang is armed) — abandoning it silently would make
                # the eventual symptom undiagnosable.
                log_warning(
                    "dispatch progress thread %s still alive after 5s join "
                    "(%d deferred requests pending); abandoning it",
                    self._thread.name,
                    self.pending_count,
                )
            self._thread = None

    def flush(self) -> None:
        if not self._pending and not self._by_id:
            # Nothing deferred: skip the lock (the hot wait()/test() path).
            # Lock-free read is safe: entries THIS thread cares about were
            # added by this thread (visible), and flush marks a request
            # in-flight BEFORE removing it from the queues (ordering below),
            # so a request is never in neither place.
            return
        # INVARIANT for the lock-free fast paths in flush()/wait_dispatched()/
        # is_in_flight(): _in_flight gains a uid BEFORE the entry leaves
        # _pending/_by_id. The lock orders writers, but lock-free readers see
        # individual bytecodes — with the opposite order a reader could find
        # the queues empty and the uid not yet in-flight while its dispatch
        # has not run, and read half-built _results.
        if self._native is not None:
            with self._lock:
                order = self._native.drain()
                items = [self._by_id[rid] for rid in order if rid in self._by_id]
                self._in_flight.update(e[0].uid for e in items)
                for rid in order:
                    self._by_id.pop(rid, None)
            self._dispatch_items(items)
            return
        with self._lock:
            self._in_flight.update(e[0].uid for e in self._pending)
            pending, self._pending = self._pending, []
            items = list(reversed(pending)) if self.config.msg_priority_mode else pending
        self._dispatch_items(items)

    def _dispatch_items(self, items) -> None:
        """Launch outside the lock (may compile); then release waiters.

        A dispatch failure is recorded on ITS request by _dispatch itself
        (under the request's dispatch lock, re-raised by that request's
        wait()/test()), so it neither strands the remaining items of the batch
        nor, on the progress thread, kills the daemon."""
        if not items:
            return
        try:
            for req, buf, epoch in items:
                req._dispatch(buf, epoch)
        finally:
            with self._cv:
                for req, _, _ in items:
                    self._in_flight.discard(req.uid)
                self._cv.notify_all()

    def is_in_flight(self, uid: int) -> bool:
        # GIL-atomic set membership; flush() adds the uid BEFORE the paired
        # _pending/_by_id removal (see the invariant there), so a caller that
        # saw the queues empty observes the uid here until its dispatch
        # completes (per-poll lock acquisition would dominate the test() floor)
        return uid in self._in_flight

    def wait_dispatched(
        self, req: CommRequest, deadline: Optional[float] = None
    ) -> None:
        """Ensure req's programs have been launched: flush the queue, then wait out
        a dispatch racing on the progress thread (its _results would otherwise be
        read half-built). ``deadline`` (monotonic) is the request watchdog's
        bound: a dispatch wedged on the progress thread past it trips the
        recoverable MLSLTimeoutError instead of blocking forever."""
        self.flush()
        if req.uid not in self._in_flight:  # hot path: nothing racing
            return
        with self._cv:
            while req.uid in self._in_flight:
                if deadline is None:
                    self._cv.wait()
                    continue
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    req._watchdog_trip("dispatch")
                self._cv.wait(min(remaining, 0.05))

    @property
    def pending_count(self) -> int:
        if self._native is not None:
            return self._native.pending()
        return len(self._pending)


class RequestStorage:
    """Tracks live generic requests so Environment.Wait/Test can free them
    (reference RequestStorage src/mlsl_impl.hpp:60-94)."""

    def __init__(self):
        self._reqs: dict = {}
        self._lock = threading.Lock()

    def register(self, req: CommRequest) -> None:
        with self._lock:
            self._reqs[req.uid] = req

    def remove(self, req: CommRequest) -> None:
        with self._lock:
            self._reqs.pop(req.uid, None)

    def __len__(self) -> int:
        return len(self._reqs)
