"""Compiled overlap engine: in-graph per-layer gradient collectives.

The host per-layer path (models/train.py ``_sync_and_update``) dispatches one
XLA executable per layer collective and overlaps them with host polling:
the comm schedule lives on the host, where XLA's latency-hiding scheduler
cannot see it. This module moves the
schedule INTO the compiled program (the PyTorch-DDP finding, PAPERS.md:
overlap only pays when the compiler/scheduler owns the comm stream):

- ONE single-dispatch, donation-enabled step program: local backward, then
  every layer's gradient collective emitted IN-GRAPH, newest-gradient-first,
  interleaved with the remaining layers' update work so XLA can overlap ICI
  DMA with compute instead of the host poll loop faking it.
- Each collective is composed from the existing algos-engine lowerings
  (comm/algos): ``lax`` psum, the ``rhd`` ppermute round sequence, the
  ``ring2d`` ring phases — via their staged ``steps``/``inline_plan`` forms,
  so the in-graph rounds are op-for-op the standalone programs (bit-exact
  parity on integer payloads, tests/test_overlap_compiled.py).
- The schedule is STAGED: a unit's reduce phases are spread over the next
  ``stages`` unit-starts (``MLSL_OVERLAP_STAGES``; tunable via the tuner
  profile's ``overlap_stages`` knob), and each stage boundary is pinned with
  ``lax.optimization_barrier`` so the emitted interleaving survives into the
  scheduled program instead of collapsing into one tail.
- Quantized sets ride an in-graph quantize -> int8 ring -> dequantize
  (quant_ring.inline_body — the same geometry/body as the host request) with
  the error-feedback residual threaded through the step carry: residual
  buffers are trainer state, donated every step.
- Small uncompressed layers coalesce into in-graph buckets under
  ``MLSL_GRAD_BUCKET_MB`` using the SAME packing policy as the host buckets
  (core/bucketing.pack_by_size).

Selection precedence per unit is the PR 4 table unchanged
(``MLSL_ALGO`` > tuned profile > ``lax`` baseline; comm/algos.select), with
an in-graph eligibility gate on top (color-group graphs cannot be served
in-graph at all — their axes are ``()`` — and ride the host path).

The host path stays the default and the parity oracle; the engine arms via
``MLSL_OVERLAP_COMPILED=1`` / ``DataParallelTrainer(overlap_compiled=True)``.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from mlsl_tpu import chaos
from mlsl_tpu.comm import algos
from mlsl_tpu.comm.collectives import (
    _BUF_SPEC, _axis_sizes, _body_allgather, _group_rank, smap,
)
from mlsl_tpu.comm.mesh import NUM_GRID_AXES, ProcessGroup
from mlsl_tpu.core import stats as stats_mod
from mlsl_tpu.log import log_debug, mlsl_assert
from mlsl_tpu.obs import tracer as obs
from mlsl_tpu.types import CompressionType, DataType, ReductionType

DEFAULT_STAGES = 2


# ---------------------------------------------------------------------------
# Plan: what gets reduced, how, in what order
# ---------------------------------------------------------------------------


class OverlapUnit:
    """One in-graph reduction unit: a single layer, or a bucket of small
    consecutive (newest-first) uncompressed layers coalesced into one
    collective. Phase closures are built once at plan time; they trace into
    the step program when the engine compiles."""

    def __init__(self, names: Tuple[str, ...], counts: Tuple[int, ...],
                 compression: CompressionType, algo: str,
                 group: ProcessGroup, *, index: int, block: int, dtype=None,
                 config=None):
        self.names = tuple(names)
        self.counts = tuple(int(c) for c in counts)
        self.total = sum(self.counts)
        self.compression = compression
        self.algo = algo
        self.index = index
        self.key: Optional[str] = None  # residual-state key (quant units)
        self.err_len = 0
        self.per_tick = 1  # phases advanced per scheduler tick (set by plan)
        self._quant_staged = False
        if compression == CompressionType.QUANTIZATION:
            if algo == "hier":
                # the table routed this unit's compressed wire through the
                # two-tier decomposition: staged phases (the ICI
                # reduce-scatter emits early, the compressed DCN hop is its
                # own phase — the natural stage boundary — the ICI
                # all-gather last), error feedback threaded through the
                # carry exactly like the flat inline body
                from mlsl_tpu.comm.algos import hier

                (self._qprep, self._phases,
                 self._qfinish, self.err_len) = hier.quant_steps(
                    group, self.total, block,
                    codec=getattr(config, "hier_dcn_codec", None),
                    topk_ratio=float(getattr(config, "topk_ratio", 0.01)),
                )
                self._quant_staged = True
                self.key = f"q{index}/{self.names[0]}"
                self.nphases = len(self._phases)
                self.algo = "hier"
                return
            from mlsl_tpu.comm import quant_ring

            self._body, self.err_len = quant_ring.inline_body(
                "allreduce", group, self.total, block
            )
            self.key = f"q{index}/{self.names[0]}"
            self.nphases = 1
            # attribution names the wire family, like the host request's
            # .algo — the ALGO stats line must not show quant rounds as lax
            self.algo = "quant_ring"
        else:
            self._prep, self._phases, self._finish = algos.inline_plan(
                "allreduce", group, algo, self.total, op=ReductionType.SUM,
                config=config,
            )
            # may be 0: a degenerate (single-member) group reduces nothing —
            # the unit retires at its first tick straight through finish()
            self.nphases = len(self._phases)

    # -- trace-time interface (called inside the shard_map body) -----------

    def prep(self, flat: Dict[str, jax.Array], mypos, err):
        x = (
            jnp.concatenate([flat[n] for n in self.names])
            if len(self.names) > 1
            else flat[self.names[0]]
        )
        if self._quant_staged:
            return self._qprep(x, mypos, err)
        if self.compression == CompressionType.QUANTIZATION:
            return (x, err)
        return self._prep(x, mypos)

    def advance(self, carry, i: int):
        if self.compression == CompressionType.QUANTIZATION \
                and not self._quant_staged:
            return self._body(*carry)
        return self._phases[i](carry)

    def finish(self, carry) -> Tuple[Dict[str, jax.Array], Optional[jax.Array]]:
        """-> ({member name -> reduced flat slice}, new residual or None)."""
        if self._quant_staged:
            out, new_err = self._qfinish(carry)
        elif self.compression == CompressionType.QUANTIZATION:
            out, new_err = carry
        else:
            out, new_err = self._finish(carry), None
        parts: Dict[str, jax.Array] = {}
        off = 0
        for n, c in zip(self.names, self.counts):
            parts[n] = out[off:off + c] if len(self.names) > 1 else out
            off += c
        return parts, new_err


class OverlapPlan:
    """The compiled-overlap schedule for one trainer/graph: units in
    newest-gradient-first start order, plus the bookkeeping stats/trace
    attribution reads."""

    def __init__(self, group: ProcessGroup, units: List[OverlapUnit],
                 stages: int, data_type: DataType = DataType.FLOAT):
        self.group = group
        self.units = units
        self.stages = max(int(stages), 1)
        self.data_type = data_type
        for u in units:
            # spread a unit's phases over the next `stages` unit starts
            u.per_tick = max(1, -(-u.nphases // self.stages))
        self.err_lens = {u.key: u.err_len for u in units if u.key}
        self.total_bytes = sum(u.total for u in units) * 4
        self.rounds = sum(u.nphases for u in units)
        breakdown: Dict[Tuple[str, str], int] = {}
        for u in units:
            k = ("allreduce", u.algo)
            breakdown[k] = breakdown.get(k, 0) + 1
        self.breakdown = breakdown

    @property
    def quant_units(self) -> int:
        return sum(1 for u in self.units if u.key)

    def algos_summary(self) -> str:
        return ",".join(
            f"{algo}:{n}" for (_, algo), n in sorted(self.breakdown.items())
        )

    def describe(self) -> List[str]:
        """One descriptor line per unit, in the CommRequest.describe()
        grammar (comm/request.in_graph_descriptor) — the in-graph rounds
        never construct a request, but tooling reads one format."""
        from mlsl_tpu.comm.request import in_graph_descriptor

        return [
            in_graph_descriptor(
                "allreduce", "+".join(u.names), u.algo,
                u.total, self.data_type, self.group,
            )
            for u in self.units
        ]


def _unit_algo(group: ProcessGroup, payload: int,
               compression: CompressionType, config, forced: Optional[str]):
    """Per-unit algorithm: a caller-forced name, else the PR 4 selection
    table (explicit MLSL_ALGO > tuned profile > 'lax'), then the in-graph
    eligibility gate on top — a selected algorithm the engine cannot embed
    falls back to the baseline with a debug log, mirroring algos.select's
    own fallback contract."""
    if compression != CompressionType.NONE:
        # compressed units carry their own wire family — except the
        # two-tier 'hier' route, whose codec lives on the DCN hop only: a
        # forced or tuned 'hier' stages the quantized unit hierarchically
        if compression == CompressionType.QUANTIZATION and config is not None:
            name = forced or algos.select(
                "allreduce", group, payload, compression, config,
                op=ReductionType.SUM,
            )
            if name == "hier" and algos._quant_hier_eligible(
                    "allreduce", group, config):
                return "hier"
        return algos.DEFAULT
    name = forced or algos.select(
        "allreduce", group, payload, compression, config, op=ReductionType.SUM
    )
    if name != algos.DEFAULT and not algos.inline_eligible(
        name, "allreduce", group, ReductionType.SUM
    ):
        log_debug(
            "overlap: algorithm %s not in-graph eligible on group %s; "
            "falling back to %s", name, algos.group_shape(group), algos.DEFAULT,
        )
        return algos.DEFAULT
    return name


def build_plan(
    group: ProcessGroup,
    layers: Sequence[Tuple[str, int, CompressionType]],
    config,
    *,
    stages: Optional[int] = None,
    bucket_mb: Optional[int] = None,
    block: Optional[int] = None,
    algo: Optional[str] = None,
) -> OverlapPlan:
    """Build the overlap schedule for ``layers`` (FORWARD order, as a
    trainer registers them: (name, flat element count, compression)). Units
    start newest-gradient-first — the reversed list — with small
    uncompressed neighbors coalesced under ``bucket_mb`` via the host
    buckets' own packing policy (core/bucketing.pack_by_size). ``algo``
    forces every dense unit's algorithm (tests/benches); None uses the
    selection table."""
    from mlsl_tpu.core.bucketing import pack_by_size

    mlsl_assert(layers, "overlap plan needs at least one layer")
    for _, _, comp in layers:
        mlsl_assert(
            comp in (CompressionType.NONE, CompressionType.QUANTIZATION),
            "compiled overlap supports NONE/QUANTIZATION compression "
            "(got %s — TOPK rides the host path)", comp,
        )
    stages = int(stages if stages is not None
                 else getattr(config, "overlap_stages", DEFAULT_STAGES))
    bucket_mb = int(bucket_mb if bucket_mb is not None
                    else getattr(config, "grad_bucket_mb", 0))
    block = int(block if block is not None
                else getattr(config, "quant_block_elems", 256))

    # bucket membership: the host packing policy over the uncompressed
    # layers (reverse order, singletons dropped, bandwidth-sized excluded)
    member_of: Dict[str, int] = {}
    plain = [(n, c) for n, c, comp in layers
             if comp == CompressionType.NONE]
    if bucket_mb > 0 and not group.is_self and group.size > 1:
        packs = pack_by_size(
            plain, bucket_mb * 1024 * 1024, lambda e: e[1] * 4
        )
        for gi, members in enumerate(packs):
            for n, _ in members:
                member_of[n] = gi
    counts = {n: c for n, c, _ in layers}
    comps = {n: comp for n, _, comp in layers}

    units: List[OverlapUnit] = []
    emitted: set = set()
    for name, _, comp in reversed(list(layers)):
        if name in emitted:
            continue
        if name in member_of:
            gi = member_of[name]
            members = tuple(
                n for n, _, _ in reversed(list(layers))
                if member_of.get(n) == gi
            )
            emitted.update(members)
            units.append(OverlapUnit(
                members, tuple(counts[n] for n in members),
                CompressionType.NONE,
                _unit_algo(group, sum(counts[n] for n in members) * 4,
                           CompressionType.NONE, config, algo),
                group, index=len(units), block=block, config=config,
            ))
            continue
        emitted.add(name)
        units.append(OverlapUnit(
            (name,), (counts[name],), comps[name],
            _unit_algo(group, counts[name] * 4, comps[name], config, algo),
            group, index=len(units), block=block, config=config,
        ))
    return OverlapPlan(group, units, stages)


# ---------------------------------------------------------------------------
# The staged in-graph scheduler
# ---------------------------------------------------------------------------


def _pin(entries: List[list]) -> None:
    """Pin a stage boundary: tie every in-flight carry together through ONE
    optimization_barrier so XLA cannot collapse the staged emission back
    into a single comm tail (the barrier constrains only the collective
    carries — backward compute upstream still floats freely for the
    latency-hiding scheduler to interleave)."""
    if not entries:
        return
    flat, treedefs = [], []
    for ent in entries:
        leaves, td = jax.tree.flatten(ent[1])
        flat.append(leaves)
        treedefs.append(td)
    all_leaves = [l for leaves in flat for l in leaves]
    if not all_leaves:
        return
    pinned = lax.optimization_barrier(tuple(all_leaves))
    off = 0
    for ent, leaves, td in zip(entries, flat, treedefs):
        ent[1] = jax.tree.unflatten(td, list(pinned[off:off + len(leaves)]))
        off += len(leaves)


def emit_schedule(
    plan: OverlapPlan,
    flat: Dict[str, jax.Array],
    residuals: Dict[str, jax.Array],
    on_ready: Optional[Callable[[str, jax.Array], None]] = None,
) -> Tuple[Dict[str, jax.Array], Dict[str, jax.Array]]:
    """Emit the staged schedule inside a traced shard_map body.

    ``flat``: per-layer local flat gradient arrays. ``residuals``: per-quant-
    unit local error-feedback arrays (threaded through, returned new).
    ``on_ready(name, reduced)`` is invoked the moment a unit's reduction
    completes (emission order — the per-layer fused-update hook). Returns
    (reduced dict, new residual dict)."""
    group = plan.group
    degenerate = group.is_self or group.size <= 1
    if degenerate:
        mypos = jnp.int32(0)
    else:
        sizes = _axis_sizes(group.topology.mesh)
        mypos = _group_rank(group.axes, sizes)

    inflight: List[list] = []  # [unit, carry, phase_idx]
    reduced: Dict[str, jax.Array] = {}
    new_res: Dict[str, jax.Array] = {}

    def retire(ent) -> None:
        parts, new_err = ent[0].finish(ent[1])
        if new_err is not None:
            new_res[ent[0].key] = new_err
        for n, r in parts.items():
            reduced[n] = r
            if on_ready is not None:
                on_ready(n, r)

    def tick() -> None:
        for ent in inflight:
            u = ent[0]
            for _ in range(u.per_tick):
                if ent[2] < u.nphases:
                    ent[1] = u.advance(ent[1], ent[2])
                    ent[2] += 1
        _pin([e for e in inflight if e[2] < e[0].nphases])
        for ent in [e for e in inflight if e[2] >= e[0].nphases]:
            inflight.remove(ent)
            retire(ent)

    for u in plan.units:
        inflight.append([u, u.prep(flat, mypos, residuals.get(u.key)), 0])
        tick()
    while inflight:
        tick()
    return reduced, new_res


# ---------------------------------------------------------------------------
# Standalone compiled multi-tensor reduce (parity suites, tuner sweep, bench)
# ---------------------------------------------------------------------------


def build_multi_reduce(
    group: ProcessGroup,
    counts: Sequence[int],
    *,
    compression: CompressionType = CompressionType.NONE,
    algo: Optional[str] = None,
    config=None,
    stages: Optional[int] = None,
    bucket_mb: int = 0,
    block: int = 256,
) -> Tuple[Callable, OverlapPlan]:
    """Compile the staged multi-tensor reduction standalone: -> (fn, plan).

    ``fn(bufs[, residuals]) -> (reduced list[, new residuals])`` over
    standard (R, D, S, M, n) distributed buffers, newest-first = the
    REVERSED list order (bufs[-1] starts first, like a backward pass).
    The lockstep-twin parity suites pin this against the host CommRequest
    path; the tuner sweep times it for the ``overlap_stages`` knob."""
    layers = [(f"t{i}", int(c), compression) for i, c in enumerate(counts)]
    plan = build_plan(group, layers, config, stages=stages,
                      bucket_mb=bucket_mb, block=block, algo=algo)
    topo = group.topology
    names = [n for n, _, _ in layers]
    res_keys = sorted(plan.err_lens)

    def body(bufs, res):
        flat = {
            n: b.reshape(b.shape[NUM_GRID_AXES:]) for n, b in zip(names, bufs)
        }
        res_l = {
            k: v.reshape(v.shape[NUM_GRID_AXES:]) for k, v in res.items()
        }
        reduced, new_res = emit_schedule(plan, flat, res_l)

        def lift(x):
            return x[None, None, None, None]

        return (
            [lift(reduced[n]) for n in names],
            {k: lift(v) for k, v in new_res.items()},
        )

    sm = smap(
        body, topo.mesh,
        in_specs=([_BUF_SPEC] * len(names), {k: _BUF_SPEC for k in res_keys}),
        out_specs=([_BUF_SPEC] * len(names), {k: _BUF_SPEC for k in res_keys}),
        check=False,
    )
    jitted = jax.jit(sm)

    def fn(bufs, residuals: Optional[dict] = None):
        if residuals is None and res_keys:
            residuals = zero_residuals(plan, topo)
        outs, new_res = jitted(list(bufs), residuals or {})
        if res_keys:
            return outs, new_res
        return outs

    return fn, plan


def zero_residuals(plan: OverlapPlan, topo) -> Dict[str, jax.Array]:
    """Fresh (zero) error-feedback residual buffers for the plan's quantized
    units — the same virgin state a host request's first round sees."""
    return {
        k: topo.shard_buffer(
            np.zeros((*topo.grid_shape, el), dtype=np.float32)
        )
        for k, el in plan.err_lens.items()
    }


# ---------------------------------------------------------------------------
# ZeRO-1 two-phase staged update (reduce-scatter -> owned update -> all-gather)
# ---------------------------------------------------------------------------


class _Zero1Unit:
    """One layer's ZeRO-1 exchange as staged phases: the reduce-scatter
    phases of the selected algorithm, the owned-shard SGD update, then the
    all-gather phases that reassemble the updated parameter. With the fused
    ring selected, BOTH wire phases are single Pallas kernel launches
    (ops/ring_kernels.py: ``kind='reduce_scatter'`` and the gather-only
    ``kind='all_gather'`` mode) — the host ZeRO-1 path's two lax programs
    (core/parameter_set.py grad/increment requests) collapsed into two
    kernel stages the overlap scheduler interleaves between layers."""

    def __init__(self, name: str, count: int, algo: str,
                 group: ProcessGroup, *, lr: float, denom: float,
                 config=None):
        self.name = name
        self.count = int(count)
        self.algo = algo
        g = max(int(group.size), 1)
        self.padded = -(-self.count // g) * g
        self.shard = self.padded // g
        self._lr, self._denom = float(lr), float(denom)
        self._degenerate = group.is_self or group.size <= 1
        if self._degenerate:
            self.nphases = 1
            return
        self._rs_prep, self._rs_phases, self._rs_finish = algos.inline_plan(
            "reduce_scatter", group, algo, self.padded,
            op=ReductionType.SUM, recv_count=self.shard, config=config,
        )
        if algo in ("pallas_ring", "pallas_ring2d"):
            # the gather phase rides the SAME kernel family as the reduce
            # phase: one fused all_gather launch over the same ring/snake
            from mlsl_tpu.ops import ring_kernels as rk_ops

            (self._ag_prep, self._ag_phases,
             self._ag_finish) = rk_ops.steps(
                "all_gather", group, self.shard,
                slots=getattr(config, "pallas_ring_slots", None),
                snake=(algo == "pallas_ring2d"),
            )
        else:
            sizes = _axis_sizes(group.topology.mesh)
            axes = group.axes

            def ag_phase(carry):
                cur, mypos = carry
                return _body_allgather(cur, axes=axes, sizes=sizes), mypos

            self._ag_prep = lambda x, mypos: (x, mypos)
            self._ag_phases = [ag_phase]
            self._ag_finish = lambda carry: carry[0]
        # reduce phases + the owned-shard update (its own stage: the
        # boundary between the two wire directions) + gather phases
        self.nphases = len(self._rs_phases) + 1 + len(self._ag_phases)
        self.per_tick = 1

    def prep(self, p_flat, g_flat, mypos):
        pad = self.padded - self.count
        p = jnp.pad(p_flat, (0, pad)) if pad else p_flat
        gr = jnp.pad(g_flat, (0, pad)) if pad else g_flat
        if self._degenerate:
            return {"p": p, "g": gr, "mypos": mypos}
        return {"p": p, "carry": self._rs_prep(gr, mypos), "mypos": mypos}

    def advance(self, state, i: int):
        if self._degenerate:
            state["p"] = state["p"] - self._lr * (state["g"] / self._denom)
            return state
        n_rs = len(self._rs_phases)
        if i < n_rs:
            state["carry"] = self._rs_phases[i](state["carry"])
        elif i == n_rs:
            # owned-shard update: this member updates ONLY its 1/G slice —
            # the ZeRO-1 contract; everyone else's slices arrive updated
            # through the gather phases
            gshard = self._rs_finish(state["carry"]) / self._denom
            owned = lax.dynamic_slice_in_dim(
                state["p"], state["mypos"] * self.shard, self.shard
            )
            state["carry"] = self._ag_prep(
                owned - self._lr * gshard, state["mypos"]
            )
        else:
            state["carry"] = self._ag_phases[i - n_rs - 1](state["carry"])
        return state

    def finish(self, state):
        if self._degenerate:
            return state["p"][: self.count]
        return self._ag_finish(state["carry"])[: self.count]


def _zero1_algo(group: ProcessGroup, payload: int, config,
                forced: Optional[str]) -> str:
    """Per-unit reduce-scatter algorithm for the ZeRO-1 plan: the same
    forced > table > in-graph-gate cascade as ``_unit_algo``, keyed on the
    reduce_scatter kind."""
    name = forced or algos.select(
        "reduce_scatter", group, payload, CompressionType.NONE, config,
        op=ReductionType.SUM,
    )
    if name and name != algos.DEFAULT and not algos.inline_eligible(
        name, "reduce_scatter", group, ReductionType.SUM
    ):
        log_debug(
            "zero1: algorithm %s not in-graph eligible on group %s; "
            "falling back to %s", name, algos.group_shape(group),
            algos.DEFAULT,
        )
        return algos.DEFAULT
    return name or algos.DEFAULT


def build_zero1_update(
    group: ProcessGroup,
    counts: Sequence[int],
    *,
    lr: float,
    denom: float = 1.0,
    algo: Optional[str] = None,
    config=None,
    stages: Optional[int] = None,
) -> Tuple[Callable, List[_Zero1Unit]]:
    """Compile the staged ZeRO-1 update standalone: -> (fn, units).

    ``fn(param_bufs, grad_bufs) -> new param bufs`` over standard
    (R, D, S, M, n) distributed buffers, newest-first (the reversed list
    starts first, like a backward pass). Each layer is ONE `_Zero1Unit`:
    reduce-scatter the gradient, update the owned 1/G shard with SGD
    (``p -= lr * g / denom``), all-gather the updated parameter — the
    optimizer-state-sharded schedule the host path runs as two separate
    request families, emitted here as in-graph stages with the phase
    boundaries pinned like the allreduce schedule. With the fused ring
    selected (forced/tuned ``pallas_ring``/``pallas_ring2d``), both wire
    phases are single Pallas kernel launches."""
    mlsl_assert(counts, "zero1 plan needs at least one layer")
    stages = int(stages if stages is not None
                 else getattr(config, "overlap_stages", DEFAULT_STAGES))
    units = [
        _Zero1Unit(
            f"p{i}", int(c),
            _zero1_algo(group, int(c) * 4, config, algo),
            group, lr=lr, denom=denom, config=config,
        )
        for i, c in enumerate(counts)
    ]
    for u in units:
        u.per_tick = max(1, -(-u.nphases // max(stages, 1)))
    topo = group.topology
    degenerate = group.is_self or group.size <= 1
    names = [u.name for u in units]

    def body(p_bufs, g_bufs):
        if degenerate:
            mypos = jnp.int32(0)
        else:
            sizes = _axis_sizes(group.topology.mesh)
            mypos = _group_rank(group.axes, sizes)
        flat_p = {n: b.reshape(b.shape[NUM_GRID_AXES:])
                  for n, b in zip(names, p_bufs)}
        flat_g = {n: b.reshape(b.shape[NUM_GRID_AXES:])
                  for n, b in zip(names, g_bufs)}
        inflight: List[list] = []  # [unit, state, phase_idx]
        out: Dict[str, jax.Array] = {}

        def tick() -> None:
            for ent in inflight:
                u = ent[0]
                for _ in range(u.per_tick):
                    if ent[2] < u.nphases:
                        ent[1] = u.advance(ent[1], ent[2])
                        ent[2] += 1
            _pin([e for e in inflight if e[2] < e[0].nphases])
            for ent in [e for e in inflight if e[2] >= e[0].nphases]:
                inflight.remove(ent)
                out[ent[0].name] = ent[0].finish(ent[1])

        for u in reversed(units):
            inflight.append([u, u.prep(flat_p[u.name], flat_g[u.name],
                                       mypos), 0])
            tick()
        while inflight:
            tick()
        return [out[n][None, None, None, None] for n in names]

    sm = smap(
        body, topo.mesh,
        in_specs=([_BUF_SPEC] * len(names), [_BUF_SPEC] * len(names)),
        out_specs=[_BUF_SPEC] * len(names),
        check=False,
    )
    jitted = jax.jit(sm)

    def fn(param_bufs, grad_bufs):
        return jitted(list(param_bufs), list(grad_bufs))

    return fn, units


# ---------------------------------------------------------------------------
# Trainer engine
# ---------------------------------------------------------------------------


class OverlapEngine:
    """The trainer-facing compiled overlap mode: owns the plan, the compiled
    step program(s), and the error-feedback residual state.

    Two program shapes, both single-dispatch for the comm segment:

    - fused: ``(params, residuals, batch) -> (loss, params', residuals')`` —
      backward + staged in-graph collectives + per-layer updates in ONE
      donation-enabled executable.
    - split: ``_grad_fn`` first (the trainer's existing program), then
      ``(params, residuals, grads) -> (params', residuals')`` — used when
      the sentinel quality gate is armed (the gate needs the gradient
      boundary on the host, before any comm starts; sentinel ``skip_step``
      then never dispatches the comm program, so residuals never advance —
      the same lockstep contract as the host path).

    Chaos: every engine step passes the ``collective.dispatch`` site ONCE at
    the step boundary (the whole comm segment is one dispatch), so armed
    budgets fire at the step they target. The precompile warm calls the
    jitted programs directly and never passes the site.
    """

    def __init__(self, trainer, plan: OverlapPlan):
        self.plan = plan
        self._trainer = trainer
        cfg_donate = trainer.donate_params
        mesh = trainer.mesh
        layers = trainer.layers
        counts = trainer.layer_counts
        padded = trainer.padded_counts
        get_layer = trainer.get_layer
        loss_fn = trainer.loss_fn
        lr, data_size = trainer.lr, trainer.data_size
        clip = trainer.clip_global_norm
        from mlsl_tpu.models.train import (  # lazy: avoid import cycle
            _clip_scale, _set_layer, _unflatten_like, build_local_grads,
        )

        res_keys = sorted(plan.err_lens)
        res_specs = {k: _BUF_SPEC for k in res_keys}
        # THE host _grad_fn's flatten/pad core — one implementation, so the
        # compiled twin can never drift from the parity oracle's grads
        grads_core = build_local_grads(loss_fn, layers, get_layer, padded)

        def local_grads(params, x, y):
            x = x.reshape(x.shape[NUM_GRID_AXES:])
            y = y.reshape(y.shape[NUM_GRID_AXES:])
            return grads_core(params, x, y)

        def reduce_and_update(params, res_l, flat):
            new_subs: Dict[str, object] = {}

            def apply(name, r):
                g = r[: counts[name]] / data_size
                sub = get_layer(params, name)
                new_subs[name] = jax.tree.map(
                    lambda p, gg: p - lr * gg, sub, _unflatten_like(sub, g)
                )

            # per-layer update fused at retirement (emission order) — except
            # under global-norm clipping, whose scale needs EVERY reduced
            # gradient before the first update
            on_ready = apply if clip is None else None
            reduced, new_res = emit_schedule(self.plan, flat, res_l, on_ready)
            if clip is not None:
                cscale = _clip_scale(
                    sum(
                        jnp.sum((reduced[n][: counts[n]] / data_size) ** 2)
                        for n in layers
                    ),
                    clip,
                )
                for name in layers:
                    g = reduced[name][: counts[name]] / data_size * cscale
                    sub = get_layer(params, name)
                    new_subs[name] = jax.tree.map(
                        lambda p, gg: p - lr * gg, sub,
                        _unflatten_like(sub, g),
                    )
            new_params = params
            for name in layers:
                new_params = _set_layer(new_params, name, new_subs[name])
            return new_params, new_res

        def lift(x):
            return x[None, None, None, None]

        def fused_body(params, res, x, y):
            loss, flat = local_grads(params, x, y)
            res_l = {
                k: v.reshape(v.shape[NUM_GRID_AXES:]) for k, v in res.items()
            }
            new_params, new_res = reduce_and_update(params, res_l, flat)
            return (
                loss[None, None, None, None, None],
                new_params,
                {k: lift(v) for k, v in new_res.items()},
            )

        def sync_body(params, res, flat_bufs):
            flat = {
                n: b.reshape(b.shape[NUM_GRID_AXES:])
                for n, b in flat_bufs.items()
            }
            res_l = {
                k: v.reshape(v.shape[NUM_GRID_AXES:]) for k, v in res.items()
            }
            new_params, new_res = reduce_and_update(params, res_l, flat)
            return new_params, {k: lift(v) for k, v in new_res.items()}

        fused_sm = smap(
            fused_body, mesh,
            in_specs=(P(), res_specs, _BUF_SPEC, _BUF_SPEC),
            out_specs=(_BUF_SPEC, P(), res_specs),
            check=False,
        )
        sync_sm = smap(
            sync_body, mesh,
            in_specs=(P(), res_specs, {n: _BUF_SPEC for n in layers}),
            out_specs=(P(), res_specs),
            check=False,
        )
        donate = (0, 1) if cfg_donate else (1,)
        self._step_fn = jax.jit(
            lambda p, r, b: fused_sm(p, r, b[0], b[1]), donate_argnums=donate
        )
        self._sync_fn = jax.jit(sync_sm, donate_argnums=donate)
        self.residuals = zero_residuals(plan, trainer.dist.topology)
        self._descr_logged = False
        log_debug(
            "compiled overlap plan: %d units (%s), stages=%d, %d phases",
            len(plan.units), plan.algos_summary(), plan.stages, plan.rounds,
        )

    # -- the step ----------------------------------------------------------

    def step(self, batch, *, grads=None, loss=None) -> jax.Array:
        """One compiled-overlap step. With ``grads`` (and ``loss``) given the
        split program runs (the sentinel-gated path: the caller already ran
        ``_grad_fn`` and the quality gate); otherwise the fused single
        program."""
        trainer = self._trainer
        tr = obs._tracer
        t0 = tr.now() if tr is not None else 0
        if chaos._plans:
            # the whole comm segment is ONE dispatch; armed budgets fire at
            # the step boundary they target
            chaos.inject("collective.dispatch", kind="overlap")
        split = grads is not None
        if split:
            new_params, self.residuals = self._sync_fn(
                trainer.params, self.residuals, grads
            )
        else:
            loss, new_params, self.residuals = self._step_fn(
                trainer.params, self.residuals, batch
            )
        trainer.params = new_params
        plan = self.plan
        stats_mod.record_overlap_step(
            len(plan.units), plan.rounds, plan.total_bytes,
            split=split, breakdown=plan.breakdown,
        )
        if tr is not None:
            tr.complete(
                "step.overlap", "step", t0, step=trainer._step_no,
                layers=len(trainer.layers), units=len(plan.units),
                stages=plan.stages, phases=plan.rounds,
                algos=plan.algos_summary(), quant_units=plan.quant_units,
                bytes=plan.total_bytes, split=split,
            )
        return loss

    # -- AOT warm-up (MLSL_PRECOMPILE) -------------------------------------

    def precompile(self, batch) -> None:
        """Warm the compiled program(s) on donation-safe copies so step 0 of
        the timed loop contains no compilation (the trainer.precompile
        contract). The warm calls the jitted fns directly — never the chaos
        site — so armed budgets survive to the step they target."""
        trainer = self._trainer
        copy = lambda tree: jax.tree.map(jnp.copy, tree)
        if trainer.sentinel is not None and trainer.sentinel.gate_armed:
            loss, grads = trainer._grad_fn(trainer.params, batch)
            out = self._sync_fn(copy(trainer.params), copy(self.residuals),
                                grads)
        else:
            out = self._step_fn(copy(trainer.params), copy(self.residuals),
                                batch)
        jax.block_until_ready(out)


def engine_for_trainer(trainer, config) -> Optional[OverlapEngine]:
    """Build the trainer's OverlapEngine, or None when the graph cannot ride
    the compiled path (the caller falls back to the host engine):
    custom codecs keep their host wire format, TOPK its sparse requests,
    color groups their flat-mesh programs. Contradictory *explicit* requests
    (optax / ZeRO-1 / overlap_updates with overlap_compiled) are asserted in
    the trainer ctor, not here."""
    group = trainer.dist.grad_group
    if getattr(config, "custom_codec", None) is not None:
        log_debug("overlap: custom codec rides the host path")
        return None
    if group.colors is not None:
        log_debug("overlap: color-group gradients ride the host path")
        return None
    layers = [
        (name, trainer.padded_counts[name],
         trainer.ops[name].get_parameter_set(0).compression)
        for name in trainer.layers
    ]
    if any(comp == CompressionType.TOPK for _, _, comp in layers):
        log_debug("overlap: TOPK compression rides the host path")
        return None
    plan = build_plan(group, layers, config)
    if getattr(config, "verify", False):
        # MLSL_VERIFY=1 covers the compiled-overlap plan too: the donated
        # carry/EF geometry is fixed here, before the step program traces —
        # the same commit-time gate contract as Session.commit
        # (analysis/plan.py A112/A120/A122; severity per
        # MLSL_VERIFY_SEVERITY, enforced by the shared plan.enforce gate)
        import time

        from mlsl_tpu.analysis import plan as plan_verifier

        t0 = time.perf_counter()
        plan_verifier.enforce(
            plan_verifier.verify_overlap_plan(
                plan, block=getattr(config, "quant_block_elems", None)
                if plan.quant_units else None,
            ),
            config, "compiled-overlap plan", t0,
        )
    return OverlapEngine(trainer, plan)
