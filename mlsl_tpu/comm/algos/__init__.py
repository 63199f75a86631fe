"""Collective algorithm engine: multiple lowering strategies per collective.

The reference eplib ships TWO allreduce implementations — the MPI-native one
and a recursive-halving/doubling priority allreduce (eplib/allreduce_pr.c) —
selected by environment knobs. Our TPU port until now lowered every
collective to exactly one ``lax`` program. This package restores (and
extends) the algorithm dimension:

- ``lax``     — the single-shot XLA-native body (comm/collectives.py): psum /
                psum_scatter / gather emulation. The baseline and the
                heuristic default; untuned behavior is bit-for-bit this.
- ``rhd``     — recursive halving/doubling composed from the pairwise
                exchange primitive (``lax.ppermute``, the same op behind the
                sendrecv body): log2(G) rounds of halving (reduce-scatter)
                and doubling (all-gather), with the classic pre/post fold
                remainder step for non-power-of-two groups. Paper parity
                with eplib/allreduce_pr.c. Latency-optimal round count.
- ``ring2d``  — hierarchical ring-of-rings for multi-axis (torus) groups:
                reduce-scatter along the minor mesh axis, reduce over the
                remaining axes, all-gather back along the minor axis. Each
                phase rides ONE physical ICI ring instead of asking XLA to
                fuse a reduction over the whole sub-torus (EQuARX/DynamiQ
                both report the multi-hop topology-aware decomposition is
                where large-group allreduce wins live).
- ``pallas_ring`` — the hand-written fused ring kernel (ops/ring_kernels.py,
                algos/pallas_ring.py): double-buffered
                ``make_async_remote_copy`` RDMA per hop with the int8 codec
                fused inside the kernel at the VMEM boundary. Single-live-
                axis ring groups on TPU (or under the explicit
                MLSL_PALLAS_INTERPRET gate off-chip); dense f32/bf16/i32
                here, and the int8-quantized variant of the same kernel
                selectable for COMPRESSION=QUANTIZATION requests (a
                compressed case the table routes — quant_ring's
                ``ring='pallas'`` wire).
- ``pallas_rhd`` — the latency-class fused allreduce (ops/rhd_kernels.py,
                algos/pallas_rhd.py): recursive halving/doubling as ONE
                Pallas kernel — 2*log2(G) remote-DMA exchange rounds between
                VMEM slots with pre/post folds for non-power-of-two groups
                (rhd's exact pair math). The small-message regime's answer:
                selected by tuned cells / MLSL_ALGO like any algorithm, and
                by the heuristic rung for sub-payload-band dense SUM
                allreduces when MLSL_PALLAS_RHD armed it.
- ``pallas_ring2d`` — the fused ring riding a 2-live-axis sub-torus
                (algos/pallas_ring2d.py): the SAME kernel as pallas_ring
                over the snake (boustrophedon) Hamiltonian cycle, so one
                ring drives both ICI axes' links (and both directions of
                each with MLSL_PALLAS_RING_BIDIR) — the groups the 1D ring
                refuses and ring2d served with composed lax phases.
- ``pallas_a2a`` — the fused quantized all-to-all (ops/a2a_kernels.py,
                algos/pallas_a2a.py) and the first member of the NEW
                ``alltoall`` engine kind: MoE dispatch/combine with the
                int8 blockwise codec fused at the VMEM boundary (quantize
                on send-slot write, dequantize on receive — wire bytes
                <= 1/3 of f32). models/moe.py routes through
                ``inline_alltoall``'s selection instead of hardcoded lax.
- ``hier``    — two-tier hierarchical allreduce for pod-scale worlds
                (algos/hier.py): intra-slice reduce-scatter -> inter-slice
                allreduce over the 1/L shard -> intra-slice all-gather,
                with a per-tier codec (f32 on ICI; int8-blockwise/top-k on
                the DCN hop via quant_ring's ``ring='hier'`` wire — a
                THC-style shared-scale integer sum that never dequantizes
                per hop). Tier structure from ``mesh.world_tier_ids``
                (``MLSL_MESH_TIERS`` override / multislice ``slice_index``).

Selection (``select``) is keyed by (kind, payload bytes, group shape,
compression) with strict precedence:

    explicit config (MLSL_ALGO)  >  tuned profile (mlsl_tpu.tuner)  >
    heuristic default ("lax")

The heuristic default is deliberately the baseline: with no explicit knob
and no measured profile the dispatched programs are bit-for-bit what they
were before this engine existed. Only a measurement (the tuner) or an
explicit operator override changes the program.

Programs built here are cached in the SAME cache as the baseline
(collectives._cache) with the algorithm name in the key, wrapped in the same
chaos-dispatch instrumentation, and therefore cleared by
collectives.clear_cache() and warmed by MLSL_PRECOMPILE like every other
collective program (the plan-cache key carries the algorithm identity —
core/session.py).
"""

from __future__ import annotations

from typing import Callable, Tuple

import numpy as np

from mlsl_tpu.comm.mesh import ProcessGroup
from mlsl_tpu.log import log_debug, mlsl_assert
from mlsl_tpu.types import CompressionType, ReductionType

#: the baseline algorithm: the single-shot lax program (comm/collectives.py)
DEFAULT = "lax"

#: engine kinds: the elementwise-reduction collectives (the reference's
#: algorithm choice is likewise allreduce-first) plus — new with the fused
#: kernel family — the MoE dispatch/combine exchange
ENGINE_KINDS = ("allreduce", "reduce_scatter", "alltoall")


def group_shape(group: ProcessGroup) -> Tuple[int, ...]:
    """The selection-table shape key for a group: per-axis member counts for
    axis-aligned groups (major -> minor, degenerate size-1 axes dropped so a
    4-axis global group over a (1, 4, 1, 2) grid and a 2-axis (4, 2) group
    share one profile cell), ``(-G,)`` for color groups (the sign marks
    'color' so a color group never aliases a 1D axis group of the same
    size)."""
    if group.colors is not None:
        return (-int(group.size),)
    topo = group.topology
    sizes = dict(zip(topo.mesh.axis_names, topo.mesh.devices.shape))
    shape = tuple(int(sizes[a]) for a in group.axes if sizes[a] > 1)
    return shape or (1,)


def _eligible_rhd(kind: str, group: ProcessGroup, op) -> bool:
    # uniform groups only (the pairwise schedule needs equal member counts);
    # any op (pairwise combine handles MIN/MAX, unlike ring/scatter forms)
    if group.is_self or not group.is_uniform:
        return False
    if group.size <= 1:
        return False
    if kind == "reduce_scatter" and op not in (None, ReductionType.SUM,
                                               ReductionType.MIN,
                                               ReductionType.MAX):
        return False
    return True


def _eligible_ring2d(kind: str, group: ProcessGroup, op) -> bool:
    # SUM only (the scatter phases are psum_scatter) on axis-aligned groups
    # spanning >= 2 non-degenerate mesh axes (a real sub-torus)
    if group.colors is not None or op not in (None, ReductionType.SUM):
        return False
    live = [s for s in group_shape(group) if s > 1]
    if len(live) < 2:
        return False
    if kind == "reduce_scatter" and len(live) != 2:
        # the 2-phase scatter placement math is 2D; >2 live axes fall back
        return False
    return True


def _eligible_pallas_ring(kind: str, group: ProcessGroup, op) -> bool:
    # single-live-axis ring groups, SUM only, and only on a backend that can
    # run the kernel (TPU, or the explicit interpret gate) — lazily imported
    # so the registry stays importable from config validation without jax
    from mlsl_tpu.ops import ring_kernels

    return ring_kernels.eligible_dense(kind, group, op)


def _eligible_hier(kind: str, group: ProcessGroup, op) -> bool:
    # single-live-axis groups with a uniform two-tier split (MLSL_MESH_TIERS
    # or multislice topology), SUM only — lazily imported like pallas_ring
    from mlsl_tpu.comm.algos import hier

    return hier.eligible(kind, group, op)


def _eligible_pallas_rhd(kind: str, group: ProcessGroup, op) -> bool:
    # allreduce only, SUM only, single-live-axis uniform groups, and a
    # backend that can run the kernel — lazily imported like pallas_ring
    from mlsl_tpu.ops import rhd_kernels

    return rhd_kernels.eligible(kind, group, op)


def _eligible_pallas_ring2d(kind: str, group: ProcessGroup, op) -> bool:
    # exactly two live mesh axes (the snake cycle is 2D), SUM only
    from mlsl_tpu.ops import ring_kernels

    return ring_kernels.eligible_dense2d(kind, group, op)


def _eligible_pallas_a2a(kind: str, group: ProcessGroup, op) -> bool:
    # alltoall only (op-less), single-live-axis or color-flat uniform groups
    from mlsl_tpu.ops import a2a_kernels

    return a2a_kernels.eligible(kind, group, op=op)


#: name -> eligibility predicate; builders are resolved lazily (the bodies
#: import jax)
_ELIGIBLE = {
    "lax": lambda kind, group, op: True,
    "rhd": _eligible_rhd,
    "ring2d": _eligible_ring2d,
    "pallas_ring": _eligible_pallas_ring,
    "pallas_rhd": _eligible_pallas_rhd,
    "pallas_ring2d": _eligible_pallas_ring2d,
    "pallas_a2a": _eligible_pallas_a2a,
    "hier": _eligible_hier,
}

ALGORITHMS = tuple(_ELIGIBLE)


def eligible(algo: str, kind: str, group: ProcessGroup, op=None) -> bool:
    """Can ``algo`` lower (kind, group, op)? Unknown names are never eligible."""
    if kind not in ENGINE_KINDS:
        return algo == DEFAULT
    if kind == "alltoall" and algo not in (DEFAULT, "pallas_a2a"):
        # the reduction algorithms' predicates predate the alltoall kind and
        # do not check it — the central guard keeps a global MLSL_ALGO=rhd
        # from claiming the MoE exchange it cannot lower
        return False
    pred = _ELIGIBLE.get(algo)
    return bool(pred and pred(kind, group, op))


def candidates(kind: str, group: ProcessGroup, op=None) -> Tuple[str, ...]:
    """Every algorithm eligible for (kind, group, op), baseline first."""
    return tuple(a for a in ALGORITHMS if eligible(a, kind, group, op))


def parse_forced(spec: str) -> dict:
    """Parse MLSL_ALGO: either one algorithm name (forced for every engine
    kind) or a comma list of kind=name entries. Raises MLSLError (via
    mlsl_assert) on unknown algorithm or kind names — the config-validation
    contract: a contradictory setting fails at init, not deep in dispatch."""
    spec = (spec or "").strip()
    out: dict = {}
    if not spec:
        return out
    if "=" not in spec:
        mlsl_assert(
            spec in ALGORITHMS,
            "MLSL_ALGO %r is not a registered collective algorithm "
            "(registry: %s)", spec, ", ".join(ALGORITHMS),
        )
        out["*"] = spec
        return out
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        mlsl_assert("=" in part, "MLSL_ALGO entry %r is not kind=algo", part)
        kind, _, name = part.partition("=")
        kind, name = kind.strip(), name.strip()
        mlsl_assert(
            kind in ENGINE_KINDS,
            "MLSL_ALGO kind %r is not an engine collective (expected one of "
            "%s)", kind, ", ".join(ENGINE_KINDS),
        )
        mlsl_assert(
            name in ALGORITHMS,
            "MLSL_ALGO %r for kind %r is not a registered collective "
            "algorithm (registry: %s)", name, kind, ", ".join(ALGORITHMS),
        )
        out[kind] = name
    return out


def select(
    kind: str,
    group: ProcessGroup,
    payload_bytes: int,
    compression: CompressionType,
    config,
    op=None,
) -> str:
    """The selection table: explicit config > tuned profile > heuristic
    default. An explicit or tuned choice that is not eligible for this
    (kind, group, op) falls back to the baseline with a debug log — forcing
    ``rhd`` globally must not break the ragged-color-group requests the
    pairwise schedule cannot serve."""
    if kind not in ENGINE_KINDS or config is None:
        return DEFAULT
    if compression != CompressionType.NONE:
        # Compressed collectives have their own wire formats (quant ring /
        # sparse top-k); the engine's dense algorithms do not apply — with
        # TWO exceptions the table routes: the fused pallas ring has an
        # int8-quantized variant (quant_ring's ring='pallas' wire), and the
        # two-tier 'hier' lowering carries the compressed wire on its DCN
        # hop only (quant_ring's ring='hier' wire — intra-slice phases stay
        # f32). A forced or tuned choice of either is honored for
        # QUANTIZATION when the group qualifies; everything else keeps the
        # composed flat ring.
        if (
            compression == CompressionType.QUANTIZATION
            and getattr(config, "custom_codec", None) is None
        ):
            name = _requested(kind, group, payload_bytes, compression, config)
            if name == "pallas_ring" and _quant_pallas_eligible(group, config):
                return _breaker_gate(name, kind)
            if name == "hier" and _quant_hier_eligible(kind, group, config):
                return _breaker_gate(name, kind)
            if name in ("pallas_ring", "hier"):
                log_debug(
                    "%s not eligible for quantized %s on group %s; "
                    "keeping the composed quant ring", name, kind,
                    group_shape(group),
                )
        return DEFAULT
    name = _requested(kind, group, payload_bytes, compression, config)
    if name and name != DEFAULT:
        if eligible(name, kind, group, op):
            return _breaker_gate(name, kind)
        log_debug(
            "selected algorithm %s not eligible for %s on group %s; "
            "falling back to %s", name, kind, group_shape(group), DEFAULT,
        )
        return DEFAULT
    if name == DEFAULT:
        # an explicit or tuned 'lax' pins the baseline — the heuristic rung
        # must not override an operator's measured/forced choice
        return DEFAULT
    # Heuristic rung (below explicit and tuned): the latency-class fused
    # allreduce for payloads inside the small-message band — ONLY when the
    # operator armed MLSL_PALLAS_RHD, so with no knob and no profile the
    # dispatched program stays bit-for-bit the baseline (the engine's
    # founding contract).
    if (
        kind == "allreduce"
        and getattr(config, "pallas_rhd", False)
        and eligible("pallas_rhd", kind, group, op)
    ):
        from mlsl_tpu.ops import rhd_kernels

        if payload_bytes <= rhd_kernels.env_max_bytes(config):
            return _breaker_gate("pallas_rhd", kind)
    return DEFAULT


def _requested(kind, group, payload_bytes, compression, config):
    """The raw forced/tuned choice for this cell, eligibility unchecked:
    explicit config (MLSL_ALGO) first, else the tuned profile's cell, else
    None."""
    forced = getattr(config, "_forced_algos", None)
    if forced:
        name = forced.get(kind) or forced.get("*")
        if name:
            return name
    profile = getattr(config, "tuned_profile", None)
    if profile is not None:
        return profile.select(kind, group_shape(group), compression,
                              payload_bytes)
    return None


def _quant_pallas_eligible(group: ProcessGroup, config) -> bool:
    from mlsl_tpu.ops import ring_kernels

    block = int(getattr(config, "quant_block_elems", 256))
    return ring_kernels.eligible_quant(group, block)


def _quant_hier_eligible(kind: str, group: ProcessGroup, config) -> bool:
    from mlsl_tpu.comm.algos import hier

    if kind != "allreduce":
        return False
    block = int(getattr(config, "quant_block_elems", 256))
    return hier.eligible_quant(group, block)


def _breaker_gate(name: str, kind: str) -> str:
    """Rung 3 at selection time: a non-baseline choice is honored only while
    the algo-engine circuit breaker admits it (mlsl_tpu.supervisor). An OPEN
    breaker pins NEW requests to the baseline; requests already built degrade
    per dispatch in CommRequest. Lazy import: the registry must stay
    importable from config validation."""
    from mlsl_tpu import supervisor

    if not supervisor.breaker("algo").allow():
        log_debug(
            "algo breaker open: %s for %s degrades to %s", name, kind, DEFAULT
        )
        return DEFAULT
    return name


def inline_eligible(algo: str, kind: str, group: ProcessGroup, op=None) -> bool:
    """Can ``algo`` be embedded IN-GRAPH (compiled overlap, comm/overlap.py)
    for (kind, group, op)? A strict subset of ``eligible``: the in-graph
    phase builders ride the group's own mesh axes, and a color group's axes
    are ``()`` (core/distribution.py builds them over the flat mesh), so NO
    algorithm — the baseline included — can reduce one in-graph: a psum
    over zero axes would be a silent identity, not a per-color reduction.
    Color-group graphs ride the host path (the standalone flat-mesh
    programs); only degenerate (size-1) color groups pass, where the
    identity IS the reduction. ``pallas_ring`` additionally requires a
    backend whose in-graph form can execute (TPU: the Pallas interpreter
    cannot resolve remote DMA inside the 4-axis grid shard_map, so off-chip
    the overlap plan falls back to the baseline)."""
    if group.colors is not None and int(group.size) > 1:
        return False
    if algo == "pallas_ring":
        from mlsl_tpu.ops import ring_kernels

        if not ring_kernels.inline_ok(group):
            return False
    if algo == "pallas_rhd":
        from mlsl_tpu.ops import rhd_kernels

        if not rhd_kernels.inline_ok(group):
            return False
    if algo == "pallas_ring2d":
        from mlsl_tpu.ops import ring_kernels

        if not ring_kernels.inline_ok2d(group):
            return False
    if algo == "pallas_a2a":
        from mlsl_tpu.ops import a2a_kernels

        if not a2a_kernels.inline_ok(group):
            return False
    return eligible(algo, kind, group, op)


def inline_plan(kind: str, group: ProcessGroup, algo: str, count: int, *,
                op=None, recv_count=None, config=None):
    """The in-graph (compiled-overlap) form of ``algo``: ``(prep, phases,
    finish)`` closures usable inside a shard_map body over the group's own
    topology mesh — ``prep(x, mypos) -> carry``, each ``phases[i](carry) ->
    carry`` is one collective phase (the unit the overlap scheduler
    interleaves between layers), ``finish(carry) -> result``. ``mypos`` must
    be the member's flattened group position (collectives._group_rank over
    the group axes); ``count`` is the static per-member element count.

    ``lax`` is the single-phase baseline (the exact ``_body_allreduce`` /
    ``_body_reduce_scatter`` ops); ``rhd``/``ring2d`` expose the same phase
    sequences their standalone ``build`` programs compile — one ppermute
    round / one ring phase per entry, bit-for-bit the same math.
    """
    from mlsl_tpu.comm import collectives
    from mlsl_tpu.types import ReductionType

    mlsl_assert(
        inline_eligible(algo, kind, group, op),
        "algorithm %s cannot lower %s in-graph on group shape %s",
        algo, kind, group_shape(group),
    )
    rop = ReductionType(op) if op is not None else ReductionType.SUM
    if group.is_self or group.size <= 1:
        # degenerate group: every reduction is the identity (the compiled
        # per-layer schedule still builds and runs on one chip)
        if kind == "reduce_scatter" and recv_count is not None:
            return (lambda x, mypos: (x, mypos), [],
                    lambda carry: carry[0][:recv_count])
        return lambda x, mypos: (x, mypos), [], lambda carry: carry[0]
    if kind == "alltoall":
        if algo == DEFAULT:
            from jax import lax as _lax

            ax = group.axes if len(group.axes) > 1 else group.axes[0]
            g = int(group.size)

            def lax_a2a(carry):
                cur, mypos = carry
                out = _lax.all_to_all(cur.reshape(g, -1), ax,
                                      split_axis=0, concat_axis=0)
                return out.reshape(-1), mypos

            return (lambda x, mypos: (x, mypos), [lax_a2a],
                    lambda carry: carry[0])
        from mlsl_tpu.comm.algos import pallas_a2a
        from mlsl_tpu.ops import a2a_kernels

        # codec/slot knobs from the caller's config, same contract as the
        # fused ring: the in-graph kernel runs the host path's geometry
        return pallas_a2a.steps(
            kind, group, count,
            block=int(getattr(config, "quant_block_elems", 256)),
            quantized=a2a_kernels.quant_enabled(config),
            slots=getattr(config, "pallas_ring_slots", None),
        )
    if algo == DEFAULT:
        sizes = collectives._axis_sizes(group.topology.mesh)

        def lax_phase(carry):
            cur, mypos = carry
            if kind == "reduce_scatter":
                return collectives._body_reduce_scatter(
                    cur, axes=group.axes, sizes=sizes, op=rop,
                    recv_count=recv_count,
                ), mypos
            return collectives._preduce(cur, group.axes, rop), mypos

        return lambda x, mypos: (x, mypos), [lax_phase], lambda carry: carry[0]
    if algo == "rhd":
        from mlsl_tpu.comm.algos import rhd

        ax = group.axes if len(group.axes) > 1 else group.axes[0]
        return rhd.steps(
            kind, int(group.size), count, ax, lambda pairs: pairs,
            op=rop, recv_count=recv_count,
        )
    if algo == "pallas_ring":
        from mlsl_tpu.comm.algos import pallas_ring

        # kernel-geometry knobs come from the caller's config (tuned
        # profiles apply there) — the in-graph kernel must run the same
        # slot geometry as the host-path requests
        return pallas_ring.steps(
            kind, group, count, op=rop, recv_count=recv_count,
            slots=getattr(config, "pallas_ring_slots", None),
            bidir=getattr(config, "pallas_ring_bidir", None),
        )
    if algo == "pallas_rhd":
        from mlsl_tpu.comm.algos import pallas_rhd

        return pallas_rhd.steps(
            kind, group, count, op=rop, recv_count=recv_count,
            slots=getattr(config, "pallas_ring_slots", None),
        )
    if algo == "pallas_ring2d":
        from mlsl_tpu.comm.algos import pallas_ring2d

        return pallas_ring2d.steps(
            kind, group, count, op=rop, recv_count=recv_count,
            slots=getattr(config, "pallas_ring_slots", None),
            bidir=getattr(config, "pallas_ring_bidir", None),
        )
    if algo == "hier":
        from mlsl_tpu.comm.algos import hier

        return hier.steps(kind, group, count, op=rop, recv_count=recv_count)
    from mlsl_tpu.comm.algos import ring2d

    return ring2d.steps(kind, group, count, op=rop, recv_count=recv_count)


def build(kind: str, group: ProcessGroup, dtype, algo: str, **kw) -> Callable:
    """Build (or fetch) the compiled program for ``algo``: global distributed
    buffer -> global result buffer, the exact calling convention of
    collectives.build_collective. ``algo='lax'`` IS build_collective — same
    cache entry, same key, bit-for-bit the baseline program."""
    from mlsl_tpu.comm import collectives

    if algo == DEFAULT:
        return collectives.build_collective(kind, group, dtype, **kw)
    mlsl_assert(
        eligible(algo, kind, group, kw.get("op")),
        "algorithm %s cannot lower %s on group shape %s",
        algo, kind, group_shape(group),
    )
    key = (
        "algo", algo, kind, collectives._group_key(group),
        np.dtype(dtype).str, tuple(sorted(kw.items())),
    )
    fn = collectives._cache.get(key)
    if fn is not None:
        return fn
    if algo == "rhd":
        from mlsl_tpu.comm.algos import rhd as impl
    elif algo == "pallas_ring":
        from mlsl_tpu.comm.algos import pallas_ring as impl
    elif algo == "pallas_rhd":
        from mlsl_tpu.comm.algos import pallas_rhd as impl
    elif algo == "pallas_ring2d":
        from mlsl_tpu.comm.algos import pallas_ring2d as impl
    elif algo == "pallas_a2a":
        from mlsl_tpu.comm.algos import pallas_a2a as impl
    elif algo == "hier":
        from mlsl_tpu.comm.algos import hier as impl
    else:
        from mlsl_tpu.comm.algos import ring2d as impl
    fn = collectives._chaos_dispatch(impl.build(kind, group, **kw), kind)
    collectives._cache[key] = fn
    return fn


# ---------------------------------------------------------------------------
# Engine-owned in-graph collectives for SPMD model/parallel code
# ---------------------------------------------------------------------------
#
# Model and parallelism modules (models/moe.py, parallel/pipeline.py) used to
# embed raw ``lax.p*`` calls inside their shard_map bodies, each carrying an
# A201 lint pragma. These helpers move the raw call INTO the engine: the one
# call site future alternative lowerings (a DCN-staged hierarchical alltoall,
# a tiered gather) slot in behind, and the place the selection table applies
# when the caller can hand over a ProcessGroup. A body-local collective with
# only an axis name lowers to the lax baseline.


def inline_allreduce(x, axis, *, group: ProcessGroup = None, config=None,
                     op=None):
    """In-graph allreduce for shard_map interiors. With ``group`` (and
    config) the selection table picks the lowering — on a two-tier world
    that is the hierarchical decomposition — executed to completion through
    ``inline_plan``; with only ``axis`` the lax baseline applies."""
    from jax import lax as _lax

    from mlsl_tpu.types import ReductionType

    rop = ReductionType(op) if op is not None else ReductionType.SUM
    if group is not None and not group.is_self and int(group.size) > 1:
        count = int(np.prod(x.shape))
        algo = select("allreduce", group, count * 4, CompressionType.NONE,
                      config, op=rop)
        if algo != DEFAULT and inline_eligible(algo, "allreduce", group, rop):
            from mlsl_tpu.comm import collectives

            sizes = collectives._axis_sizes(group.topology.mesh)
            prep, phases, finish = inline_plan(
                "allreduce", group, algo, count, op=rop, config=config,
            )
            carry = prep(x.reshape(-1),
                         collectives._group_rank(group.axes, sizes))
            for phase in phases:
                carry = phase(carry)
            return finish(carry).reshape(x.shape)
        axis = group.axes
    if rop == ReductionType.SUM:
        return _lax.psum(x, axis)
    if rop == ReductionType.MIN:
        return _lax.pmin(x, axis)
    return _lax.pmax(x, axis)


def inline_alltoall(x, axis, *, split_axis=0, concat_axis=0, tiled=False,
                    group: ProcessGroup = None, config=None):
    """In-graph alltoall (the MoE expert dispatch/combine exchange). With
    ``group`` (and config) the selection table picks the lowering — a forced
    ``MLSL_ALGO=alltoall=pallas_a2a`` or a tuned cell routes the exchange
    through the fused quantized kernel; with only ``axis`` (or a selected
    kernel the backend cannot emit in-graph) the lax baseline applies, with
    a debug log naming the fallback so an operator forcing the kernel
    off-TPU sees WHY the wire stayed f32.

    The kernel path applies to the MoE layout specifically: leading dim ==
    group size, ``split_axis == concat_axis == 0``, untiled — exactly the
    flat chunks-by-member convention ops/a2a_kernels.py exchanges."""
    from jax import lax as _lax

    if (
        group is not None and not group.is_self and int(group.size) > 1
        and split_axis == 0 and concat_axis == 0 and not tiled
        and int(x.shape[0]) == int(group.size)
        and x.dtype == np.float32  # the fused kernel's codec/scratch are f32
    ):
        count = int(np.prod(x.shape))
        algo = select("alltoall", group, count * 4, CompressionType.NONE,
                      config)
        if algo != DEFAULT:
            if inline_eligible(algo, "alltoall", group):
                from mlsl_tpu.comm import collectives

                sizes = collectives._axis_sizes(group.topology.mesh)
                prep, phases, finish = inline_plan(
                    "alltoall", group, algo, count, config=config,
                )
                carry = prep(x.reshape(-1),
                             collectives._group_rank(group.axes, sizes))
                for phase in phases:
                    carry = phase(carry)
                return finish(carry).reshape(x.shape)
            log_debug(
                "alltoall algorithm %s selected but not emittable in-graph "
                "on this backend/group; falling back to the lax exchange",
                algo,
            )
    return _lax.all_to_all(x, axis, split_axis=split_axis,
                           concat_axis=concat_axis, tiled=tiled)


def inline_allgather(x, axis, *, gather_axis=0, tiled=True):
    """In-graph all-gather (the MoE output reassembly)."""
    from jax import lax as _lax

    return _lax.all_gather(x, axis, axis=gather_axis, tiled=tiled)
