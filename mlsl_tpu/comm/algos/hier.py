"""Two-tier hierarchical collectives: DCN-aware compressed allreduce.

Fifth entry in the selection table (after ``lax``/``rhd``/``ring2d``/
``pallas_ring``). Everything before this lowering assumes one flat/2D ICI
mesh; production scale means pods — fast ICI slices bridged by a slow DCN
tier. The DynamiQ multi-hop shape (PAPERS.md) wins there:

  allreduce(n) over G = T slices x L devices/slice:
    1. intra-slice reduce-scatter (f32, ICI)   -> shard of n/L
    2. inter-slice allreduce over the shard    -> only n/L crosses the DCN,
       per-tier codec applies HERE (int8-blockwise / top-k / f32)
    3. intra-slice all-gather (f32, ICI)       -> full n

The compressed DCN hop is THC-shaped: every slice quantizes its shard
against a SHARED per-block scale (one tiny pmax across slices), the slices
exchange int8 payloads summed in int32 — exact integer arithmetic, no
dequantize/requantize round-trip per hop — and ONE dequantize lands the
result. Round-to-nearest-even entry rounding keeps the per-element
quantization error zero-mean (the bias-corrected integer-sum contract);
what error remains is carried by the same client-side error-feedback
residual the flat quant ring uses, so CommRequest's snapshot/rewind and the
supervisor's degrade-to-f32 flush apply unchanged (the residual inverts to
the logical layout through ``flush_residual`` — each member owns its own
slice's error).

Tier structure derives from ``mesh.world_tier_ids`` (real ``slice_index``
on TPU multislice; the ``MLSL_MESH_TIERS=TxL`` synthetic override lets the
8-dev CPU proof mesh and tier-1 exercise a two-tier split). Groups are
eligible when their members split into T contiguous equal runs of L in
group-rank order — exactly what ``mesh.dcn_aware_devices`` ordering
produces for the data/replica axes.

Like rhd/ring2d, the schedule is exposed as staged ``steps`` shared by the
standalone ``build`` program and the compiled overlap engine: the ICI
phases emit early and the compressed DCN phase is the natural stage
boundary between layers.
"""

from __future__ import annotations

import os
from typing import Callable, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from mlsl_tpu.comm.mesh import ProcessGroup, world_tier_ids
from mlsl_tpu.comm.collectives import _axis_sizes
from mlsl_tpu.log import mlsl_assert

#: DCN-tier codecs (the ICI tier is always f32 — its phases are exact).
#: int8/f32/topk keep their hand-written bit-exact hops; the rest route
#: through the registry's ``Codec.hier_aggregate`` (mlsl_tpu.codecs).
DCN_CODECS = ("int8", "f32", "topk", "vq", "prune")
DEFAULT_DCN_CODEC = "int8"


def dcn_codec(value: Optional[str] = None) -> str:
    """The DCN-tier codec: explicit value > MLSL_HIER_DCN_CODEC > int8."""
    v = (value if value is not None
         else os.environ.get("MLSL_HIER_DCN_CODEC", "")).strip().lower()
    if not v:
        return DEFAULT_DCN_CODEC
    mlsl_assert(v in DCN_CODECS,
                "MLSL_HIER_DCN_CODEC must be one of %s (got %r)",
                "/".join(DCN_CODECS), v)
    return v


# ---------------------------------------------------------------------------
# Tier structure
# ---------------------------------------------------------------------------


def _live_axis(group: ProcessGroup) -> Optional[str]:
    if group.colors is not None or group.is_self:
        return None
    sizes = _axis_sizes(group.topology.mesh)
    live = [a for a in group.axes if sizes[a] > 1]
    return live[0] if len(live) == 1 else None


def tier_structure(group: ProcessGroup) -> Optional[Tuple[int, int]]:
    """(T, L) when the group's members split into T contiguous equal tiers
    of L members (in group-rank order) under the world tier map, identically
    for every group instance — else None (the flat lowerings apply).

    T==1 (one tier holds the whole group — the degenerate 1xG split) and
    L==1 (every member its own tier, Gx1) are both valid shapes: the
    corresponding ICI/DCN phase simply vanishes."""
    if _live_axis(group) is None or int(group.size) <= 1:
        return None
    tids = world_tier_ids(tuple(group.topology.mesh.devices.flat))
    if tids is None:
        return None
    from mlsl_tpu.comm.collectives import _axis_groups_tbl

    g = int(group.size)
    shape = None
    for row in _axis_groups_tbl(group):
        runs: List[Tuple[int, int]] = []  # (tier id, run length)
        for w in row:
            t = tids[w]
            if runs and runs[-1][0] == t:
                runs[-1] = (t, runs[-1][1] + 1)
            else:
                runs.append((t, 1))
        if len({t for t, _ in runs}) != len(runs):
            return None  # a tier appears in two runs: interleaved layout
        lens = {n for _, n in runs}
        if len(lens) != 1:
            return None
        cur = (len(runs), runs[0][1])
        if shape is None:
            shape = cur
        elif shape != cur:
            return None  # instances see different splits
    if shape is None or shape[0] * shape[1] != g:
        return None
    return shape


def _tier_groups(g: int, t: int, l: int) -> Tuple[list, list]:
    """(intra groups, inter groups) as axis_index_groups over the live axis:
    intra = the L members of each tier (contiguous), inter = the T tier
    peers sharing a local rank."""
    intra = [[ti * l + li for li in range(l)] for ti in range(t)]
    inter = [[ti * l + li for ti in range(t)] for li in range(l)]
    return intra, inter


def _inter_sum(x, axis: str, inter, t: int):
    """Sum over the T tier peers: all_gather + a LOCAL axis-0 sum (this
    jax's shard_map psum does not take axis_index_groups; the gather form is
    exact for the int32 codec payload, and its fixed local summation order
    makes every member's float result bit-identical). T is the pod count —
    small — so the (T-1)x inbound gather traffic stays modest."""
    if t <= 1:
        return x
    g = lax.all_gather(x, axis, axis=0, axis_index_groups=inter)
    return jnp.sum(g, axis=0)


# ---------------------------------------------------------------------------
# Eligibility
# ---------------------------------------------------------------------------


def eligible(kind: str, group: ProcessGroup, op=None) -> bool:
    """Dense eligibility: SUM over a single-live-axis group with a uniform
    two-tier split (the scatter phases are psum_scatter, SUM-only like
    ring2d)."""
    from mlsl_tpu.types import ReductionType

    if op not in (None, ReductionType.SUM):
        return False
    return tier_structure(group) is not None


def eligible_quant(group: ProcessGroup, block: int) -> bool:
    """Compressed eligibility (the QUANTIZATION route through the table):
    allreduce only — the tentpole shape is RS -> compressed AR -> AG; a
    quantized reduce_scatter keeps the flat quant-ring wire."""
    del block  # geometry pads internally; any block size serves
    return tier_structure(group) is not None


# ---------------------------------------------------------------------------
# Dense lowering (f32 both tiers)
# ---------------------------------------------------------------------------


def steps(
    kind: str,
    group: ProcessGroup,
    n: int,
    *,
    op=None,
    recv_count=None,
) -> Tuple[Callable, List[Callable], Callable]:
    """The staged two-tier schedule: ``(prep, phases, finish)``, rhd/ring2d
    carry convention ((x, mypos) rides through; mypos unused — placement is
    axis-index-native). One collective per phase: intra-RS, inter-AR,
    intra-AG, with degenerate tiers (T==1 or L==1) dropping their phases."""
    axis = _live_axis(group)
    tiers = tier_structure(group)
    mlsl_assert(
        axis is not None and tiers is not None,
        "hier needs a single-live-axis group with a uniform tier split "
        "(MLSL_MESH_TIERS or multislice topology); got axes=%s", group.axes,
    )
    t, l = tiers
    g = t * l
    intra, inter = _tier_groups(g, t, l)

    if kind == "reduce_scatter":
        mlsl_assert(
            recv_count is not None and n == g * recv_count,
            "hier reduce_scatter needs count == G*recv_count "
            "(count %d, G %d, recv_count %s)", n, g, recv_count,
        )
        rc = recv_count

        def prep_rs(x, mypos):
            # l-major chunk order so intra-scatter-by-l then inter-scatter-
            # by-t lands group chunk t*L+l on member (t, l) — its own group
            # rank (a local relabeling, no wire)
            xr = jnp.transpose(
                x.reshape(t, l, rc), (1, 0, 2)
            ).reshape(-1)
            return (xr, mypos)

        def rs_intra(carry):
            cur, mypos = carry
            return lax.psum_scatter(
                cur, axis, scatter_dimension=0, tiled=True,
                axis_index_groups=intra,
            ), mypos

        def rs_inter(carry):
            cur, mypos = carry
            return lax.psum_scatter(
                cur, axis, scatter_dimension=0, tiled=True,
                axis_index_groups=inter,
            ), mypos

        phases = ([rs_intra] if l > 1 else []) + ([rs_inter] if t > 1 else [])
        if not phases:
            return prep_rs, [], lambda carry: carry[0][:rc]
        return prep_rs, phases, lambda carry: carry[0]

    sc = -(-n // l)
    m = sc * l

    def prep(x, mypos):
        xp = jnp.pad(x, (0, m - n)) if m != n else x
        return (xp, mypos)

    def rs_intra(carry):
        cur, mypos = carry
        return lax.psum_scatter(
            cur, axis, scatter_dimension=0, tiled=True,
            axis_index_groups=intra,
        ), mypos

    def ar_inter(carry):
        cur, mypos = carry
        return _inter_sum(cur, axis, inter, t), mypos

    def ag_intra(carry):
        cur, mypos = carry
        return lax.all_gather(
            cur, axis, axis=0, tiled=True, axis_index_groups=intra,
        ), mypos

    phases = ([rs_intra] if l > 1 else []) \
        + ([ar_inter] if t > 1 else []) \
        + ([ag_intra] if l > 1 else [])
    return prep, phases, lambda carry: carry[0][:n]


def build(kind: str, group: ProcessGroup, *, op=None, recv_count=None,
          **_) -> Callable:
    from mlsl_tpu.comm import collectives

    mesh = group.topology.mesh

    def body(x):
        prep, phases, finish = steps(
            kind, group, x.shape[0], op=op, recv_count=recv_count
        )
        carry = prep(x, jnp.int32(0))
        for phase in phases:
            carry = phase(carry)
        return finish(carry)

    return collectives._build_axis(body, mesh, kind, "hier")


# ---------------------------------------------------------------------------
# Compressed DCN tier (the QUANTIZATION wire through the table)
# ---------------------------------------------------------------------------


def quant_geometry(
    kind: str, group: ProcessGroup, count: int, block: int
) -> Tuple[int, int, int, Tuple[int, int]]:
    """-> (g, slen, err_len, (T, L)): the hierarchical compressed layout.

    ``slen`` is the per-member DCN shard: ceil(count/L) aligned UP to the
    quant block, so no compressed-tier block ever straddles the intra-slice
    shard boundary (the A114 invariant) and the shared-scale blocks tile the
    shard exactly. The error-feedback residual covers exactly the member's
    own shard (err_len == slen): unlike the flat ring — whose residual spans
    the whole logical buffer in ring-chunk layout — each member only ever
    quantizes its 1/L slice, and the degrade flush re-places that slice at
    its logical offset (``flush_residual``)."""
    mlsl_assert(kind == "allreduce",
                "hier compressed wire serves allreduce only (got %s)", kind)
    tiers = tier_structure(group)
    mlsl_assert(tiers is not None,
                "hier quant geometry needs a tiered group")
    t, l = tiers
    slen = -(-(-(-count // l)) // block) * block
    return t * l, slen, slen, (t, l)


def intra_positions(group: ProcessGroup) -> np.ndarray:
    """(R, D, S, M) int array: each world position's intra-tier rank l — the
    static table the degrade flush uses to re-place a member's residual at
    its own logical slice offset."""
    tiers = tier_structure(group)
    mlsl_assert(tiers is not None, "intra_positions needs a tiered group")
    _, l = tiers
    topo = group.topology
    out = np.zeros(topo.grid_shape, dtype=np.int32)
    w = topo.world_size
    for p in range(w):
        out[topo.coords(p)] = group.group_idx_of(p) % l
    return out


def flush_residual(err, l_idx, L: int, slen: int, count: int):
    """Hier-layout error-feedback residual -> the logical buffer layout.

    ``err``: (*lead, slen) — each member's residual for ITS OWN slice.
    ``l_idx``: (*lead) static intra-tier ranks (``intra_positions``). The
    plain-allreduce degrade flush sums every member's flushed payload, so
    placing each residual at offset l*slen delivers slice l's un-sent error
    exactly once (summed over that slice's tier peers — the same total the
    healthy compressed hop still owed). Padding-region residual beyond
    ``count`` is discarded, like the healthy path truncates its result."""
    lead = err.shape[:-1]
    onehot = jax.nn.one_hot(l_idx, L, dtype=err.dtype)      # (*lead, L)
    placed = onehot[..., :, None] * err[..., None, :]       # (*lead, L, slen)
    return placed.reshape(*lead, L * slen)[..., :count]


def _block_quant_shared(xq, block: int, axis: str, inter, t: int):
    """Shared-scale blockwise int8 for the DCN hop: per-block absmax pmax'd
    across the tier peers (the only extra DCN traffic — one f32 per block),
    quantize-once against the SHARED scale (round-to-nearest-even, the
    zero-mean entry rounding), then exchange the int8 payload — the wire
    stays 1 byte/elem — widening to int32 only in the LOCAL sum: the THC
    shape, the slow tier never dequantizes per hop. -> (red, new_err)."""
    blocks = xq.reshape(-1, block)
    m = jnp.max(jnp.abs(blocks), axis=1)
    if t > 1:
        m = lax.pmax(m, axis, axis_index_groups=inter)
    scale = jnp.where(m == 0, 1.0, m / 127.0).astype(jnp.float32)
    q8 = jnp.clip(
        jnp.round(blocks / scale[:, None]), -127, 127
    ).astype(jnp.int8)
    xhat = (q8.astype(jnp.float32) * scale[:, None]).reshape(-1)
    new_err = xq - xhat
    if t > 1:
        gathered = lax.all_gather(q8, axis, axis=0, axis_index_groups=inter)
        q = jnp.sum(gathered.astype(jnp.int32), axis=0)
    else:
        q = q8.astype(jnp.int32)
    red = (q.astype(jnp.float32) * scale[:, None]).reshape(-1)
    return red, new_err


def _topk_shared(xq, ratio: float, axis: str, inter, t: int):
    """Top-k DCN codec: keep the k largest-|.| shard elements, the rest feed
    the residual; the kept (sparse) payload sums across tiers."""
    k = max(1, int(xq.shape[0] * ratio))
    vals = lax.top_k(jnp.abs(xq), k)[0]
    thr = vals[k - 1]
    keep = jnp.where(jnp.abs(xq) >= thr, xq, 0.0)
    new_err = xq - keep
    return _inter_sum(keep, axis, inter, t), new_err


def quant_steps(
    group: ProcessGroup,
    count: int,
    block: int,
    *,
    codec: Optional[str] = None,
    topk_ratio: float = 0.01,
) -> Tuple[Callable, List[Callable], Callable, int]:
    """Staged compressed-allreduce schedule for the overlap engine:
    ``(prep(x, mypos, err) -> carry, phases, finish(carry) -> (out,
    new_err), err_len)``. Phase boundaries mirror the dense ``steps``: the
    ICI reduce-scatter emits early, the compressed DCN exchange is its own
    phase (the natural stage boundary), the ICI all-gather last."""
    axis = _live_axis(group)
    g, slen, err_len, (t, l) = quant_geometry("allreduce", group, count,
                                              block)
    intra, inter = _tier_groups(g, t, l)
    codec = dcn_codec(codec)
    if t == 1:
        codec = "f32"  # nothing crosses the DCN; never quantize on ICI
    reg = None
    if codec not in ("int8", "topk", "f32"):
        # registry-routed DCN codec: resolve the instance once, outside the
        # traced phases; knobs come from the process env (MLSL_VQ_*,
        # MLSL_PRUNE_RATIO) since quant_steps has no session Config in hand.
        from mlsl_tpu import codecs as codecs_mod
        from mlsl_tpu.config import Config
        reg = codecs_mod.configure(codec, Config.from_env())

    def prep(x, mypos, err):
        xp = x.astype(jnp.float32)
        pad = l * slen - count
        if pad:
            xp = jnp.pad(xp, (0, pad))
        del mypos
        return (xp, err)

    def rs_intra(carry):
        cur, err = carry
        if l == 1:
            return carry
        return lax.psum_scatter(
            cur, axis, scatter_dimension=0, tiled=True,
            axis_index_groups=intra,
        ), err

    def dcn_hop(carry):
        cur, err = carry
        xq = cur + err
        if codec == "int8":
            red, new_err = _block_quant_shared(xq, block, axis, inter, t)
        elif codec == "topk":
            red, new_err = _topk_shared(xq, topk_ratio, axis, inter, t)
        elif reg is not None:  # registry codec: wire exchange + aggregate
            red, new_err = reg.hier_aggregate(xq, axis=axis, inter=inter,
                                              t=t)
        else:  # f32: exact hop, residual fully delivered and reset
            red = _inter_sum(xq, axis, inter, t)
            new_err = jnp.zeros_like(xq)
        return red, new_err

    def ag_intra(carry):
        cur, err = carry
        if l == 1:
            return carry
        return lax.all_gather(
            cur, axis, axis=0, tiled=True, axis_index_groups=intra,
        ), err

    phases = ([rs_intra] if l > 1 else []) + [dcn_hop] \
        + ([ag_intra] if l > 1 else [])
    return prep, phases, lambda carry: (carry[0][:count], carry[1]), err_len


def quant_body(
    kind: str,
    group: ProcessGroup,
    count: int,
    block: int,
    *,
    codec: Optional[str] = None,
    topk_ratio: float = 0.01,
) -> Tuple[Callable, int]:
    """The compressed round as an un-compiled ``(x, err) -> (result,
    new_err)`` shard_map body — quant_ring.inline_body's contract, so
    ``build_quantized_collective(ring='hier')`` compiles it through the same
    ``build_stateful_collective`` scaffolding (and the same chaos roundtrip
    wrapper) as the flat ring."""
    prep, phases, finish, err_len = quant_steps(
        group, count, block, codec=codec, topk_ratio=topk_ratio
    )
    mlsl_assert(kind == "allreduce",
                "hier compressed wire serves allreduce only (got %s)", kind)

    def body(x, err):
        carry = prep(x, jnp.int32(0), err)
        for phase in phases:
            carry = phase(carry)
        return finish(carry)

    return body, err_len


# ---------------------------------------------------------------------------
# Cost model: bytes and round trips one hier allreduce puts on a DCN link
# (no caller since PR 32 removed the CPU-mesh simulator; ROADMAP D3)
# ---------------------------------------------------------------------------


def dcn_wire_bytes(count: int, tiers: Tuple[int, int], codec: str,
                   block: int) -> int:
    """Bytes one member's DCN link carries for a hier allreduce of ``count``
    f32 elems: the 1/L shard at the codec's wire width, ring-modeled across
    the T tier peers (2(T-1)/T), plus the shared-scale exchange for int8."""
    t, l = tiers
    if t <= 1:
        return 0
    slen = -(-(-(-count // l)) // block) * block
    if codec == "int8":
        per = slen * 1 + 4 * (slen // block)  # q + the shared-scale pmax
    elif codec == "topk":
        per = slen * 4  # dense psum carries the masked shard (sim mesh)
    elif codec not in ("f32", "none"):  # "none" = an uncompressed DCN tier
        from mlsl_tpu import codecs as codecs_mod
        per = codecs_mod.configure(codec).wire_len(slen)  # encoded shard
    else:
        per = slen * 4
    return int(2 * (t - 1) / t * per)


def dcn_phases(tiers: Tuple[int, int], codec: str) -> int:
    """DCN round-trips (latency terms) for one hier allreduce: the shared-
    scale pmax (int8 only) plus the 2(T-1) exchange hops of a ring-modeled
    allreduce across tiers."""
    t, _ = tiers
    if t <= 1:
        return 0
    return 2 * (t - 1) + (1 if codec == "int8" else 0)
