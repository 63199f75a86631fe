"""Wire codecs + sharded zero-staging placement + jitted on-device decode.

The trainer is input-bound whenever the host->device link is slow relative to
the step: shipping full-width f32 batches wastes the one resource that
matters. The same principle the gradient path already exploits
(quantize before the wire, decode where FLOPs are cheap — comm/quant_ring,
THC in PAPERS.md) applies to the feed: batches cross the link in a compact
*wire dtype* and a jitted on-device decode restores the training dtype.

Wire kinds per leaf (``MLSL_FEED_WIRE_DTYPE``, parsed by
:func:`parse_wire_spec`):

- ``none``/``f32`` — ship unchanged (the baseline path).
- ``bf16``        — host cast, device cast back: 2x for f32 leaves.
- ``uint8``       — images. A uint8 source leaf ships raw (4x vs f32); a f32
  leaf ships affine-quantized with a per-shard (offset, scale) pair riding
  alongside (decode contract ``(q + off) * scale`` — FMA-proof, see
  ``_encode_uint8``). Decode = cast + affine + optional (mean, std)
  normalize, bit-exact against the same host-side f32 math.
- ``int8``        — generic tensors via the SAME blockwise int8 codec the
  quantized collectives use (ops/quant_kernels: max|x|/127 per block,
  per-block f32 scales; the device decode IS quant_kernels.dequantize, so
  feeds share the quant kernels and their block/scale conventions).

Placement is *sharded zero-staging*: every (replica, data) shard slice of the
host batch is encoded independently and goes up via
``jax.make_array_from_single_device_arrays`` — no (R, D, S, M, ...)
full-replica staging array is ever materialized on the host, and the decode
program DONATES the wire buffers so the compact staging HBM is reclaimed the
moment the f32 batch exists. Per-shard encoding also keeps the int8 block
geometry local: a quant block never straddles two devices' examples.

Non-float leaves (labels) always ride unchanged: a wire kind that cannot
represent a leaf losslessly-or-by-contract falls back to ``none`` for that
leaf rather than corrupting it.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from mlsl_tpu.comm.mesh import GRID_AXES, NUM_GRID_AXES
from mlsl_tpu.log import MLSLError, mlsl_assert
from mlsl_tpu.obs import tracer as obs_trace
from mlsl_tpu.ops import quant_kernels

# the wire-spec grammar lives in data/common.py (dependency-free, so
# Config.validate can parse it without importing the kernel stack)
from mlsl_tpu.data.common import WIRE_KINDS, parse_wire_spec  # noqa: F401


def _path_key(path) -> str:
    """Flattened-tree path -> stable leaf name ('0', '1', 'img.raw', ...)."""
    parts = []
    for e in path:
        if hasattr(e, "idx"):
            parts.append(str(e.idx))
        elif hasattr(e, "key"):
            parts.append(str(e.key))
        elif hasattr(e, "name"):
            parts.append(str(e.name))
        else:  # pragma: no cover - future jax key types
            parts.append(str(e))
    return ".".join(parts)


def _effective_kind(kind: str, arr: np.ndarray) -> str:
    """Clamp a requested kind to what the leaf can carry. Integer labels and
    other non-float leaves always ride unchanged; uint8 additionally accepts
    native uint8 leaves (raw image bytes)."""
    if kind == "none":
        return "none"
    if kind == "uint8":
        if arr.dtype == np.uint8 or np.issubdtype(arr.dtype, np.floating):
            return "uint8"
        return "none"
    if np.issubdtype(arr.dtype, np.floating):
        return kind
    return "none"


# -- host-side encoders (numpy; run on the loader's worker thread) -----------


#: |off| bound for the affine uint8 codec: above this, float32 ulp(off)
#: exceeds 0.25 quant units and ``q + off`` starts eating the 8 payload
#: bits — the leaf would decode toward a constant, silently. Loud > wrong.
_UINT8_OFF_LIMIT = float(2 ** 22)


def _encode_uint8(sl: np.ndarray, key: str = "?"):
    """Affine uint8: decode contract is ``(q + off) * scale`` — an add
    FEEDING a multiply, deliberately: a ``q * scale + lo`` form is an FMA
    pattern that XLA fuses (through optimization_barrier, on CPU at least)
    into a single-rounding fma, breaking bit-exact parity with the two-
    rounding host reference. Add-then-multiply has no fused form, so every
    backend rounds each op exactly once.

    The formulation carries the DC offset in quant units (off = lo/scale),
    which float32 can only do faithfully while |off| stays small; a leaf
    whose offset dwarfs its spread (|lo| >> hi - lo) fails LOUDLY here
    instead of silently collapsing to a constant on decode — route such
    leaves to ``bf16``/``none`` via a per-leaf override."""
    if sl.dtype == np.uint8:
        return np.ascontiguousarray(sl), None
    f = sl.astype(np.float32)
    lo = np.float32(f.min()) if f.size else np.float32(0.0)
    hi = np.float32(f.max()) if f.size else np.float32(0.0)
    scale = np.float32((hi - lo) / np.float32(255.0))
    if scale == 0.0:
        scale = np.float32(1.0)
    off = np.float32(lo / scale)
    if abs(float(off)) > _UINT8_OFF_LIMIT:
        raise MLSLError(
            f"feed leaf {key!r}: uint8 affine wire cannot carry this data — "
            f"DC offset / spread ratio too large (lo={float(lo):g}, "
            f"scale={float(scale):g}, off=lo/scale={float(off):g} exceeds "
            f"{_UINT8_OFF_LIMIT:g}); float32 would drop quantization bits "
            f"and decode toward a constant. Use a per-leaf override "
            f"(MLSL_FEED_WIRE_DTYPE='...,{key}=bf16' or '...,{key}=none') "
            f"for this leaf."
        )
    q = np.clip(np.rint(f / scale - off), 0, 255).astype(np.uint8)
    return q, np.array([off, scale], np.float32)


def _encode_int8(sl: np.ndarray, block: int):
    """Blockwise int8: the numpy mirror of quant_kernels.quantize_blocks_ref
    (same max|x|/127 scale, same round-half-even), padded to the kernels'
    block*ROW_TILE unit so the Pallas dequant path is always tile-legal."""
    f = sl.reshape(-1).astype(np.float32)
    n = f.size
    unit = block * quant_kernels.ROW_TILE
    npad = -(-max(n, 1) // unit) * unit
    buf = np.zeros(npad, np.float32)
    buf[:n] = f
    x2d = buf.reshape(-1, block)
    amax = np.abs(x2d).max(axis=1)
    scale = np.where(amax == 0.0, 1.0, amax / 127.0).astype(np.float32)
    q = np.clip(np.rint(x2d / scale[:, None]), -127, 127).astype(np.int8)
    return q.reshape(-1), scale


def _encode_slice(kind: str, sl: np.ndarray, block: int, key: str = "?"):
    """-> (payload np array, meta np array or None) for one shard slice."""
    if kind == "none":
        return np.ascontiguousarray(sl), None
    if kind == "bf16":
        import ml_dtypes

        return np.ascontiguousarray(sl.astype(ml_dtypes.bfloat16)), None
    if kind == "uint8":
        return _encode_uint8(sl, key)
    return _encode_int8(sl, block)


@dataclasses.dataclass(frozen=True)
class _Leaf:
    """Static per-leaf layout, fixed after the first staged batch."""

    key: str
    kind: str
    local_shape: Tuple[int, ...]  # decoded per-shard shape (localB, *payload)
    dtype: np.dtype               # source dtype (decode target for 'none')
    n: int                        # flattened elements per shard (int8)
    has_meta: bool
    payload_ndim: int             # wire payload rank (sans grid dims)


class FeedCodec:
    """Wire encode + zero-staging placement + jitted decode for one batch
    structure (shapes fixed across batches, like the rest of the Session
    graph). ``normalize=(mean, std)`` is applied to uint8-decoded leaves
    (image pipelines); ``augment`` is an optional traced transform applied to
    the decoded batch inside the decode program."""

    def __init__(self, topology, wire: Optional[str] = None, *,
                 normalize: Optional[Tuple] = None,
                 train_dtype=jnp.float32,
                 augment: Optional[Callable] = None,
                 quant_block: int = 256):
        self.topo = topology
        self.default, self.overrides = parse_wire_spec(wire)
        self.normalize = None
        if normalize is not None:
            # mean + HOST-computed reciprocal of std: the device applies
            # (x - mean) * inv_std. A device-side division would let XLA
            # rewrite it as multiply-by-reciprocal with its own rounding —
            # the decode-parity contract (bit-exact vs the same host f32
            # math) requires one canonical formulation on both sides.
            self.normalize = (
                np.asarray(normalize[0], np.float32),
                np.float32(1.0) / np.asarray(normalize[1], np.float32),
            )
        self.train_dtype = train_dtype
        self.augment = augment
        self.block = int(quant_block)
        self._layout: Optional[List[_Leaf]] = None
        self._treedef = None
        self._decode_jit: Dict[bool, Callable] = {}
        self._batches = 0

    # -- encode + placement -------------------------------------------------

    def leaf_kind(self, key: str, arr: np.ndarray) -> str:
        kind = self.overrides.get(key)
        if kind is None and key in ("0", "1"):
            # x/y alias the canonical batch tuple's positional leaves; an
            # exact key match (e.g. a dict leaf literally named 'x') wins
            alias = "x" if key == "0" else "y"
            kind = self.overrides.get(alias)
        if kind is None:
            kind = self.default
        return _effective_kind(kind, arr)

    def stage(self, host_batch, corrupt: bool = False):
        """Host batch -> wire-format device batch.

        Each (replica, data) shard slice is encoded independently and placed
        via ``jax.make_array_from_single_device_arrays`` — zero-staging: no
        full-replica host array, one compact h2d transfer per device.
        Returns ``(wire_batch, wire_bytes, full_bytes)`` where ``full_bytes``
        is what the uncompressed f32 path would have shipped. ``corrupt``
        flips bytes in the first payload block (the chaos ``bitrot`` kind —
        a bad host read must flow through decode/cache, not crash them)."""
        t0 = time.perf_counter_ns() if obs_trace._tracer is not None else 0
        leaves, treedef = jax.tree_util.tree_flatten_with_path(host_batch)
        if self._layout is None:
            self._treedef = treedef
            self._layout = self._build_layout(leaves)
        else:
            mlsl_assert(
                treedef == self._treedef,
                "feed batch structure changed mid-stream (got %s, staged %s)",
                treedef, self._treedef,
            )
        topo = self.topo
        r_, d_, s_, m_ = topo.grid_shape
        mesh_devs = topo.mesh.devices
        wire_leaves = []
        wire_bytes = full_bytes = 0
        for leaf, (_, arr) in zip(self._layout, leaves):
            arr = np.asarray(arr)
            b = arr.shape[0]
            local_b = b // (r_ * d_)
            mlsl_assert(
                local_b * r_ * d_ == b,
                "batch size %d must divide over %d data ranks", b, r_ * d_,
            )
            mlsl_assert(
                (local_b, *arr.shape[1:]) == leaf.local_shape,
                "feed leaf %s shape changed mid-stream (got %s, staged %s)",
                leaf.key, (local_b, *arr.shape[1:]), leaf.local_shape,
            )
            f32_nbytes = (
                arr[: local_b].size * 4
                if np.issubdtype(arr.dtype, np.floating)
                else arr[: local_b].nbytes
            )
            q_parts, s_parts = [], []
            for r in range(r_):
                for d in range(d_):
                    i = r * d_ + d
                    sl = arr[i * local_b : (i + 1) * local_b]
                    q, meta = _encode_slice(leaf.kind, sl, self.block,
                                            leaf.key)
                    if corrupt:
                        q = q.copy()
                        flat = q.view(np.uint8).reshape(-1)
                        flat[: min(64, flat.size)] ^= 0xFF
                        corrupt = False  # one rotted block per batch
                    q_parts.append(q)
                    s_parts.append(meta)
            wire_leaf = {
                "q": self._place(q_parts, mesh_devs),
            }
            per_dev = s_ * m_
            wire_bytes += sum(q.nbytes for q in q_parts) * per_dev
            full_bytes += f32_nbytes * r_ * d_ * per_dev
            if leaf.has_meta:
                wire_leaf["s"] = self._place(s_parts, mesh_devs)
                wire_bytes += sum(s.nbytes for s in s_parts) * per_dev
            wire_leaves.append(wire_leaf)
        self._batches += 1
        from mlsl_tpu.core import stats

        stats.record_feed_stage(wire_bytes, full_bytes)
        tr = obs_trace._tracer
        if tr is not None:
            tr.complete("h2d.transfer", "feed", t0, batch=self._batches,
                        wire_bytes=wire_bytes, saved=full_bytes - wire_bytes)
        return tuple(wire_leaves), wire_bytes, full_bytes

    def _place(self, blocks, mesh_devs) -> jax.Array:
        """Per-(r, d) host blocks -> one sharded array, one compact transfer
        per device (broadcast over the seq/model axes like shard_batch)."""
        r_, d_, s_, m_ = self.topo.grid_shape
        payload = blocks[0].shape
        grid1 = (1,) * NUM_GRID_AXES
        global_shape = (r_, d_, s_, m_, *payload)
        sharding = self.topo.buffer_sharding(len(payload))
        arrays = []
        for r in range(r_):
            for d in range(d_):
                block = blocks[r * d_ + d].reshape(grid1 + payload)
                for s in range(s_):
                    for m in range(m_):
                        arrays.append(
                            jax.device_put(block, mesh_devs[r, d, s, m])
                        )
        return jax.make_array_from_single_device_arrays(
            global_shape, sharding, arrays
        )

    def _build_layout(self, leaves) -> List[_Leaf]:
        r_, d_ = self.topo.grid_shape[:2]
        layout = []
        for path, arr in leaves:
            arr = np.asarray(arr)
            key = _path_key(path)
            kind = self.leaf_kind(key, arr)
            local_b = arr.shape[0] // (r_ * d_)
            local_shape = (local_b, *arr.shape[1:])
            n = int(np.prod(local_shape))
            if kind == "int8":
                payload_ndim, has_meta = 1, True
            elif kind == "uint8":
                payload_ndim = len(local_shape)
                has_meta = arr.dtype != np.uint8
            else:
                payload_ndim, has_meta = len(local_shape), False
            layout.append(_Leaf(key, kind, local_shape, arr.dtype, n,
                                has_meta, payload_ndim))
        return layout

    # -- on-device decode ---------------------------------------------------

    def decode(self, wire_batch, donate: bool = False):
        """Wire batch -> decoded distributed-buffer batch (the same layout
        ``DataParallelTrainer.shard_batch`` produces). ``donate=True`` hands
        the wire buffers to XLA (fresh-staged batches: the compact staging
        HBM is reclaimed immediately); cached batches must decode with
        ``donate=False`` so the cache entry survives."""
        fn = self._decode_jit.get(donate)
        if fn is None:
            fn = self._build_decode(donate)
            self._decode_jit[donate] = fn
        tr = obs_trace._tracer
        t0 = tr.now() if tr is not None else 0
        out = fn(wire_batch)
        if tr is not None:
            tr.complete("feed.decode", "feed", t0, donated=donate)
        return out

    def _build_decode(self, donate: bool):
        from mlsl_tpu.comm.collectives import smap

        layout, treedef = self._layout, self._treedef
        mlsl_assert(layout is not None, "decode before any staged batch")
        mesh = self.topo.mesh
        block, train_dtype = self.block, self.train_dtype
        normalize, augment = self.normalize, self.augment
        grid1 = (None,) * NUM_GRID_AXES

        in_specs = tuple(
            {
                "q": P(*GRID_AXES, *([None] * leaf.payload_ndim)),
                **({"s": P(*GRID_AXES, None)} if leaf.has_meta else {}),
            }
            for leaf in layout
        )
        out_specs = tuple(
            P(*GRID_AXES, *([None] * len(leaf.local_shape)))
            for leaf in layout
        )

        def body(wire):
            out = []
            for leaf, w in zip(layout, wire):
                q = w["q"]
                q = q.reshape(q.shape[NUM_GRID_AXES:])
                if leaf.kind == "none":
                    x = q
                elif leaf.kind == "bf16":
                    x = q.astype(train_dtype)
                elif leaf.kind == "uint8":
                    x = q.astype(jnp.float32)
                    if leaf.has_meta:
                        # (q + off) * scale — NOT q*scale + lo: see
                        # _encode_uint8 (FMA-proof decode formulation)
                        s = w["s"].reshape(-1)
                        x = (x + s[0]) * s[1]
                    if normalize is not None:
                        x = (x - normalize[0]) * normalize[1]
                    x = x.astype(train_dtype)
                else:  # int8 block codec: the gradient path's dequant kernel
                    s = w["s"].reshape(-1)
                    flat = quant_kernels.dequantize(
                        q.reshape(-1), s, block=block, orig_len=leaf.n
                    )
                    x = flat.reshape(leaf.local_shape).astype(train_dtype)
                out.append(x[grid1])
            return tuple(out)

        sm = smap(body, mesh, in_specs=(in_specs,), out_specs=out_specs,
                  check=False)

        def fn(wire):
            decoded = sm(wire)
            batch = jax.tree_util.tree_unflatten(treedef, list(decoded))
            if augment is not None:
                batch = augment(batch)
            return batch

        return jax.jit(fn, donate_argnums=(0,) if donate else ())
