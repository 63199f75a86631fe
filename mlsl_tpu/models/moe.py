"""Mixture-of-experts FFN with expert parallelism over the model axis.

Expert parallelism is the modern descendant of the reference's redistribution
machinery: tokens move to the device holding their expert and back — two AlltoAlls
over the model group (exactly the reference's case-4/5 AlltoAll redistribution,
src/mlsl_impl.cpp:203-226, applied per token instead of per feature block).

Switch-style top-1 routing (GShard dispatch algebra): each device routes its local
tokens, builds a capacity-bounded dispatch tensor, all_to_all's token buffers to the
expert owners, applies that device's expert FFNs, and returns the outputs for
gate-weighted combination. Tokens over capacity are dropped (the residual connection
carries them). Routing gradients flow through the gate probability (argmax is
non-differentiable by construction).
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from mlsl_tpu.comm import algos
from mlsl_tpu.log import mlsl_assert


def init_moe_params(key, d_model: int, d_ff: int, n_experts: int, std=0.02) -> Dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wg": jax.random.normal(k1, (d_model, n_experts)) * std,   # replicated
        "w1": jax.random.normal(k2, (n_experts, d_model, d_ff)) * std,  # sharded[0]
        "w2": jax.random.normal(k3, (n_experts, d_ff, d_model)) * std,  # sharded[0]
    }


def _route(x, wg, n_experts: int, capacity: int, top_k: int = 1):
    """-> (dispatch (T, E, C) f32, combine (T, E, C) f32, aux_loss scalar).

    top_k=1 is switch routing; top_k=2 is GShard-style with the two gate
    probabilities renormalized over the selected pair. Capacity positions are
    assigned choice-major (all first choices queue before any second choice,
    GShard's priority rule), so over-capacity drops hit second choices first."""
    logits = x.astype(jnp.float32) @ wg.astype(jnp.float32)      # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = lax.top_k(probs, top_k)                          # (T, K)
    if top_k == 1:
        # switch routing: the RAW probability gates the output — renormalizing
        # would make the gate identically 1.0 and kill the router's task-loss
        # gradient (d(v/v)/dv == 0)
        gates = topv
    else:
        denom = jnp.sum(topv, axis=-1, keepdims=True)
        gates = topv / jnp.maximum(denom, 1e-9)                   # GShard renorm

    dispatch = jnp.zeros((x.shape[0], n_experts, capacity), jnp.float32)
    combine = jnp.zeros_like(dispatch)
    # running per-expert queue length, carried across choices (choice-major)
    taken = jnp.zeros((n_experts,), jnp.float32)
    onehot_first = None
    for c in range(top_k):
        onehot = jax.nn.one_hot(topi[:, c], n_experts, dtype=jnp.float32)  # (T, E)
        if c == 0:
            onehot_first = onehot
        pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot + taken[None, :] * onehot
        keep = (pos < capacity).astype(jnp.float32) * onehot
        slot = jax.nn.one_hot(pos.astype(jnp.int32), capacity, dtype=jnp.float32)
        d_c = keep[:, :, None] * slot
        dispatch = dispatch + d_c
        combine = combine + d_c * gates[:, c][:, None, None]
        taken = taken + jnp.sum(onehot, axis=0)
    # load-balancing auxiliary loss on the FIRST choice (switch/GShard convention)
    frac_tokens = jnp.mean(onehot_first, axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = n_experts * jnp.sum(frac_tokens * frac_probs)
    return dispatch, combine, aux


def mxu_einsum(spec: str, a, b):
    """Einsum with f32 accumulation from (possibly) bf16 operands.

    On TPU this is the MXU-native contract (bf16 in, f32 out). The CPU backend
    cannot execute mixed bf16->f32 dots ("Unsupported element type for
    DotThunk"), so there the dot runs in the operand dtype and the result is
    cast — bf16 on CPU is a simulation path, not a precision contract."""
    if jax.default_backend() == "cpu":
        return jnp.einsum(spec, a, b).astype(jnp.float32)
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


def _expert_ffn(buf, w1, w2, compute_dtype=jnp.float32):
    """buf: (..., El, C, D); w1: (El, D, F); w2: (El, F, D).

    With a bf16 compute_dtype the expert matmuls run bf16-in/f32-accumulate
    (MXU-native); dispatch, combine and the gate always stay f32 for routing
    stability."""
    cdt = jnp.dtype(compute_dtype)
    h = jax.nn.gelu(mxu_einsum("...ecd,edf->...ecf", buf.astype(cdt), w1.astype(cdt)))
    return mxu_einsum("...ecf,efd->...ecd", h.astype(cdt), w2.astype(cdt))


def moe_ffn(
    x: jax.Array,
    params: Dict,
    axis: str,
    ep: int,
    capacity_factor: float = 1.25,
    top_k: int = 1,
    compute_dtype=jnp.float32,
    group=None,
    config=None,
) -> Tuple[jax.Array, jax.Array]:
    """SPMD MoE feed-forward (call inside shard_map over ``axis`` of size ep).

    x: (T, D) tokens REPLICATED over the expert axis (the transformer's post-psum
    residual stream). Each rank routes its 1/ep token slice, token buffers
    all_to_all to the expert owners, expert outputs return and combine, and an
    all-gather reassembles the replicated output — so routing, expert compute and
    capacity competition are all sharded over the ep axis.
    params['w1'/'w2']: this rank's expert shard (El = E/ep experts); 'wg'
    replicated. -> (out (T, D) f32 replicated, aux-loss scalar for this slice).

    ``group``/``config``: the expert-axis ProcessGroup and mlsl Config, when
    the caller has them (HybridTrainer threads its model group). With both,
    the dispatch/combine exchanges route through the collective engine's
    selection table (MLSL_ALGO > tuned profile > inline lax) — a forced or
    tuned ``pallas_a2a`` cell lowers them to the fused quantized alltoall
    kernel. Without, the lax baseline applies unchanged.
    """
    t, d = x.shape
    el = params["w1"].shape[0]
    n_experts = el * ep
    if ep == 1:
        return _moe_slice(x, params, n_experts, capacity_factor, top_k,
                          compute_dtype)

    mlsl_assert(
        t % ep == 0,
        "moe_ffn: token count %d not divisible by ep=%d (trailing tokens would be "
        "silently dropped)", t, ep,
    )
    me = lax.axis_index(axis)
    tl = t // ep
    xs = lax.dynamic_slice_in_dim(x, me * tl, tl, axis=0)         # (Tl, D) distinct
    capacity = max(1, int(tl * capacity_factor * top_k / n_experts))
    dispatch, combine, aux = _route(xs, params["wg"], n_experts, capacity, top_k)
    buf = jnp.einsum("tec,td->ecd", dispatch, xs.astype(jnp.float32))
    # Cast to the compute dtype BEFORE the wire: the experts downcast anyway, so
    # a bf16 dispatch alltoall moves half the bytes for identical inputs (the
    # return path stays f32 — combine consumes it in f32).
    buf = buf.reshape(ep, el, capacity, d).astype(compute_dtype)
    # expert dispatch/combine exchanges route through the collective engine
    # (comm/algos inline helpers): the engine owns the call site, so the
    # lint gate, stats attribution, and future tiered alltoall lowerings
    # all apply here without touching the routing math
    recv = algos.inline_alltoall(buf, axis, split_axis=0, concat_axis=0,
                                 group=group, config=config)
    y = _expert_ffn(recv, params["w1"], params["w2"], compute_dtype)  # (ep, El, C, D)
    back = algos.inline_alltoall(y, axis, split_axis=0, concat_axis=0,
                                 group=group, config=config)
    y_full = back.reshape(n_experts, capacity, d)
    out_slice = jnp.einsum("tec,ecd->td", combine, y_full)         # (Tl, D)
    out = algos.inline_allgather(out_slice, axis, gather_axis=0,
                                 tiled=True)                       # (T, D)
    return out, aux


def _moe_slice(xs, params, n_experts: int, capacity_factor: float, top_k: int = 1,
               compute_dtype=jnp.float32):
    capacity = max(1, int(xs.shape[0] * capacity_factor * top_k / n_experts))
    dispatch, combine, aux = _route(xs, params["wg"], n_experts, capacity, top_k)
    buf = jnp.einsum("tec,td->ecd", dispatch, xs.astype(jnp.float32))
    y = _expert_ffn(buf, params["w1"], params["w2"], compute_dtype)
    return jnp.einsum("tec,ecd->td", combine, y), aux


def moe_ffn_dense(x, wg, w1, w2, ep: int = 1, capacity_factor: float = 1.25,
                  top_k: int = 1):
    """Single-device oracle reproducing the sharded semantics: tokens are routed in
    ep independent slices (capacity competition is per slice). w1: (E, D, F)."""
    t, d = x.shape
    e = w1.shape[0]
    params = {"wg": wg, "w1": w1, "w2": w2}
    outs, auxes = [], []
    tl = t // ep
    for s in range(ep):
        o, a = _moe_slice(x[s * tl : (s + 1) * tl], params, e, capacity_factor, top_k)
        outs.append(o)
        auxes.append(a)
    return jnp.concatenate(outs, axis=0), jnp.stack(auxes).mean()


# -- dropless experts (serving) ----------------------------------------------
#
# The capacity path above builds a dense (T, E, C) dispatch tensor and drops
# what does not fit: right for training at a handful of experts, gigabytes at
# 2,048 tokens x 128 experts. The serving path sorts the token-expert pairs by
# expert and walks the sorted rows a tile at a time, each tile one expert's:
# no pair is ever dropped, only the experts that got a token are read, and
# the trip count is data (decode: 16 tokens touch about 80 of 128 experts and
# the step is bound by the bytes of their weights; prefill: every expert is
# busy and the step is bound by the products).


def init_dropless_params(key, d_model: int, d_expert: int, n_experts: int,
                         std=0.02, dtype=jnp.float32) -> Dict:
    """Router over all experts; gate and up projections side by side in one
    matrix an expert (one product a tile), then down."""
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wr": (jax.random.normal(k1, (d_model, n_experts)) * std).astype(dtype),
        "wgu": (jax.random.normal(k2, (n_experts, d_model, 2 * d_expert))
                * std).astype(dtype),
        "wd": (jax.random.normal(k3, (n_experts, d_expert, d_model))
               * std).astype(dtype),
    }


def route_top_k(y, wr, top_k: int):
    """Softmax over every expert in float32, then the ``top_k`` largest with
    their weights renormalised to sum to one (``norm_topk_prob``). ->
    (experts (T, K) int32, weights (T, K) f32). ``lax.top_k`` is exact and
    puts the lower index first among equals."""
    logits = jnp.dot(y.astype(jnp.float32), wr.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    topv, topi = lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    return topi, topv / jnp.sum(topv, axis=-1, keepdims=True)


def expert_tile(n_tokens: int) -> int:
    """Rows a trip of the walk multiplies: a token meets an expert once, so
    no expert gets more than ``n_tokens`` rows; 256 keeps a trip's products
    level with the bytes of the expert's weights (2 x 256 rows x 3 x d x f
    operations against 6 x d x f bytes)."""
    return max(8, min(256, -(-n_tokens // 8) * 8))


def dropless_experts(y, params: Dict, top_k: int, valid=None,
                     compute_dtype=jnp.float32):
    """Gated-SiLU experts, every routed pair computed.

    y: (T, D) f32, the normed residual. params: ``wr`` (D, E), ``wgu``
    (E, D, 2F) gate then up, ``wd`` (E, F, D). ``valid``: (T,) bool, rows
    that are tokens (padding of a prefill chunk and idle decode slots are
    routed nowhere and read zeros). -> (out (T, D) f32 = sum over a token's
    experts of weight * ((silu(y Wg) * (y Wu)) Wd), experts that got a token,
    token-expert pairs), the two counts int32 scalars.
    """
    cdt = jnp.dtype(compute_dtype)
    prec = lax.Precision.HIGHEST if cdt == jnp.float32 else None
    t, d = y.shape
    n_e, _, f2 = params["wgu"].shape
    f, k = f2 // 2, top_k
    tile = expert_tile(t)
    topi, gates = route_top_k(y, params["wr"], k)
    if valid is not None:
        topi = jnp.where(valid[:, None], topi, n_e)     # sorts last, no tile
    flat_e = topi.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)            # sorted row -> pair
    counts = jnp.sum(flat_e[:, None] == jnp.arange(n_e)[None, :], axis=0,
                     dtype=jnp.int32)
    ends = jnp.cumsum(counts)
    starts = ends - counts
    tiles_of = (counts + tile - 1) // tile
    tile_ends = jnp.cumsum(tiles_of)
    xs = jnp.pad(y.astype(cdt)[order // k], ((0, tile), (0, 0)))
    gs = jnp.pad(gates.reshape(-1)[order], (0, tile))
    wgu, wd = params["wgu"], params["wd"]

    def one_tile(i, out):
        e = jnp.sum(tile_ends <= i)                     # whose tile this is
        at = starts[e] + (i - (tile_ends[e] - tiles_of[e])) * tile
        x = lax.dynamic_slice(xs, (at, 0), (tile, d))
        gu = jnp.dot(x, wgu[e].astype(cdt), precision=prec,
                     preferred_element_type=jnp.float32)
        a = (jax.nn.silu(gu[:, :f]) * gu[:, f:]).astype(cdt)
        o = jnp.dot(a, wd[e].astype(cdt), precision=prec,
                    preferred_element_type=jnp.float32)
        o = o * lax.dynamic_slice(gs, (at,), (tile,))[:, None]
        # the tile's last rows may be the next expert's: leave them as they are
        keep = (at + jnp.arange(tile) < ends[e])[:, None]
        old = lax.dynamic_slice(out, (at, 0), (tile, d))
        return lax.dynamic_update_slice(out, jnp.where(keep, o, old), (at, 0))

    out = lax.fori_loop(0, tile_ends[-1], one_tile,
                        jnp.zeros((t * k + tile, d), jnp.float32))
    back = jnp.argsort(order)                           # pair -> sorted row
    out = jnp.sum(out[back].reshape(t, k, d), axis=1)
    return out, jnp.sum(counts > 0, dtype=jnp.int32), jnp.sum(counts)
