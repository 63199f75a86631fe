"""Decoder-only transformer with dp x sp x tp hybrid parallelism, MLSL in the loop.

The scaling design (SURVEY.md §2 parallelism table + §5.7):
- batch over the 'data' axis (DP), sequence over the 'seq' axis (SP, ring or Ulysses
  attention), heads/hidden over the 'model' axis (TP — the reference's feature-map
  sharding, src/mlsl_impl.cpp:36-66, applied to attention heads and MLP width);
- TP activation reductions are lax.psum over 'model' inside the forward (the
  reference's needReduce -> AllReduce case 2);
- parameter-gradient sync across data x seq goes through ParameterSet requests exactly
  like the ResNet trainer — TP-sharded leaves ride the same distributed buffers, with
  each model-axis slot carrying that rank's shard;
- gradients of replicated params (embeddings, layer norms, head) are psum'd over
  'model' inside the grad program (their forward is used by every TP branch).

Compute is bf16 on the MXU; params and reductions f32.
"""

# mlsl-lint: disable-file=A201 -- the hybrid TP/SP forward embeds its
# activation reductions in-graph by design (the needReduce -> AllReduce
# cases above); they fuse with the surrounding matmuls and are not request
# collectives the engine could route

from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from mlsl_tpu.comm.collectives import _BUF_SPEC
from mlsl_tpu.comm.mesh import DATA_AXIS, MODEL_AXIS, SEQ_AXIS
from mlsl_tpu.log import mlsl_assert
from mlsl_tpu.models.moe import (
    dropless_experts, init_dropless_params, init_moe_params, moe_ffn, mxu_einsum,
)
from mlsl_tpu.models.train import (
    _leaf_buf_spec,
    build_owned_increment_fn,
    build_owned_opt_increment_fn,
    init_shard_opt_state,
    smap,
    _unflatten_like,
)
from mlsl_tpu.comm.mesh import NUM_GRID_AXES
from mlsl_tpu.ops import paged_attention
from mlsl_tpu.parallel.sequence import (
    ring_attention, ulysses_attention, zigzag_perm, zigzag_ring_attention,
)
from mlsl_tpu.types import CompressionType, DataType, OpType


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 256
    d_model: int = 64
    n_heads: int = 8
    head_dim: int = 8
    n_blocks: int = 2
    seq_len: int = 64
    mlp_ratio: int = 4
    attention: str = "ring"  # 'ring' | 'zigzag' | 'ulysses'. 'zigzag' is the
    # load-balanced causal ring (parallel/sequence.py): the trainer feeds
    # tokens/labels in zigzag sequence order and the position embedding rows
    # follow, so training is mathematically identical to 'ring' at ~2x fewer
    # attention block-FLOPs on the ring hops.
    dtype: str = "bfloat16"  # MXU compute dtype; 'float32' for exactness tests
    remat: bool = False      # jax.checkpoint each block: save only the block
    # input, recompute internals (incl. ring-attention hops' collectives) in
    # the backward — O(n_blocks) residual streams instead of O(n_blocks *
    # per-block intermediates) of saved activations; the long-context trade
    remat_policy: str = "full"  # 'full' | 'dots' (with remat=True): 'dots'
    # applies jax.checkpoint_policies.checkpoint_dots — matmul/attention
    # outputs are saved and only elementwise/softmax work replays in the
    # backward, trading O(blocks * S * d) extra saved bytes for nearly all
    # of full remat's recomputed MXU FLOPs
    n_experts: int = 0       # >0: MoE FFN with expert parallelism over 'model'
    moe_top_k: int = 1       # 1 = switch routing; 2 = GShard-style top-2
    moe_aux_weight: float = 0.01
    capacity_factor: float = 2.0
    sharded_vocab: bool = False  # shard the LM head over 'model'; CE via collectives
    # -- the block's description. The defaults are GPT-2's block (LayerNorm,
    # learned positions, as many key-value heads as query heads, GELU MLP or
    # the capacity experts above); the serving programs (prefill_local,
    # chunk_local, decode_local) run any instance of it through _block.
    # Training (forward_local) runs the default instance only.
    norm: str = "layer"          # 'layer' (scale and bias) | 'rms' (scale)
    norm_eps: float = 1e-5
    positions: str = "learned"   # 'learned' table | 'rope' (rotate-half over
    # the whole head; the cache then holds rotated keys)
    rope_theta: float = 10000.0
    n_kv_heads: int = 0          # 0 = n_heads; else grouped-query attention
    qk_norm: bool = False        # RMS norm over head_dim on q and k, learned
    mlp: str = "gelu"            # 'gelu' | 'experts': gated-SiLU experts,
    # dropless (moe.dropless_experts), n_experts of expert_width, moe_top_k a
    # token renormalised; no biases
    expert_width: int = 0
    # learned selection: index_heads heads of index_dim over one shared index
    # key a token; a query attends to the index_topk highest-scored positions
    index_heads: int = 0
    index_dim: int = 0
    index_topk: int = 0
    weights_dtype: str = "float32"   # matrices at rest (norm scales stay f32)
    kv_dtype: str = "float32"        # K, V and index keys at rest
    residual_dtype: str = ""         # '' = dtype; 'float32' keeps the stream

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def index_row(self) -> int:
        """Lanes an index key takes at rest: ``index_dim`` rounded up to the
        device's 128. A 64-wide row is padded to 128 lanes by the device
        anyway, and the compiler then lays the whole pool out anew around
        every gather (12 ms of a 32 ms decode step and 25 ms of a chunk:
        PERF.md section 6, PR 29); the zeros above ``index_dim`` are stored,
        counted in the pool's page bytes, and add nothing to a score."""
        return -(-self.index_dim // 128) * 128 if self.index_topk else 0

    @property
    def gpt2_block(self) -> bool:
        return (self.norm == "layer" and self.positions == "learned"
                and self.mlp == "gelu" and not self.n_kv_heads
                and not self.qk_norm and not self.index_topk)


def init_params(key, cfg: TransformerConfig) -> Dict:
    if not cfg.gpt2_block:
        return _init_described(key, cfg)
    ks = iter(jax.random.split(key, 8 + 8 * cfg.n_blocks))
    dm, h, dh = cfg.d_model, cfg.n_heads, cfg.head_dim
    f = cfg.mlp_ratio * dm
    std = 0.02
    params = {
        "embed": {
            "tok": jax.random.normal(next(ks), (cfg.vocab, dm)) * std,
            "pos": jax.random.normal(next(ks), (cfg.seq_len, dm)) * std,
        },
        "final": {
            "ln_scale": jnp.ones((dm,)),
            "ln_bias": jnp.zeros((dm,)),
            "head": jax.random.normal(next(ks), (dm, cfg.vocab)) * std,
        },
    }
    for i in range(cfg.n_blocks):
        params[f"blk{i}.ln"] = {
            "ln1_scale": jnp.ones((dm,)), "ln1_bias": jnp.zeros((dm,)),
            "ln2_scale": jnp.ones((dm,)), "ln2_bias": jnp.zeros((dm,)),
        }
        params[f"blk{i}.attn"] = {
            "wqkv": jax.random.normal(next(ks), (dm, 3, h, dh)) * std,
            "wo": jax.random.normal(next(ks), (h, dh, dm)) * std,
        }
        if cfg.n_experts > 0:
            params[f"blk{i}.mlp"] = init_moe_params(
                next(ks), dm, f, cfg.n_experts, std
            )
        else:
            params[f"blk{i}.mlp"] = {
                "w1": jax.random.normal(next(ks), (dm, f)) * std,
                "b1": jnp.zeros((f,)),
                "w2": jax.random.normal(next(ks), (f, dm)) * std,
                "b2": jnp.zeros((dm,)),
            }
    return params


def _init_described(key, cfg: TransformerConfig) -> Dict:
    """Weights of a block that is not GPT-2's (RMS norm, rotary positions,
    grouped-query heads, an indexer, dropless experts): matrices normal 0.02
    in ``weights_dtype``, scales 1 and biases 0 in float32. No bias on any
    projection, no position table."""
    mlsl_assert(cfg.norm == "rms" and cfg.positions == "rope"
                and cfg.mlp == "experts" and cfg.n_experts > 0,
                "the described block is RMS norm, rotary, dropless experts")
    wdt = jnp.dtype(cfg.weights_dtype)
    dm, h, g, dh = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    std = 0.02

    def normal(k, shape):
        return (jax.random.normal(k, shape) * std).astype(wdt)

    ks = iter(jax.random.split(key, 2 + 8 * cfg.n_blocks))
    params = {
        "embed": {"tok": normal(next(ks), (cfg.vocab, dm))},
        "final": {"ln_scale": jnp.ones((dm,)),
                  "head": normal(next(ks), (dm, cfg.vocab))},
    }
    for i in range(cfg.n_blocks):
        params[f"blk{i}.ln"] = {"ln1_scale": jnp.ones((dm,)),
                                "ln2_scale": jnp.ones((dm,))}
        attn = {"wq": normal(next(ks), (dm, h, dh)),
                "wkv": normal(next(ks), (dm, 2, g, dh)),
                "wo": normal(next(ks), (h, dh, dm))}
        if cfg.qk_norm:
            attn["q_norm"] = jnp.ones((dh,))
            attn["k_norm"] = jnp.ones((dh,))
        if cfg.index_topk:
            ji, di = cfg.index_heads, cfg.index_dim
            attn["wiq"] = normal(next(ks), (dm, ji, di))
            attn["wik"] = normal(next(ks), (dm, di))
            attn["wiw"] = normal(next(ks), (dm, ji))
            attn["ik_scale"] = jnp.ones((di,))
            attn["ik_bias"] = jnp.zeros((di,))
        params[f"blk{i}.attn"] = attn
        params[f"blk{i}.mlp"] = init_dropless_params(
            next(ks), dm, cfg.expert_width, cfg.n_experts, std, wdt)
    return params


def param_specs(cfg: TransformerConfig) -> Dict:
    """PartitionSpec pytree: which leaves are TP-sharded over 'model'."""
    if not cfg.gpt2_block:
        # served on one chip of a pipeline stage: nothing is sharded yet
        return jax.tree.map(
            lambda _: P(), jax.eval_shape(
                lambda: _init_described(jax.random.PRNGKey(0), cfg)))
    specs = {
        "embed": {"tok": P(), "pos": P()},
        "final": {
            "ln_scale": P(),
            "ln_bias": P(),
            # large-vocab: the head shards over 'model'; CE is computed from the
            # per-shard logits with pmax/psum (never materializing full-V logits)
            "head": P(None, MODEL_AXIS) if cfg.sharded_vocab else P(),
        },
    }
    for i in range(cfg.n_blocks):
        specs[f"blk{i}.ln"] = {
            "ln1_scale": P(), "ln1_bias": P(), "ln2_scale": P(), "ln2_bias": P(),
        }
        specs[f"blk{i}.attn"] = {
            "wqkv": P(None, None, MODEL_AXIS, None),
            "wo": P(MODEL_AXIS, None, None),
        }
        if cfg.n_experts > 0:
            # expert parallelism: experts sharded over the model axis
            specs[f"blk{i}.mlp"] = {
                "wg": P(),
                "w1": P(MODEL_AXIS, None, None),
                "w2": P(MODEL_AXIS, None, None),
            }
        else:
            specs[f"blk{i}.mlp"] = {
                "w1": P(None, MODEL_AXIS),
                "b1": P(MODEL_AXIS),
                "w2": P(MODEL_AXIS, None),
                "b2": P(),
            }
    return specs


def layer_names(cfg: TransformerConfig) -> List[str]:
    names = ["embed"]
    for i in range(cfg.n_blocks):
        names += [f"blk{i}.ln", f"blk{i}.attn", f"blk{i}.mlp"]
    names.append("final")
    return names


def get_layer(params, name):
    return params[name]


def _ln(x, scale, bias, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * scale + bias


def forward_local(params, tokens, cfg: TransformerConfig, sp: int, tp: int,
                  comm=None):
    """SPMD forward on local shards (call inside shard_map).

    tokens: (Bl, Sl) int32. params: LOCAL shards per param_specs. Returns
    (final hidden states (Bl, Sl, d_model) f32 — post final-LN, replicated over
    'model' (psum'd), sharded over data/seq — and the MoE aux-loss total, 0.0
    without experts). The LM head is applied by the loss (local_loss), which owns
    the replicated-vs-vocab-sharded distinction.

    ``comm``: optional (model ProcessGroup, mlsl Config) pair; with it the MoE
    dispatch/combine exchanges route through the collective engine's selection
    table (comm/algos.inline_alltoall) instead of pinning the lax baseline.
    """
    mlsl_assert(cfg.gpt2_block, "training runs the learned-position "
                "LayerNorm block (ROADMAP D4: forward_local has a body of its "
                "own; the serving programs share _block)")
    emb = params["embed"]
    cdt = jnp.dtype(cfg.dtype)
    aux_total = jnp.float32(0.0)
    s_idx = lax.axis_index(SEQ_AXIS) if sp > 1 else 0
    sl = tokens.shape[1]
    if cfg.attention == "zigzag" and sp > 1:
        # zigzag layout: tokens/labels arrive zigzag-ordered (shard_tokens),
        # so the position rows follow the SAME permutation — zigzag_perm is
        # the single source of truth for the layout, derived from the RUN-TIME
        # global length sp*sl (shard_tokens permutes whatever length it is
        # fed, which may be shorter than cfg.seq_len). Slice this shard's
        # window of the constant index vector first, then gather only the sl
        # needed rows.
        perm = jnp.asarray(zigzag_perm(sp * sl, sp))
        idx = lax.dynamic_slice_in_dim(perm, s_idx * sl, sl, axis=0)
        pos = emb["pos"][idx]
    else:
        pos = lax.dynamic_slice_in_dim(emb["pos"], s_idx * sl, sl, axis=0)
    h = (emb["tok"][tokens] + pos[None]).astype(cdt)

    if cfg.attention == "zigzag":
        def attn_fn(q, k, v, ax, n, causal=True):
            mlsl_assert(causal, "zigzag attention is causal-only "
                                "(use attention='ring' for non-causal)")
            if n > 1:
                return zigzag_ring_attention(q, k, v, ax, n)
            return ring_attention(q, k, v, ax, n, causal=True)
    else:
        attn_fn = ring_attention if cfg.attention == "ring" else ulysses_attention
    def block_body(h, lnp, ap, mp):
        a = _ln(h.astype(jnp.float32), lnp["ln1_scale"], lnp["ln1_bias"]).astype(cdt)
        qkv = jnp.einsum("bsd,dchx->bcshx", a, ap["wqkv"].astype(cdt))
        q, k, v = (
            jnp.moveaxis(qkv[:, c], 2, 1) for c in range(3)
        )  # (Bl, Hl, Sl, Dh)
        attn = attn_fn(q, k, v, SEQ_AXIS, sp, causal=True)
        # bf16 operands, f32 accumulate/output: keeps the projection on the MXU's
        # native path while the residual add and TP psum stay f32.
        o = mxu_einsum("bhsx,hxd->bsd", attn.astype(cdt), ap["wo"].astype(cdt))
        o = lax.psum(o, MODEL_AXIS) if tp > 1 else o      # TP reduction (case-2 analog)
        h = (h.astype(jnp.float32) + o).astype(cdt)

        a = _ln(h.astype(jnp.float32), lnp["ln2_scale"], lnp["ln2_bias"]).astype(cdt)
        if cfg.n_experts > 0:
            bl, sl_, dm = a.shape
            o2d, aux = moe_ffn(
                a.reshape(bl * sl_, dm).astype(jnp.float32),
                mp, MODEL_AXIS, tp, cfg.capacity_factor, cfg.moe_top_k,
                compute_dtype=cdt,
                group=comm[0] if comm else None,
                config=comm[1] if comm else None,
            )
            h = (h.astype(jnp.float32) + o2d.reshape(bl, sl_, dm)).astype(cdt)
        else:
            aux = jnp.float32(0.0)
            f = jax.nn.gelu(
                jnp.einsum("bsd,df->bsf", a, mp["w1"].astype(cdt))
                + mp["b1"].astype(cdt)
            )
            o = mxu_einsum("bsf,fd->bsd", f, mp["w2"].astype(cdt))
            o = lax.psum(o, MODEL_AXIS) if tp > 1 else o
            h = (h.astype(jnp.float32) + o + mp["b2"]).astype(cdt)
        return h, aux

    # cfg.remat: save only each block's input residual stream; the backward
    # replays the block (incl. the ring hops' collectives) instead of keeping
    # qkv/attn/gelu intermediates alive — the O(sqrt)-style memory trade that
    # makes long sequences fit (docs/DESIGN.md long-context section)
    mlsl_assert(cfg.remat_policy in ("full", "dots"),
                "unknown remat_policy %r", cfg.remat_policy)
    if cfg.remat:
        if cfg.remat_policy == "dots":
            blk = jax.checkpoint(
                block_body, policy=jax.checkpoint_policies.checkpoint_dots
            )
        else:
            blk = jax.checkpoint(block_body)
    else:
        blk = block_body
    for i in range(cfg.n_blocks):
        h, aux = blk(
            h, params[f"blk{i}.ln"], params[f"blk{i}.attn"], params[f"blk{i}.mlp"]
        )
        aux_total = aux_total + aux

    fin = params["final"]
    h = _ln(h.astype(jnp.float32), fin["ln_scale"], fin["ln_bias"])
    return h, aux_total


def _sharded_vocab_ce(h, head_local, labels, vocab_local: int):
    """CE over a model-axis-sharded vocabulary: per-shard logits + pmax/psum
    log-sum-exp; the (tokens, V) logits matrix never exists on any device."""
    logits_l = h @ head_local                                  # (B, S, Vl)
    # the stability max cancels analytically in d(lse)/d(logits) (= softmax), so
    # stop_gradient is exact; pmax has no JVP rule, so the cross-shard max rides
    # a (small) all_gather of the per-shard maxima instead
    mx = lax.stop_gradient(
        jnp.max(lax.all_gather(jnp.max(logits_l, axis=-1), MODEL_AXIS, axis=0), axis=0)
    )                                                          # (B, S)
    se = lax.psum(
        jnp.sum(jnp.exp(logits_l - mx[..., None]), axis=-1), MODEL_AXIS
    )
    lse = jnp.log(se) + mx
    off = lax.axis_index(MODEL_AXIS) * vocab_local
    local_label = jnp.clip(labels - off, 0, vocab_local - 1)
    in_range = jnp.logical_and(labels >= off, labels < off + vocab_local)
    picked = jnp.take_along_axis(logits_l, local_label[..., None], axis=-1)[..., 0]
    label_logit = lax.psum(jnp.where(in_range, picked, 0.0), MODEL_AXIS)
    return jnp.sum(lse - label_logit)


def local_loss(params, tokens, labels, cfg, sp, tp, comm=None):
    """Sum (not mean) of CE over the LOCAL token shard — the reduction across
    data/seq shards belongs to the MLSL gradient requests. Owns the LM head:
    replicated (dense softmax) or model-axis vocab-sharded (pmax/psum CE, full-V
    logits never materialize). Returns (ce_sum, aux)."""
    h, aux = forward_local(params, tokens, cfg, sp, tp, comm=comm)
    head = params["final"]["head"].astype(jnp.float32)
    if cfg.sharded_vocab and tp > 1:
        return _sharded_vocab_ce(h, head, labels, head.shape[-1]), aux
    logp = jax.nn.log_softmax(h @ head)
    ce = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(ce), aux


# -- decode mode (mlsl_tpu.serve): prefill + paged single-token steps ---------
#
# The serving engine (serve/engine.py) compiles these bodies as model-axis
# shard_map programs (dp = sp = 1): a per-sequence prefill, and the batched
# decode step over the paged KV pool. KV pages shard over 'model' on the
# merged heads x head_dim axis (whole heads a rank, the wqkv spec); TP output
# reductions route through the collective engine's selection table
# (algos.inline_allreduce) when a (model group, config) pair is passed, so
# the µs-class decode allreduces are pallas_rhd-eligible and breaker
# degradation to lax stays intact.
#
# Attention math runs in f32 over f32-at-rest KV (or int8 with f32 scales) in
# both programs. The decode step reads K and V where they lie in the pool,
# through the flat list of the pages some live sequence holds
# (ops/paged_attention.ragged_paged_attention): its reductions walk that
# list and not the prefill's padded context, so the two programs agree to
# rounding, not to the bit; tests/test_serve.py holds every served token's
# logit within a tolerance of the unpaged oracle's best.


def _decode_reduce(x, tp: int, comm):
    """TP output reduction for the decode path: selection-table routed when
    a (model group, config) pair is supplied, lax baseline otherwise."""
    if tp <= 1:
        return x
    if comm is not None:
        from mlsl_tpu.comm import algos

        return algos.inline_allreduce(
            x, MODEL_AXIS, group=comm[0], config=comm[1]
        )
    return lax.psum(x, MODEL_AXIS)


def _causal_attn_f32(q, k, v, scale):
    """Plain causal attention on one sequence (sp=1): (Hl, S, Dh) f32 ->
    (Hl, S, Dh) f32. The prefill twin of the decode step's masked softmax."""
    s = jnp.einsum("hsx,htx->hst", q * scale, k)
    n = q.shape[1]
    mask = jnp.tril(jnp.ones((n, n), bool))
    s = jnp.where(mask[None], s, -jnp.inf)
    return jnp.einsum("hst,htx->hsx", jax.nn.softmax(s, axis=-1), v)


def kv_block_quant(x):
    """Symmetric int8 over the trailing (head_dim) lane dim — the
    ops/quant_kernels blockwise-ref contract with block = head_dim, applied
    per (token, head) row. Returns (q int8, scales f32 without the lane
    dim); dequantize is ``q * scales[..., None]`` (the dequantize oracle
    tests/test_serve.py pins against)."""
    amax = jnp.max(jnp.abs(x), axis=-1)
    scale = jnp.where(amax == 0, 1.0, amax / 127.0).astype(jnp.float32)
    q = jnp.clip(
        jnp.round(x / scale[..., None]), -127, 127
    ).astype(jnp.int8)
    return q, scale


# -- the block, as the serving programs run it --------------------------------
#
# One description (TransformerConfig: norm, positions, head counts, MLP kind,
# selection) and one body: rows of (T, d_model) in, the attention handed in by
# the program that calls it (a whole padded prompt, a chunk of a prompt over
# the paged cache, one token a slot over the paged cache). GPT-2's block and
# the RMS-norm / rotary / grouped-query / indexer / dropless-experts block
# are instances.


def _norm(x, p, name: str, cfg: TransformerConfig):
    """Float32 norm of the rows of x by the scale (and bias) ``name`` of p."""
    x = x.astype(jnp.float32)
    if cfg.norm == "rms":
        ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x * lax.rsqrt(ms + cfg.norm_eps) * p[name + "_scale"]
    return _ln(x, p[name + "_scale"], p[name + "_bias"], cfg.norm_eps)


def _rope(x, positions, theta: float):
    """Rotate-half rotary embedding over the whole trailing axis. x: (T, ...,
    D) f32 with its rows at ``positions`` (T,)."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) * 2.0
                           / x.shape[-1]))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _project(a, ap, cfg: TransformerConfig, positions):
    """Queries, keys and values of the normed rows a (T, d_model), float32:
    q (T, Hl, Dh), k and v (T, Gl, Dh), keys rotated where positions are
    rotary; and the indexer's (queries (T, J, Dr), key (T, Dr), head weights
    (T, J)) where the configuration has one, else None: Dr = ``index_row``
    lanes, zeros above ``index_dim``, as the index keys' pool stores them."""
    cdt = a.dtype
    if "wqkv" in ap:
        qkv = jnp.einsum("td,dchx->cthx", a, ap["wqkv"].astype(cdt))
        q, k, v = (qkv[c].astype(jnp.float32) for c in range(3))
    else:
        q = mxu_einsum("td,dhx->thx", a, ap["wq"].astype(cdt))
        kv = mxu_einsum("td,dchx->cthx", a, ap["wkv"].astype(cdt))
        k, v = kv[0], kv[1]
    if cfg.qk_norm:
        def head_rms(x, scale):
            ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
            return x * lax.rsqrt(ms + cfg.norm_eps) * scale

        q, k = head_rms(q, ap["q_norm"]), head_rms(k, ap["k_norm"])
    if cfg.positions == "rope":
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
    index = None
    if cfg.index_topk:
        qi = mxu_einsum("td,djx->tjx", a, ap["wiq"].astype(cdt))
        ki = _ln(mxu_einsum("td,dx->tx", a, ap["wik"].astype(cdt)),
                 ap["ik_scale"], ap["ik_bias"], cfg.norm_eps)
        wi = mxu_einsum("td,dj->tj", a, ap["wiw"].astype(cdt))
        lanes = [(0, 0), (0, cfg.index_row - cfg.index_dim)]
        index = (jnp.pad(_rope(qi, positions, cfg.rope_theta), [(0, 0)] + lanes),
                 jnp.pad(_rope(ki, positions, cfg.rope_theta), lanes), wi)
    return q, k, v, index


def _embed(params, tokens, positions, cfg: TransformerConfig, cdt):
    emb = params["embed"]
    h = emb["tok"][tokens]
    if cfg.positions == "learned":
        h = h + emb["pos"][positions]
    return h.astype(jnp.dtype(cfg.residual_dtype or cdt))


def _logits(params, h, cfg: TransformerConfig, cdt):
    """Final norm and the output head on rows h -> float32 logits. Float32
    weights multiply in float32; weights at rest in bfloat16 go to the MXU as
    they are, the sums in float32."""
    fin = params["final"]
    h = _norm(h, fin, "ln", cfg)
    if fin["head"].dtype == jnp.float32:
        return h @ fin["head"]
    return mxu_einsum("td,dv->tv", h.astype(cdt), fin["head"].astype(cdt))


def _block(h, params, i: int, cfg: TransformerConfig, cdt, tp: int, comm,
           attend, valid=None):
    """Block i on rows h (T, d_model). ``attend(i, a, ap)`` -> (T, Hl, Dh)
    f32 is the calling program's attention over the normed rows a (it also
    keeps the cache). -> (h, int32 [experts that got a token, token-expert
    pairs]; zeros where the MLP is dense)."""
    lnp = params[f"blk{i}.ln"]
    ap = params[f"blk{i}.attn"]
    mp = params[f"blk{i}.mlp"]
    rdt = jnp.dtype(cfg.residual_dtype or cdt)
    a = _norm(h, lnp, "ln1", cfg).astype(cdt)
    attn = attend(i, a, ap)
    o = mxu_einsum("thx,hxd->td", attn.astype(cdt), ap["wo"].astype(cdt))
    o = _decode_reduce(o, tp, comm)
    h = (h.astype(jnp.float32) + o).astype(rdt)

    a = _norm(h, lnp, "ln2", cfg)
    if cfg.mlp == "experts":
        o, hit, pairs = dropless_experts(a, mp, cfg.moe_top_k, valid, cdt)
        return (h.astype(jnp.float32) + o).astype(rdt), jnp.stack([hit, pairs])
    a = a.astype(cdt)
    f = jax.nn.gelu(
        jnp.einsum("td,df->tf", a, mp["w1"].astype(cdt))
        + mp["b1"].astype(cdt)
    )
    o = mxu_einsum("tf,fd->td", f, mp["w2"].astype(cdt))
    o = _decode_reduce(o, tp, comm)
    h = (h.astype(jnp.float32) + o + mp["b2"]).astype(rdt)
    return h, jnp.zeros((2,), jnp.int32)


def _check_decode_mode(cfg: TransformerConfig):
    mlsl_assert(cfg.mlp == "experts" or cfg.n_experts == 0,
                "decode mode serves dense MLPs and dropless experts "
                "(mlp='experts'), not the capacity path")
    mlsl_assert(not cfg.sharded_vocab,
                "decode mode serves a replicated LM head")


def prefill_local(params, tokens, length, cfg: TransformerConfig, tp: int,
                  comm=None, dtype=None):
    """Decode-mode prefill over one sequence (call inside shard_map).

    tokens: (S,) int32, padded past ``length`` with any value — padded
    positions' K/V are computed but land on the KV cache's reserved garbage
    page (serve/kv_cache.py) and are masked out of every decode read.
    Returns (next-token logits (V,) f32 read at position length-1,
    k, v: (n_blocks, S, Gl*Dh) f32 local head shards, heads merged with
    head_dim as the pool's pages store them).
    """
    _check_decode_mode(cfg)
    mlsl_assert(not cfg.index_topk and not cfg.n_kv_heads,
                "a grouped-query or indexer configuration prefills by "
                "chunks over the paged cache (chunk_local)")
    cdt = jnp.dtype(dtype or cfg.dtype)
    n = tokens.shape[0]
    positions = jnp.arange(n)
    h = _embed(params, tokens, positions, cfg, cdt)
    scale = 1.0 / float(np.sqrt(cfg.head_dim))
    ks, vs = [], []

    def attend(i, a, ap):
        q, k, v, _ = _project(a, ap, cfg, positions)   # f32, the at-rest KV
        ks.append(k.reshape(n, -1))                     # (S, Hl*Dh): page
        vs.append(v.reshape(n, -1))                     # layout
        q, k, v = (jnp.moveaxis(x, 1, 0) for x in (q, k, v))
        return jnp.moveaxis(_causal_attn_f32(q, k, v, scale), 0, 1)

    valid = positions < length
    for i in range(cfg.n_blocks):
        h, _ = _block(h, params, i, cfg, cdt, tp, comm, attend, valid)
    last = lax.dynamic_slice_in_dim(h, length - 1, 1, axis=0)
    return _logits(params, last, cfg, cdt)[0], jnp.stack(ks), jnp.stack(vs)


def _keys_per_trip(pages: int, page: int, want: int = 512) -> int:
    """Keys a trip of a chunk's walks reads: whole pages, a divisor of the
    sequence's page table, about ``want``."""
    n = max(1, min(pages, want // page))
    while pages % n:
        n -= 1
    return n * page


def chunk_local(params, tokens, offset, n_valid, table, kpool, vpool, ipool,
                cfg: TransformerConfig, tp: int, comm=None, dtype=None):
    """One chunk of one sequence's prompt, through the paged cache (call
    inside shard_map).

    tokens: (C,) int32, the chunk's ids at positions offset .. offset + C -
    1, valid up to ``n_valid`` (the last chunk is padded to the chunk's
    shape; its padding writes to the garbage page, is routed to no expert
    and is read by no query). table: (P,) int32, the sequence's page table
    padded with page 0. The chunk's K, V (and index keys: ``ipool``, None
    for a configuration without an indexer) are written into the pools,
    then each query attends to what the cache holds of the sequence up to
    itself: every such position, or the ``index_topk`` that its indexer
    scores highest (paged_attention.select_in_context: exactly, searched
    as far as the context held reaches). The walks over the
    context stop at offset + n_valid. Returns (logits (V,) f32 at the last
    valid position, int32 [experts that got a token, token-expert pairs]
    summed over layers, kpool, vpool[, ipool]) - the engine donates the
    pools.
    """
    _check_decode_mode(cfg)
    mlsl_assert(tp == 1, "chunked prefill serves one chip a stage")
    cdt = jnp.dtype(dtype or cfg.dtype)
    c = tokens.shape[0]
    page, n_pages = kpool.shape[2], table.shape[0]
    s_max = n_pages * page
    g, dh = cfg.kv_heads, cfg.head_dim
    block = _keys_per_trip(n_pages, page)
    qpos = offset + jnp.arange(c)
    valid = jnp.arange(c) < n_valid
    n_keys = offset + n_valid
    at_page = jnp.where(valid, table[jnp.minimum(qpos // page, n_pages - 1)], 0)
    at_row = qpos % page
    kpos = jnp.arange(s_max)
    # what a query may read at all: itself and before, inside what is held
    causal = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] < n_keys)
    pools = {"k": kpool, "v": vpool, "i": ipool}
    h = _embed(params, tokens, jnp.minimum(qpos, cfg.seq_len - 1), cfg, cdt)

    def attend(i, a, ap):
        q, k, v, index = _project(a, ap, cfg, qpos)
        kdt = pools["k"].dtype
        pools["k"] = pools["k"].at[i, at_page, at_row].set(
            k.reshape(c, -1).astype(kdt))
        pools["v"] = pools["v"].at[i, at_page, at_row].set(
            v.reshape(c, -1).astype(kdt))
        allowed = causal
        if index is not None:
            qi, ki, wi = index
            pools["i"] = pools["i"].at[i, at_page, at_row].set(ki.astype(kdt))
            kictx = pools["i"][i, table].reshape(s_max, -1)
            scores = paged_attention.context_index_scores(
                qi.astype(kdt), wi, kictx, causal, n_keys, block)
            room = jnp.minimum(jnp.minimum(qpos + 1, n_keys), cfg.index_topk)
            allowed = paged_attention.select_in_context(
                scores, room, n_keys, cfg.index_topk)
        kctx = pools["k"][i, table].reshape(s_max, g, dh)
        vctx = pools["v"][i, table].reshape(s_max, g, dh)
        return paged_attention.masked_context_attention(
            q, kctx, vctx, allowed, n_keys, block)

    counts = jnp.zeros((2,), jnp.int32)
    for i in range(cfg.n_blocks):
        h, n = _block(h, params, i, cfg, cdt, tp, comm, attend, valid)
        counts = counts + n
    last = lax.dynamic_slice_in_dim(h, n_valid - 1, 1, axis=0)
    out = (_logits(params, last, cfg, cdt)[0], counts, pools["k"], pools["v"])
    return out + ((pools["i"],) if ipool is not None else ())


def decode_local(params, slots, live, kpool, vpool,
                 cfg: TransformerConfig, tp: int, comm=None, dtype=None,
                 kscale=None, vscale=None, ipool=None):
    """One continuous-batching decode step (call inside shard_map).

    slots: (3, B) int32, a column a batch slot: the token the slot feeds, the
    index that token occupies (its K/V is written there, and it attends over
    indices <= it) and the pool page that index lies in (0 = the reserved
    garbage page: inactive slots carry zeros and their writes land there).
    live: (3, capacity) int32, the flat list of the pages some live sequence
    holds, in slot order: pool page, owner slot (-1 pads the list), token
    index of the page's first row. kpool/vpool: (n_blocks, Np, page, Gl*Dh)
    KV pools, int8 with kscale/vscale (n_blocks, Np, Hl*page) for the
    quantized variant (kv_block_quant codec; a page's scales head-major).
    The new token's K and V are scattered into the pools and attention reads
    the listed pages where they lie; no operation's cost follows the pool's
    size. Returns (logits (B, V) f32, kpool, vpool[, kscale, vscale]) — the
    engine donates the pools.

    With an indexer (``ipool``: the index keys' pool, (n_blocks, Np, page,
    ``cfg.index_row``)) ``live`` is instead (B, P) int32, a page table a slot padded with
    page 0: a slot scores the index keys of its whole context, keeps the
    ``index_topk`` highest exactly and attends over those rows, gathered
    from the pools where they lie. Returns (logits, kpool, vpool, ipool,
    int32 [experts that got a token, token-expert pairs] summed over
    layers).
    """
    _check_decode_mode(cfg)
    cdt = jnp.dtype(dtype or cfg.dtype)
    quant = kscale is not None
    page = kpool.shape[2]
    tokens, positions, pages_b = slots
    h = _embed(params, tokens, positions, cfg, cdt)               # (B, dm)
    b = tokens.shape[0]
    offs_b = positions % page
    pools = {"k": kpool, "v": vpool, "ks": kscale, "vs": vscale, "i": ipool}
    if ipool is None:
        pages, owners, bases = live
        valid, mine = paged_attention.live_masks(owners, bases, positions, page)
    else:
        mlsl_assert(tp == 1 and not quant,
                    "the indexer's decode serves one chip, unquantised pools")
        tables = live
        s_max = tables.shape[1] * page
        top = min(cfg.index_topk, s_max)
        seen = jnp.arange(s_max)[None, :] <= positions[:, None]

    def attend(i, a, ap):
        q, knew, vnew, index = _project(a, ap, cfg, positions)
        kdt = pools["k"].dtype
        if quant:
            knew, ksc = kv_block_quant(knew)
            vnew, vsc = kv_block_quant(vnew)
            at = (i, pages_b[:, None],
                  jnp.arange(ksc.shape[1]) * page + offs_b[:, None])
            pools["ks"] = pools["ks"].at[at].set(ksc)
            pools["vs"] = pools["vs"].at[at].set(vsc)
        pools["k"] = pools["k"].at[i, pages_b, offs_b].set(
            knew.reshape(b, -1).astype(kdt))
        pools["v"] = pools["v"].at[i, pages_b, offs_b].set(
            vnew.reshape(b, -1).astype(kdt))
        if index is None:
            return paged_attention.ragged_paged_attention(
                q, pools["k"], pools["v"], i, pages, owners, valid, mine,
                pools["ks"], pools["vs"],
                chunk=paged_attention.PAGES_PER_CHUNK)
        qi, ki, wi = index
        pools["i"] = pools["i"].at[i, pages_b, offs_b].set(ki.astype(kdt))
        kictx = pools["i"][i, tables].reshape(b, s_max, -1)
        scores = jnp.where(seen, paged_attention.index_scores(
            qi.astype(kdt), wi, kictx), -jnp.inf)
        chosen = paged_attention.exact_top_k_mask(
            scores, jnp.minimum(positions + 1, top))
        rows, ok = paged_attention.compact_selected(
            chosen.reshape(b, -1, page), tables, top)
        return paged_attention.selected_attention(
            q, pools["k"], pools["v"], i, rows, ok, cfg.kv_heads // tp)

    counts = jnp.zeros((2,), jnp.int32)
    for i in range(cfg.n_blocks):
        h, n = _block(h, params, i, cfg, cdt, tp, comm, attend, pages_b > 0)
        counts = counts + n
    logits = _logits(params, h, cfg, cdt)
    if quant:
        return logits, pools["k"], pools["v"], pools["ks"], pools["vs"]
    if ipool is not None:
        return logits, pools["k"], pools["v"], pools["i"], counts
    return logits, pools["k"], pools["v"]


class HybridTrainer:
    """dp x sp x tp training with per-layer MLSL gradient sync over data x seq."""

    def __init__(self, env, cfg: TransformerConfig, dp: int, sp: int, tp: int,
                 batch: int = None, lr: float = 0.1, seed: int = 0,
                 distributed_update: bool = False,
                 compression=None,
                 devices=None,
                 optimizer=None,
                 donate_params: bool = True):
        """optimizer: optional optax.GradientTransformation; state lives per
        layer over each rank's flat local (TP-sharded) parameter vector, or the
        owned gradient shard under distributed_update (ZeRO-1). Elementwise/
        shard-local transforms only (adam, momentum, ...); params-consuming
        transforms see the flat local param vector on the plain path.

        donate_params: EVERY update path (fused no-comm, graph barrier
        update, optax update, ZeRO-1 increment apply) donates the parameter
        and optimizer-state buffers to XLA so the update is in-place in HBM —
        after step() returns, any EXTERNAL reference to the previous
        ``trainer.params`` tree points at deleted buffers (reading it raises).
        Pass donate_params=False to keep old param trees readable (e.g. EMA
        snapshots, debugging diffs) at the cost of double-buffering the
        weights."""
        self.env = env
        self.cfg = cfg
        self.dp, self.sp, self.tp = dp, sp, tp
        self.batch = batch if batch is not None else dp
        mlsl_assert(self.batch % dp == 0, "batch %d %% dp %d", self.batch, dp)
        self.lr = lr
        from mlsl_tpu.optim import ShardedAdafactor

        mlsl_assert(
            not isinstance(optimizer, ShardedAdafactor),
            "ShardedAdafactor's cross-shard factored stats are implemented for "
            "DataParallelTrainer's distributed update; pass "
            "optimizer.as_optax() to HybridTrainer (plain path only)",
        )
        self.optimizer = optimizer
        self.donate_params = bool(donate_params)
        self.dist = env.create_distribution(
            dp, tp, seq_parts=sp, devices=devices
        )
        mlsl_assert(
            self.dist.replica_count == 1,
            "device count must equal dp*sp*tp (got %d replicas)",
            self.dist.replica_count,
        )
        mlsl_assert(cfg.n_heads % tp == 0, "heads %d %% tp %d", cfg.n_heads, tp)
        mlsl_assert(cfg.seq_len % sp == 0, "seq %d %% sp %d", cfg.seq_len, sp)
        if cfg.sharded_vocab:
            mlsl_assert(
                cfg.vocab % tp == 0, "vocab %d %% tp %d (sharded head)",
                cfg.vocab, tp,
            )
        if cfg.n_experts > 0:
            local_tokens = (self.batch // dp) * (cfg.seq_len // sp)
            mlsl_assert(
                cfg.n_experts % tp == 0,
                "n_experts %d must be divisible by tp %d (experts shard over "
                "the model axis)", cfg.n_experts, tp,
            )
            mlsl_assert(
                local_tokens % tp == 0,
                "local token count %d (batch/dp * seq/sp) must be divisible by "
                "tp %d for expert-parallel routing", local_tokens, tp,
            )
        self.mesh = self.dist.topology.mesh
        self.session = env.create_session()
        self.session.set_global_minibatch_size(self.batch)

        self.specs = param_specs(cfg)
        params = init_params(jax.random.PRNGKey(seed), cfg)
        self.params = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)),
            params,
            self.specs,
            is_leaf=lambda x: isinstance(x, P),
        )
        self.layers = layer_names(cfg)
        self._replicated = {
            name: all(s == P() for s in jax.tree.leaves(
                self.specs[name], is_leaf=lambda x: isinstance(x, P))
            )
            for name in self.layers
        }

        # local (per-device) flat size of each layer = Operation kernel count
        self.local_counts = {}
        for name in self.layers:
            n = 0
            for leaf, spec in zip(
                jax.tree.leaves(params[name]),
                jax.tree.leaves(self.specs[name], is_leaf=lambda x: isinstance(x, P)),
            ):
                size = int(np.prod(leaf.shape))
                for dim_spec, dim in zip(spec, leaf.shape):
                    if dim_spec == MODEL_AXIS:
                        size //= tp
                n += size
            self.local_counts[name] = n

        self.distributed_update = bool(distributed_update)
        comp = CompressionType(compression) if compression is not None else CompressionType.NONE
        self.ops = {}
        for name in self.layers:
            reg = self.session.create_operation_reg_info(OpType.CC)
            reg.set_name(name)
            reg.add_input(tp, 1)   # placeholder activations (graph comm is unused
            reg.add_output(tp, 1)  # here; grads flow through the parameter sets)
            # MLSL kernel counts are global: the ParameterSet partitions them over the
            # model group, recovering the per-device length local_counts[name]
            reg.add_parameter_set(
                self.local_counts[name] * tp, 1, DataType.FLOAT,
                distributed_update=self.distributed_update,
                compression_type=comp,
            )
            self.ops[name] = self.session.get_operation(
                self.session.add_operation(reg, self.dist)
            )
        self.session.commit()
        self.padded_counts = {
            name: self.ops[name].get_parameter_set(0).get_local_kernel_count()
            for name in self.layers
        }

        self._opt_state = None
        self._du_opt_state = None
        if optimizer is not None:
            topo = self.dist.topology
            if self.distributed_update:
                self._du_opt_state = {
                    n: init_shard_opt_state(
                        topo, optimizer,
                        self.ops[n].get_parameter_set(0).owned_kernel_count,
                    )
                    for n in self.layers
                }
            else:
                self._opt_state = {
                    n: init_shard_opt_state(topo, optimizer, self.local_counts[n])
                    for n in self.layers
                }

        self._grad_fn = self._build_grad_fn()
        self._update_fn = self._build_update_fn()
        self._du_inc_fn = self._build_du_inc_fn() if self.distributed_update else None
        self._du_apply_fn = (
            self._build_du_apply_fn() if self.distributed_update else None
        )
        # When no ParameterSet needs gradient comm (grad group of one: dp=sp=1;
        # TP-only grids qualify — TP grad psums live inside the loss body), fuse
        # loss+grad+update into ONE donated jit: skips the flatten/unflatten
        # round trip through per-layer buffers and lets XLA update params in
        # place — the same shortcut DataParallelTrainer takes.
        needs_comm = any(
            self.ops[n].get_parameter_set(0).need_comm for n in self.layers
        )
        self._needs_comm = needs_comm
        self._fused_fn = (
            self._build_fused_fn()
            if (not needs_comm and not self.distributed_update)
            else None
        )

    # -- compiled programs -------------------------------------------------

    def compiled_step(self, tokens, labels):
        """Lower+compile the fused train step for (tokens, labels) and return
        the jax Compiled object (cost_analysis, memory_analysis, as_text) —
        what chip_smoke.py reads the step's program text from. None on the
        per-layer graph path, where the step is many programs, not one."""
        if self._fused_fn is None:
            return None
        if self.optimizer is None:
            return self._fused_fn.lower(self.params, tokens, labels).compile()
        return self._fused_fn.lower(
            self.params, self._opt_state, tokens, labels
        ).compile()

    def _token_spec(self):
        return P((DATA_AXIS,), (SEQ_AXIS,))

    def _scaled_loss_fn(self):
        """Per-device loss whose autodiff yields d(global CE sum)/d(local leaf).

        SPMD autodiff semantics: differentiating a per-device scalar seeds
        cotangent 1 on EVERY device, so the computed gradient is d(sum of all
        devices' losses)/d(local leaf). The CE loss is replicated over the model
        axis (logits are psum'd), so that sum counts the true loss tp times —
        scale it by 1/tp. The MoE aux loss is per-slice (DEVICE-VARYING over
        model), so the natural sum over model ranks is already the total. The
        synced gradient is later divided by batch*seq_len (the CE-mean
        normalizer); pre-scaling aux by tokens-per-slice makes the effective
        objective mean_CE + moe_aux_weight * mean_aux, independent of token
        count. Shared by the graph and fused paths — the two must not diverge.
        """
        cfg, sp, tp = self.cfg, self.sp, self.tp
        tokens_per_slice = (self.batch // self.dp) * (cfg.seq_len // self.sp) / tp
        aux_w = cfg.moe_aux_weight * tokens_per_slice

        # the model group + config thread the MoE alltoalls through the
        # selection table; the group is a static trace-time object, so the
        # choice is baked per compiled step like every engine decision
        comm = (self.dist.model_group, self.env.config) if self.tp > 1 else None

        def scaled_loss(p, t, l):
            ce, aux = local_loss(p, t, l, cfg, sp, tp, comm=comm)
            return ce / tp + aux_w * aux, ce

        return scaled_loss

    def _flat_opt_layer_update(self, params_sub, state_sub, flat_grad):
        """One layer's optax update on the rank's flat local parameter vector
        (shared by the graph update path and the fused path; the flat state
        layout keeps checkpoints interchangeable between them). Inputs are
        LOCAL (grid dims stripped); returns (new subtree, new local state)."""
        flat_p = jnp.concatenate(
            [l.reshape(-1).astype(jnp.float32)
             for l in jax.tree.leaves(params_sub)]
        )
        updates, ns = self.optimizer.update(flat_grad, state_sub, flat_p)
        new_sub = jax.tree.map(
            lambda p, uu: (p + uu).astype(p.dtype),
            params_sub,
            _unflatten_like(params_sub, updates),
        )
        return new_sub, ns

    def _build_grad_fn(self):
        cfg, sp, tp = self.cfg, self.sp, self.tp
        layers, padded = self.layers, self.padded_counts
        specs = self.specs
        scaled_loss = self._scaled_loss_fn()

        def body(params, tokens, labels):
            (_, loss), grads = jax.value_and_grad(scaled_loss, has_aux=True)(
                params, tokens, labels
            )
            flat = {}
            for name in layers:
                parts = []
                leaf_specs = jax.tree.leaves(
                    specs[name], is_leaf=lambda x: isinstance(x, P)
                )
                for leaf, spec in zip(jax.tree.leaves(grads[name]), leaf_specs):
                    g = leaf.reshape(-1).astype(jnp.float32)
                    if tp > 1 and MODEL_AXIS not in spec:
                        g = lax.psum(g, MODEL_AXIS)
                    parts.append(g)
                g = jnp.concatenate(parts)
                flat[name] = jnp.pad(g, (0, padded[name] - g.shape[0]))[
                    None, None, None, None
                ]
            return loss[None, None, None, None, None], flat

        sm = smap(
            body,
            self.mesh,
            in_specs=(self.specs, self._token_spec(), self._token_spec()),
            out_specs=(_BUF_SPEC, {n: _BUF_SPEC for n in layers}),
            check=False,
        )
        return jax.jit(sm)

    def _build_update_fn(self):
        if self.optimizer is not None:
            return self._build_opt_update_fn()
        layers, lr = self.layers, self.lr
        counts = self.local_counts
        # synced grads are sums of d(CE sum)/dw over all data x seq shards; SGD on the
        # mean loss divides by the total token count
        norm = self.batch * self.cfg.seq_len

        def update(params, reduced):
            def body(params, *flat_grads):
                new = dict(params)
                for name, g in zip(layers, flat_grads):
                    g = g.reshape(-1)[: counts[name]] / norm
                    sub = params[name]
                    new[name] = jax.tree.map(
                        lambda p, gg: (p - lr * gg).astype(p.dtype),
                        sub,
                        _unflatten_like(sub, g),
                    )
                return new

            sm = smap(
                body,
                self.mesh,
                in_specs=(self.specs,) + tuple(_BUF_SPEC for _ in layers),
                out_specs=self.specs,
                check=False,
            )
            return sm(params, *[reduced[n] for n in layers])

        # donated params: in-place HBM update (same contract as the fused path)
        return jax.jit(
            update, donate_argnums=(0,) if self.donate_params else ()
        )

    def _build_fused_fn(self):
        """One donated jit: loss + grads (+ in-body TP psum for replicated
        leaves) + update, bypassing the per-layer buffer round trip. Optimizer
        state keeps the flat per-layer layout of _build_opt_update_fn, so
        checkpoints are interchangeable with the graph path."""

        cfg, sp, tp = self.cfg, self.sp, self.tp
        lr, layers, specs = self.lr, self.layers, self.specs
        norm = self.batch * cfg.seq_len
        optimizer = self.optimizer
        scaled_loss = self._scaled_loss_fn()

        def synced_layer_grads(params, grads, name):
            leaf_specs = jax.tree.leaves(
                specs[name], is_leaf=lambda x: isinstance(x, P)
            )
            out = []
            for leaf, spec in zip(jax.tree.leaves(grads[name]), leaf_specs):
                g = leaf.astype(jnp.float32)
                if tp > 1 and MODEL_AXIS not in spec:
                    g = lax.psum(g, MODEL_AXIS)
                out.append(g / norm)
            return out

        tok = self._token_spec()
        if optimizer is None:
            def body(params, tokens, labels):
                (_, loss), grads = jax.value_and_grad(
                    scaled_loss, has_aux=True
                )(params, tokens, labels)
                new = dict(params)
                for name in layers:
                    subl, treedef = jax.tree.flatten(params[name])
                    gl = synced_layer_grads(params, grads, name)
                    new[name] = jax.tree.unflatten(
                        treedef,
                        [(p - lr * g).astype(p.dtype) for p, g in zip(subl, gl)],
                    )
                return loss[None, None, None, None, None], new

            sm = smap(
                body, self.mesh,
                in_specs=(specs, tok, tok),
                out_specs=(_BUF_SPEC, specs),
                check=False,
            )
            return jax.jit(
                sm, donate_argnums=(0,) if self.donate_params else ()
            )

        def body(params, states, tokens, labels):
            (_, loss), grads = jax.value_and_grad(scaled_loss, has_aux=True)(
                params, tokens, labels
            )
            new, new_states = dict(params), {}
            grid1 = (1,) * NUM_GRID_AXES
            for name in layers:
                gl = jnp.concatenate(
                    [g.reshape(-1) for g in synced_layer_grads(params, grads, name)]
                )
                local = jax.tree.map(
                    lambda l: l.reshape(l.shape[NUM_GRID_AXES:]), states[name]
                )
                new[name], ns = self._flat_opt_layer_update(
                    params[name], local, gl
                )
                new_states[name] = jax.tree.map(
                    lambda l: l.reshape(grid1 + l.shape), ns
                )
            return loss[None, None, None, None, None], new, new_states

        state_specs = {
            n: jax.tree.map(_leaf_buf_spec, self._opt_state[n]) for n in layers
        }
        sm = smap(
            body, self.mesh,
            in_specs=(specs, state_specs, tok, tok),
            out_specs=(_BUF_SPEC, specs, state_specs),
            check=False,
        )
        return jax.jit(
            sm, donate_argnums=(0, 1) if self.donate_params else ()
        )

    def _build_opt_update_fn(self):
        """optax path: each layer's optimization variable is the rank's flat
        local (TP-sharded) parameter vector; state buffers mirror it."""
        layers, counts = self.layers, self.local_counts
        norm = self.batch * self.cfg.seq_len
        optimizer = self.optimizer

        def update(params, states, reduced):
            state_specs = {
                n: jax.tree.map(_leaf_buf_spec, states[n]) for n in layers
            }

            def body(params, states, *flat_grads):
                new, new_states = dict(params), {}
                grid1 = (1,) * NUM_GRID_AXES
                for name, g in zip(layers, flat_grads):
                    gl = g.reshape(-1)[: counts[name]] / norm
                    local = jax.tree.map(
                        lambda l: l.reshape(l.shape[NUM_GRID_AXES:]), states[name]
                    )
                    new[name], ns = self._flat_opt_layer_update(
                        params[name], local, gl
                    )
                    new_states[name] = jax.tree.map(
                        lambda l: l.reshape(grid1 + l.shape), ns
                    )
                return new, new_states

            sm = smap(
                body,
                self.mesh,
                in_specs=(self.specs, state_specs)
                + tuple(_BUF_SPEC for _ in layers),
                out_specs=(self.specs, state_specs),
                check=False,
            )
            return sm(params, states, *[reduced[n] for n in layers])

        return jax.jit(
            update, donate_argnums=(0, 1) if self.donate_params else ()
        )

    def _build_du_inc_fn(self):
        """distributed update: owned-shard gradient -> owned-shard increment."""
        if self.optimizer is not None:
            return build_owned_opt_increment_fn(
                self.mesh, self.optimizer, self.batch * self.cfg.seq_len
            )
        return build_owned_increment_fn(
            self.mesh, self.lr, self.batch * self.cfg.seq_len
        )

    def _build_du_apply_fn(self):
        """Apply all-gathered increments: params += inc (per model shard)."""
        layers, counts = self.layers, self.local_counts

        def body(params, *flat_incs):
            new = dict(params)
            for name, inc in zip(layers, flat_incs):
                inc = inc.reshape(-1)[: counts[name]]
                sub = params[name]
                new[name] = jax.tree.map(
                    lambda p, dd: (p + dd).astype(p.dtype),
                    sub,
                    _unflatten_like(sub, inc),
                )
            return new

        sm = smap(
            body, self.mesh,
            in_specs=(self.specs,) + tuple(_BUF_SPEC for _ in layers),
            out_specs=self.specs,
            check=False,
        )
        jitted = jax.jit(
            sm, donate_argnums=(0,) if self.donate_params else ()
        )

        def apply(params, incs):
            return jitted(params, *[incs[n] for n in layers])

        return apply

    # -- step --------------------------------------------------------------

    def shard_tokens(self, tokens: np.ndarray, labels: np.ndarray):
        if self.cfg.attention == "zigzag" and self.sp > 1:
            # feed the sequence in zigzag order; CE is position-wise, so a
            # consistent (tokens, labels) permutation leaves the loss and the
            # parameter trajectory identical to the contiguous layout
            perm = zigzag_perm(tokens.shape[1], self.sp)
            tokens = np.asarray(tokens)[:, perm]
            labels = np.asarray(labels)[:, perm]
        sharding = NamedSharding(self.mesh, self._token_spec())
        return (
            jax.device_put(jnp.asarray(tokens), sharding),
            jax.device_put(jnp.asarray(labels), sharding),
        )

    def step_accum(self, batches):
        """Gradient accumulation: k local fwd/bwd passes over (tokens, labels)
        pairs, one gradient sync + update (Caffe iter_size pattern). The
        effective objective is the mean over all k micro-batches."""
        mlsl_assert(len(batches) >= 1, "step_accum needs at least one batch")
        if getattr(self, "_accum_fns", None) is None:
            def add(a, b):
                return jax.tree.map(jnp.add, a, b)

            def scale(tree, k):
                return jax.tree.map(lambda g: g / k, tree)

            self._accum_fns = (jax.jit(add), jax.jit(scale, static_argnums=1))
        add_fn, scale_fn = self._accum_fns
        total, loss_sum = None, None
        for tokens, labels in batches:
            loss, grads = self._grad_fn(self.params, tokens, labels)
            total = grads if total is None else add_fn(total, grads)
            loss_sum = loss if loss_sum is None else loss_sum + loss
        k = len(batches)
        return self._sync_and_update(scale_fn(total, k), loss_sum) / k

    def step(self, tokens, labels):
        if self._fused_fn is not None:
            if self.optimizer is None:
                loss, self.params = self._fused_fn(self.params, tokens, labels)
            else:
                loss, self.params, self._opt_state = self._fused_fn(
                    self.params, self._opt_state, tokens, labels
                )
            return jnp.sum(loss[:, :, :, 0]) / (self.batch * self.cfg.seq_len)
        loss, grads = self._grad_fn(self.params, tokens, labels)
        return self._sync_and_update(grads, loss)

    def _sync_and_update(self, grads, loss):
        for name in reversed(self.layers):
            self.ops[name].get_parameter_set(0).start_gradient_comm(grads[name])
        if self.distributed_update:
            # ZeRO-1: update only the owned shard, all-gather the increments
            incs = {}
            for name in self.layers:
                ps = self.ops[name].get_parameter_set(0)
                owned = ps.wait_gradient_comm()
                src = grads[name] if owned is None else owned
                if self.optimizer is None:
                    inc = self._du_inc_fn(src)
                else:
                    inc, self._du_opt_state[name] = self._du_inc_fn(
                        src, self._du_opt_state[name]
                    )
                if owned is None:  # degenerate grad group: full local increment
                    incs[name] = inc
                else:
                    ps.start_increment_comm(inc)
            for name in self.layers:
                ps = self.ops[name].get_parameter_set(0)
                inc = ps.wait_increment_comm()
                if inc is not None:
                    incs[name] = inc
            self.params = self._du_apply_fn(self.params, incs)
        else:
            reduced = {}
            for name in self.layers:
                ps = self.ops[name].get_parameter_set(0)
                out = ps.wait_gradient_comm()
                reduced[name] = out if out is not None else grads[name]
            if self.optimizer is None:
                self.params = self._update_fn(self.params, reduced)
            else:
                self.params, self._opt_state = self._update_fn(
                    self.params, self._opt_state, reduced
                )
        # loss buffer holds per-(data,seq)-shard partial CE sums (replicated over the
        # model axis -> take slot 0); mean = total / (batch * seq_len)
        return jnp.sum(loss[:, :, :, 0]) / (self.batch * self.cfg.seq_len)
