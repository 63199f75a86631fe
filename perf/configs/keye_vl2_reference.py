"""Plain reference of the language model of Keye-VL-2.0-30B-A3B as
perf/configs/keye-vl2-30b-a3b-pp8.json states it: RMS norm, grouped-query
attention (32 query heads over 4 key-value heads of 128) with a per-head RMS
norm on queries and keys and rotate-half rotary positions, a lightning
indexer (16 heads of 64 over one shared index key a token) whose 2,048
highest-scored positions a query attends to, 128 gated-SiLU experts of width
768 with the 8 most probable a token renormalised, an untied head.
``jax.numpy`` in float32 with every product at ``highest`` precision; no
cache, no kernel, no batching. It imports nothing of the program and makes
its own weights from the seed; the tree it returns is the layout the
program's entry points take (``embed.tok``, ``blk3.attn.wq`` ...), which is
the interface.

Departures from the published model, each under ``assumed`` in the
configuration's file: the vision tower is left out (text only, so the three
``mrope_section`` streams coincide and the rotation is the plain rotary
one); the per-head norm on q and k (the Qwen3-MoE block these keys
describe); the indexer's key norm (a LayerNorm) and rotary over its whole
width with the model's theta; ``q_chunk_size`` / ``kv_chunk_size`` are tile
sizes and change no number; weights normal 0.02, drawn layer by layer from
the seed and rounded to bfloat16.

A layer at a time: the weights at rest are bfloat16 (8.15 GiB at the
published widths), one layer is cast to float32 (2.5 GB) while it runs, and
a sequence's queries go through attention and its tokens through the experts
in blocks, so that the 33k x 33k scores never exist at once.

``precision``: 'f32' is the reference. 'fp8' is the control of "How correct is
decided": every matrix product takes its two operands rounded to
float8_e4m3 with one scale a tensor, the step below the bfloat16 the
configuration states. 'bf16' rounds them to bfloat16.
"""

import functools
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

from perf.lib.lowprec import leaf_paths, product  # noqa: F401 (leaf_paths: the adapters' interface)

HI = jax.lax.Precision.HIGHEST
KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "num_hidden_layers", "vocab_size", "moe_intermediate_size",
        "num_experts", "num_experts_per_tok", "rms_norm_eps", "rope_theta",
        "initializer_range")


def model_cfg(config):
    """The numbers of the configuration's file that fix the mathematics."""
    cfg = {k: config[k] for k in KEYS}
    sa = config["sa_config"]
    cfg.update(index_heads=sa["indexer_num_heads"],
               index_dim=sa["indexer_head_dim"], topk=sa["topk"],
               weights_dtype=config["weights_dtype"])
    return cfg


def layer_shapes(cfg):
    d, h, g, dh = (cfg["hidden_size"], cfg["num_attention_heads"],
                   cfg["num_key_value_heads"], cfg["head_dim"])
    j, di = cfg["index_heads"], cfg["index_dim"]
    e, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    return {
        "ln": {"ln1_scale": ((d,), "ones"), "ln2_scale": ((d,), "ones")},
        "attn": {"wq": ((d, h, dh), "normal"), "wkv": ((d, 2, g, dh), "normal"),
                 "wo": ((h, dh, d), "normal"),
                 "q_norm": ((dh,), "ones"), "k_norm": ((dh,), "ones"),
                 "wiq": ((d, j, di), "normal"), "wik": ((d, di), "normal"),
                 "wiw": ((d, j), "normal"),
                 "ik_scale": ((di,), "ones"), "ik_bias": ((di,), "zeros")},
        "mlp": {"wr": ((d, e), "normal"), "wgu": ((e, d, 2 * f), "normal"),
                "wd": ((e, f, d), "normal")},
    }


def _draw(key, spec, std, dtype):
    """Leaves of ``spec`` from ``key``: matrices normal ``std`` rounded to
    bfloat16 and kept in ``dtype``; scales 1 and biases 0 in float32."""
    leaves, treedef = jax.tree.flatten(
        spec, is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[1], str))
    keys = jax.random.split(key, len(leaves))
    made = []
    for k, (shape, kind) in zip(keys, leaves):
        if kind == "normal":
            made.append((jax.random.normal(k, shape, jnp.float32) * std)
                        .astype(jnp.bfloat16).astype(dtype))
        else:
            made.append(jnp.full(shape, 1.0 if kind == "ones" else 0.0,
                                 jnp.float32))
    return jax.tree.unflatten(treedef, made)


@functools.partial(jax.jit, static_argnums=(1,))
def _init(key, cfg_items):
    cfg = dict(cfg_items)
    std, dtype = cfg["initializer_range"], jnp.dtype(cfg["weights_dtype"])
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    ends = _draw(jax.random.fold_in(key, 1 << 20), {
        "embed": {"tok": ((v, d), "normal")},
        "final": {"ln_scale": ((d,), "ones"), "head": ((d, v), "normal")}},
        std, dtype)
    params = dict(ends)
    for i in range(cfg["num_hidden_layers"]):
        layer = _draw(jax.random.fold_in(key, i), layer_shapes(cfg), std, dtype)
        for part, leaves in layer.items():
            params[f"blk{i}.{part}"] = leaves
    return params


def init_params(seed, config):
    """Weights from the seed, on the device, in one jitted call, in the type
    the configuration keeps them at rest; each layer from a key of its own
    (the seed's key folded with the layer's index)."""
    cfg = model_cfg(config)
    key = jax.random.PRNGKey(int(seed) % (2**31 - 1))
    return _init(key, tuple(sorted(cfg.items())))


# -- the layer, as ISSUE 29 writes it ------------------------------------------

def _mm(eq, a, b, precision):
    return product(lambda x, y: jnp.einsum(
        eq, x, y, precision=HI, preferred_element_type=jnp.float32),
        a, b, precision)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * scale


def _layernorm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _rotary(x, positions, theta):
    """Rotate-half over the whole trailing axis; x (S, ..., D)."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) * 2.0
                           / x.shape[-1]))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _rows(s):
    """Queries (and tokens) a block: so that a block's (rows, 32 heads, S)
    scores stay near a gigabyte."""
    rows = 512
    while rows > 32 and rows * s > (1 << 23):
        rows //= 2
    return min(rows, s)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _layer(h, p, cfg_items, precision, with_selected):
    """One layer over one sequence h (S, d); S a whole number of blocks.
    -> (h, selected (S, S) bool or None)."""
    cfg = dict(cfg_items)
    eps, theta, topk = cfg["rms_norm_eps"], cfg["rope_theta"], cfg["topk"]
    hq, g, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    s = h.shape[0]
    rows = _rows(s)
    pos = jnp.arange(s)
    ln, ap, mp = p["ln"], p["attn"], p["mlp"]
    mm = functools.partial(_mm, precision=precision)

    x = _rms(h, ln["ln1_scale"], eps)
    q = mm("sd,dhx->shx", x, ap["wq"])
    kv = mm("sd,dchx->cshx", x, ap["wkv"])
    k, v = kv[0], kv[1]
    q = _rotary(_rms(q, ap["q_norm"], eps), pos, theta)
    k = _rotary(_rms(k, ap["k_norm"], eps), pos, theta)
    qi = _rotary(mm("sd,djx->sjx", x, ap["wiq"]), pos, theta)
    ki = _rotary(_layernorm(mm("sd,dx->sx", x, ap["wik"]),
                            ap["ik_scale"], ap["ik_bias"], eps), pos, theta)
    wi = mm("sd,dj->sj", x, ap["wiw"])
    k_rep = jnp.repeat(k, hq // g, axis=1)          # head i reads head i // 8
    v_rep = jnp.repeat(v, hq // g, axis=1)
    top = min(topk, s)

    def attend(at):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, at, rows)  # noqa: E731
        qpos = at + jnp.arange(rows)
        score = jnp.sum(jax.nn.relu(mm("qjx,sx->qjs", sl(qi), ki))
                        * sl(wi)[:, :, None], axis=1)            # (rows, S)
        causal = pos[None, :] <= qpos[:, None]
        # both zeros as +0: a sum of w * relu(.) comes out as either
        score = jnp.where(causal, jnp.where(score == 0, 0.0, score), -jnp.inf)
        # the min(topk, t + 1) highest; lax.top_k is exact and puts the lower
        # position first among equals
        _, idx = jax.lax.top_k(score, top)
        keep = jnp.arange(top)[None, :] < jnp.minimum(topk, qpos + 1)[:, None]
        chosen = jnp.zeros((rows, s), bool).at[
            jnp.arange(rows)[:, None], idx].set(keep)
        sc = mm("qhx,shx->hqs", sl(q), k_rep) / np.sqrt(dh)
        pr = jax.nn.softmax(jnp.where(chosen[None], sc, -jnp.inf), axis=-1)
        return mm("hqs,shx->qhx", pr, v_rep), chosen

    o, chosen = jax.lax.map(attend, jnp.arange(0, s, rows))
    h = h + mm("shx,hxd->sd", o.reshape(s, hq, dh), ap["wo"])

    y = _rms(h, ln["ln2_scale"], eps)
    f = cfg["moe_intermediate_size"]

    def experts(at):
        yb = jax.lax.dynamic_slice_in_dim(y, at, rows)
        prob = jax.nn.softmax(mm("td,de->te", yb, mp["wr"]), axis=-1)
        topv, topi = jax.lax.top_k(prob, cfg["num_experts_per_tok"])
        weight = jnp.zeros_like(prob).at[
            jnp.arange(rows)[:, None], topi].set(
                topv / jnp.sum(topv, axis=-1, keepdims=True))
        # every expert on every token, the unchosen at weight nought: the
        # per-token definition, sixteen times the work a server does
        gu = mm("td,edf->tef", yb, mp["wgu"])
        act = jax.nn.silu(gu[..., :f]) * gu[..., f:]
        out = _mm("tef,efd->ted", act, mp["wd"], precision)
        return jnp.sum(out * weight[:, :, None], axis=1)

    h = h + jax.lax.map(experts, jnp.arange(0, s, rows)).reshape(s, -1)
    return h, (chosen.reshape(s, s) if with_selected else None)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(h, fin, at, cfg_items, precision):
    cfg = dict(cfg_items)
    x = _rms(h[at], fin["ln_scale"], cfg["rms_norm_eps"])
    return _mm("sd,dv->sv", x, fin["head"], precision)


def logits_at(params, cfg, tokens, at, precision="f32", with_selected=False):
    """The logits at positions ``at`` of one sequence ``tokens``: (len(at),
    V) float32, and with ``with_selected`` the selected sets a layer, (L, S,
    S) bool. One layer's weights are cast to float32 at a time."""
    tokens = np.asarray(tokens, np.int32)
    n = tokens.size
    rows = _rows(n)
    if n > 4096:                     # few distinct shapes: few compilations
        rows = 8192
    s = -(-n // rows) * rows
    items = tuple(sorted(cfg.items()))
    padded = np.zeros((s,), np.int32)
    padded[:n] = tokens
    h = params["embed"]["tok"][jnp.asarray(padded)].astype(jnp.float32)
    selected = []
    for i in range(cfg["num_hidden_layers"]):
        layer = jax.tree.map(
            lambda x: x.astype(jnp.float32),
            {part: params[f"blk{i}.{part}"] for part in ("ln", "attn", "mlp")})
        h, chosen = _layer(h, layer, items, precision, with_selected)
        del layer
        if with_selected:
            selected.append(np.asarray(chosen)[:n, :n])
    fin = jax.tree.map(lambda x: x.astype(jnp.float32), params["final"])
    out = _head(h, fin, jnp.asarray(np.asarray(at, np.int32)), items, precision)
    return (out, np.stack(selected)) if with_selected else out


# -- serving: one pass over a prompt with its served tokens ------------------

#: the least mean gap the stated precision is taken to read (a run whose
#: logits are so far apart that nothing rounds to another token)
FLOOR = 0.002


def token_gaps(params, config, sequences, judges, precision="f32"):
    """``sequences``: [(prompt ids, served ids)]. For each served position,
    how far the judged token's logit lies below the reference's best there;
    a judge is None (the served token itself) or a precision ('bf16',
    'fp8': the token which the reference computed in that precision puts
    first, over the same prompt and tokens). -> {judge: list of np arrays of
    gaps, one per sequence}."""
    cfg = model_cfg(config)
    out = {judge: [] for judge in judges}
    for prompt, served in sequences:
        seq = np.concatenate([prompt, served]).astype(np.int32)
        # position i predicts token i + 1: the first served token is
        # predicted at the prompt's last position
        at = np.arange(prompt.size - 1, prompt.size - 1 + len(served))
        rows = logits_at(params, cfg, seq[:-1], at, precision)
        best = jnp.max(rows, axis=-1)
        for judge in judges:
            judged = jnp.asarray(np.asarray(served, np.int32)) \
                if judge is None else jnp.argmax(
                    logits_at(params, cfg, seq[:-1], at, judge), axis=-1)
            got = jnp.take_along_axis(rows, judged[:, None], axis=-1)[:, 0]
            out[judge].append(np.asarray(best - got, np.float64))
    return out


def served_gaps(params, config, sequences, precision="f32", control=None):
    """What the cell's limit is held against: the mean gap of the served
    tokens (with ``control`` 'fp8': of the tokens that the reference in
    float8 puts first) over the mean gap of the tokens that the reference
    puts first when it is computed in the precision the configuration
    states, bfloat16 operands into every product, over the same prompts and
    tokens; one number, as the one array of one sequence that the harness
    takes the widest of.

    Why not the widest single token, as GPT-2's reference gives: with 8 of
    128 experts chosen by a router over random weights, a token's eighth and
    ninth choices lie 0.04 apart in the router's logits, bfloat16's rounding
    swaps them now and then, and a swap moves that token's logits by tenths.
    The widest token of a sound run read 0.17 to 0.38 on the chip and the
    float8 control's 0.40 to 0.75; means over blocks or sequences overlapped
    as well, because how often a token rounds to another follows the weights
    that a seed draws (8 times from run to run) more than the precision.
    On the same sequences the two precisions stand 4 to 8 times apart: so
    the run is measured in units of what bfloat16 itself does to these
    sequences, and a sound program reads about 1 (PERF.md section 2, PR 29,
    has every reading). The tokens' own gaps go to standard error, for the
    reader of a run that fails."""
    judge = control
    gaps = token_gaps(params, config, sequences, [judge, "bf16"], precision)
    run, stated = (np.concatenate(gaps[j]) for j in (judge, "bf16"))
    ratio = float(run.mean() / max(stated.mean(), FLOOR))
    print(json.dumps({"served_token_gaps": {
        "judged": judge or "served", "ratio": ratio, "floor": FLOOR,
        "mean": [float(run.mean()), float(stated.mean())],
        "widest": [float(run.max()), float(stated.max())],
        "differ_share": [float((run > 0).mean()), float((stated > 0).mean())],
        "tokens": [[round(float(x), 4) for x in g] for g in gaps[judge]],
        "stated": [[round(float(x), 4) for x in g] for g in gaps["bf16"]]}}),
        file=sys.stderr, flush=True)
    # one array a sequence, as long as its served tokens, so that the record
    # counts what was checked; every entry is the run's one number
    return [np.full(len(g), ratio) for g in gaps[judge]]
