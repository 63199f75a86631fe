"""Plain reference of the GPT-2 block as the configuration states it:
learned positions, pre-LayerNorm, causal attention, tanh-GELU MLP, an output
head kept apart from the token embedding and no bias on the attention
projections (``assumed`` in the configuration's file). ``jax.numpy`` in
float32 with every product at ``highest`` precision; no kernel, no cache, no
batching tricks. It imports nothing of the program and makes its own weights
from the seed; the tree it returns is the layout the program's entry points
take (``embed.tok``, ``blk3.attn.wqkv`` ...), which is the interface.

``precision``: 'f32' is the reference. 'fp8' is the control of "How correct is
decided": every matrix product takes its two operands rounded to
float8_e4m3 with one scale a tensor, the step below the bfloat16 the
configuration states. 'bf16' rounds them to bfloat16 (what the program is
stated to do; used to see where the lower readings come from).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from perf.lib.lowprec import leaf_paths, product  # noqa: F401 (leaf_paths: the adapters' interface)

HI = jax.lax.Precision.HIGHEST


def shapes(cfg):
    d, h, dh = cfg["n_embd"], cfg["n_head"], cfg["head_dim"]
    f = cfg["mlp_ratio"] * d
    out = {"embed": {"tok": ((cfg["vocab_size"], d), "normal"),
                     "pos": ((cfg["n_positions"], d), "normal")},
           "final": {"ln_scale": ((d,), "ones"), "ln_bias": ((d,), "zeros"),
                     "head": ((d, cfg["vocab_size"]), "normal")}}
    for i in range(cfg["n_layer"]):
        out[f"blk{i}.ln"] = {k: ((d,), "ones" if k.endswith("scale") else "zeros")
                             for k in ("ln1_scale", "ln1_bias",
                                       "ln2_scale", "ln2_bias")}
        out[f"blk{i}.attn"] = {"wqkv": ((d, 3, h, dh), "normal"),
                               "wo": ((h, dh, d), "normal")}
        out[f"blk{i}.mlp"] = {"w1": ((d, f), "normal"), "b1": ((f,), "zeros"),
                              "w2": ((f, d), "normal"), "b2": ((d,), "zeros")}
    return out


@functools.partial(jax.jit, static_argnums=(1,))
def _init(key, cfg_items):
    cfg = dict(cfg_items)
    spec = shapes(cfg)
    leaves, treedef = jax.tree.flatten(
        spec, is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[1], str))
    keys = jax.random.split(key, len(leaves))
    made = []
    for k, (shape, kind) in zip(keys, leaves):
        if kind == "normal":
            made.append(jax.random.normal(k, shape, jnp.float32)
                        * cfg["initializer_range"])
        elif kind == "ones":
            made.append(jnp.ones(shape, jnp.float32))
        else:
            made.append(jnp.zeros(shape, jnp.float32))
    return jax.tree.unflatten(treedef, made)


def model_cfg(config):
    """The numbers of the configuration's file that fix the mathematics."""
    keys = ("n_embd", "n_head", "head_dim", "n_layer", "n_positions",
            "vocab_size", "mlp_ratio", "initializer_range",
            "layer_norm_epsilon")
    return {k: config[k] for k in keys}


def init_params(seed, config):
    """Weights from the seed, on the device, in one jitted call, float32 (the
    type the program trains and serves them in)."""
    cfg = model_cfg(config)
    key = jax.random.PRNGKey(int(seed) % (2**31 - 1))
    return _init(key, tuple(sorted(cfg.items())))


def _mm(eq, a, b, precision):
    return product(lambda x, y: jnp.einsum(
        eq, x, y, precision=HI, preferred_element_type=jnp.float32),
        a, b, precision)


def _ln(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _block(h, lnp, ap, mp, cfg, precision):
    eps = cfg["layer_norm_epsilon"]
    s = h.shape[1]
    a = _ln(h, lnp["ln1_scale"], lnp["ln1_bias"], eps)
    qkv = _mm("bsd,dchx->cbhsx", a, ap["wqkv"], precision)
    q, k, v = qkv[0], qkv[1], qkv[2]
    scores = _mm("bhqx,bhkx->bhqk", q, k, precision) / np.sqrt(cfg["head_dim"])
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    o = _mm("bhqk,bhkx->bhqx", p, v, precision)
    h = h + _mm("bhsx,hxd->bsd", o, ap["wo"], precision)
    a = _ln(h, lnp["ln2_scale"], lnp["ln2_bias"], eps)
    f = jax.nn.gelu(_mm("bsd,df->bsf", a, mp["w1"], precision) + mp["b1"],
                    approximate=True)
    return h + _mm("bsf,fd->bsd", f, mp["w2"], precision) + mp["b2"]


def logits(params, tokens, cfg, precision="f32", remat=False):
    """tokens (B, S) -> logits (B, S, V), float32."""
    s = tokens.shape[1]
    h = params["embed"]["tok"][tokens] + params["embed"]["pos"][:s][None]
    blk = functools.partial(_block, cfg=cfg, precision=precision)
    if remat:
        blk = jax.checkpoint(blk)
    for i in range(cfg["n_layer"]):
        h = blk(h, params[f"blk{i}.ln"], params[f"blk{i}.attn"],
                params[f"blk{i}.mlp"])
    fin = params["final"]
    h = _ln(h, fin["ln_scale"], fin["ln_bias"], cfg["layer_norm_epsilon"])
    return _mm("bsd,dv->bsv", h, fin["head"], precision)


def ce_sum(params, tokens, labels, cfg, precision):
    lg = logits(params, tokens, cfg, precision, remat=True)
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], axis=-1))


# -- training: three steps of AdamW as the traffic file states it -----------

def leaf_norms(tree):
    return np.asarray(jax.device_get(_norms(tree)), np.float64)


@jax.jit
def _norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32))))
                      for l in jax.tree.leaves(tree)])


@jax.jit
def _diff_norms(a, b):
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32) - y.astype(jnp.float32))))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])


def diff_norms(a, b):
    return np.asarray(jax.device_get(_diff_norms(a, b)), np.float64)


def train(seed, config, traffic, batches, precision="f32", keep_rows=None,
          steps=3):
    """Follow the first ``steps`` steps on ``batches`` [(tokens, labels)].
    ``keep_rows``: a planted fault, the mean taken over the first rows only.
    -> dict(losses, grad_norms (step 1, per leaf), delta_norms (after the
    steps, per leaf), paths)."""
    cfg = model_cfg(config)
    opt = traffic["optimizer"]
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    lr, wd = opt["learning_rate"], opt["weight_decay"]
    chunk = traffic.get("reference_rows", 1)

    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, t, l: ce_sum(p, t, l, cfg, precision)))

    @functools.partial(jax.jit, donate_argnums=(0,))
    def add(a, b):
        return jax.tree.map(jnp.add, a, b)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def scale(g, k):
        return jax.tree.map(lambda x: x * k, g)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def adam(p, m, v, g, t):
        m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
        v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        p = jax.tree.map(
            lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                                      + wd * p), p, m, v)
        return p, m, v

    p = init_params(seed, config)
    paths = leaf_paths(p)
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    losses, grad_norms = [], None
    for step in range(steps):
        toks, labels = batches[step]
        if keep_rows is not None:
            toks, labels = toks[:keep_rows], labels[:keep_rows]
        n_tok = toks.shape[0] * toks.shape[1]
        total, grads = 0.0, None
        for i in range(0, toks.shape[0], chunk):
            ce, g = grad_fn(p, jnp.asarray(toks[i:i + chunk]),
                            jnp.asarray(labels[i:i + chunk]))
            total += float(ce)
            grads = g if grads is None else add(grads, g)
        grads = scale(grads, jnp.float32(1.0 / n_tok))
        losses.append(total / n_tok)
        if step == 0:
            grad_norms = leaf_norms(grads)
        p, m, v = adam(p, m, v, grads, jnp.float32(step + 1))
    del m, v, grads
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": diff_norms(p, init_params(seed, config)),
            "paths": paths}


# -- serving: one pass over a prompt with its served tokens ------------------

def served_gaps(params, config, sequences, precision="f32", control=None):
    """``sequences``: [(prompt ids, served ids)]. For each served token, how
    far its logit lies below the reference's best at that position. With
    ``control`` ('fp8'), the token judged at each position is the one that the
    lower precision puts first there, over the same prompt and tokens.
    -> list of np arrays of gaps, one per sequence."""
    cfg = model_cfg(config)
    n_pos = cfg["n_positions"]

    # the weights are an argument: closed over, they would be compiled into
    # the program as gigabytes of constants that no compile cache holds
    @jax.jit
    def run(params, tokens):
        return logits(params, tokens[None], cfg, precision)[0]

    @jax.jit
    def run_control(params, tokens):
        return jnp.argmax(logits(params, tokens[None], cfg, control)[0], axis=-1)

    out = []
    for prompt, served in sequences:
        seq = np.concatenate([prompt, served]).astype(np.int32)
        n = min(seq.size, n_pos)
        padded = np.zeros((n_pos,), np.int32)
        padded[:n] = seq[:n]
        lg = run(params, jnp.asarray(padded))
        # position i predicts token i + 1: the first served token is
        # predicted at the prompt's last position
        first = prompt.size - 1
        count = min(len(served), n_pos - prompt.size + 1)
        rows = lg[first:first + count]
        if control is None:
            judged = jnp.asarray(np.asarray(served[:count], np.int32))
        else:
            judged = run_control(params, jnp.asarray(padded))[first:first + count]
        best = jnp.max(rows, axis=-1)
        got = jnp.take_along_axis(rows, judged[:, None], axis=-1)[:, 0]
        out.append(np.asarray(best - got, np.float64))
    return out
