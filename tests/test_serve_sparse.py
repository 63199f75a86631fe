"""The serving path of a block that is not GPT-2's: RMS norm, rotary
positions, grouped-query heads under a learned top-k indexer with a paged
index-key pool, dropless experts, prompts prefilled in chunks. Small sizes on
the CPU, seeded random weights, the float32 program against the plain
reference that the benchmark keeps beside the configuration
(perf/configs/keye_vl2_reference.py, which imports nothing of the program):
logits, selected sets, the expert layer, the pools' accounting, and the
precision the configuration states against the one below it."""

import copy
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from perf.lib import manifest  # noqa: E402

from mlsl_tpu.models import moe, transformer as tfm  # noqa: E402
from mlsl_tpu.ops import paged_attention  # noqa: E402
from mlsl_tpu.serve import InferenceEngine, kv_cache as kvc  # noqa: E402

REF = manifest.load_module(manifest.PERF / "configs" / "keye_vl2_reference.py")
ADAPTER = manifest.load_module(manifest.PERF / "adapters" / "keye.py")
CONFIG = {**manifest.read_json(
    manifest.PERF / "configs" / "keye-vl2-30b-a3b-pp8.json"),
    **manifest.read_json(
        manifest.PERF / "configs" / "tiny" / "keye-vl2-30b-a3b-pp8.json")}
TOPK = CONFIG["sa_config"]["topk"]
PAGE, CTX = 16, 128
#: root mean square gap of the toy's logits (0.16 wide) from the reference's:
#: the bfloat16 program reads 0.0167, the reference in float8 0.0354
BF16_TOLERANCE = 0.025


def toy(layers=2, **more):
    config = {**CONFIG, "num_hidden_layers": layers, **more}
    return config, ADAPTER.program_config(config, {"max_total_tokens": CTX})


class Paged:
    """The pools and one page table a sequence, driven by hand: what the
    engine does around the two programs, with the logits kept."""

    def __init__(self, cfg, params, chunk, batch=2):
        self.cfg, self.params, self.chunk, self.batch = cfg, params, chunk, batch
        self.cache = kvc.PagedKVCache(cfg, page_elems=PAGE, budget_mb=4,
                                      max_len=CTX)
        n = self.cache.num_pages + 1
        dt = jnp.dtype(cfg.kv_dtype)
        self.k = jnp.zeros((cfg.n_blocks, n, PAGE, cfg.kv_heads * cfg.head_dim), dt)
        self.v = jnp.zeros_like(self.k)
        self.i = jnp.zeros((cfg.n_blocks, n, PAGE, cfg.index_row), dt)
        self._chunk = jax.jit(lambda p, t, o, m, tb, k, v, i: tfm.chunk_local(
            p, t, o, m, tb, k, v, i, cfg, 1))
        self._decode = jax.jit(lambda p, s, tb, k, v, i: tfm.decode_local(
            p, s, tb, k, v, cfg, 1, ipool=i))

    def prefill(self, seq_id, tokens):
        """-> the logits after each chunk's last token, [(position, logits)]."""
        assert self.cache.admit(seq_id, len(tokens) + 1)
        table = np.asarray(self.cache.table_padded(seq_id), np.int32)
        out = []
        for at in range(0, len(tokens), self.chunk):
            n = min(self.chunk, len(tokens) - at)
            padded = np.zeros((self.chunk,), np.int32)
            padded[:n] = tokens[at:at + n]
            logits, counts, self.k, self.v, self.i = self._chunk(
                self.params, padded, np.int32(at), np.int32(n), table,
                self.k, self.v, self.i)
            assert int(counts[1]) == n * self.cfg.moe_top_k * self.cfg.n_blocks
            out.append((at + n - 1, np.asarray(logits)))
        return out

    def decode(self, feeds):
        """One step; ``feeds``: [(seq_id, token, position)] a slot. -> logits
        (len(feeds), V)."""
        slots = np.zeros((3, self.batch), np.int32)
        tables = np.zeros((self.batch, self.cache.max_pages_per_seq), np.int32)
        for b, (seq_id, token, position) in enumerate(feeds):
            assert self.cache.extend(seq_id, position + 1)
            slots[:, b] = (token, position, self.cache.page_of(seq_id, position))
            tables[b] = self.cache.table_padded(seq_id)
        logits, self.k, self.v, self.i, counts = self._decode(
            self.params, slots, tables, self.k, self.v, self.i)
        assert int(counts[1]) == len(feeds) * self.cfg.moe_top_k * self.cfg.n_blocks
        return np.asarray(logits)[:len(feeds)]


def sequences(seed, lengths, vocab):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=n).astype(np.int32) for n in lengths]


# -- (a) logits: chunks, then decode through the paged cache -----------------

@pytest.mark.parametrize("layers,chunk", [
    (1, 24), (2, 24), (2, 32), (2, 40)],
    ids=["one_layer", "chunk_off_page", "chunk_on_page", "chunk_over_two_pages"])
def test_chunked_prefill_and_paged_decode_match_the_reference(layers, chunk):
    """Contexts several times the toy ``topk`` (16), two sequences of
    different lengths side by side in the decode batch, chunk boundaries that
    do (32) and do not (24, 40) fall on a page boundary (16). Every chunk's
    last logits and every decode step's are the reference's full forward
    pass over the same tokens, to float32 rounding."""
    config, cfg = toy(layers)
    params = REF.init_params(11, config)
    rcfg = REF.model_cfg(config)
    prompts = sequences(3, (75, 41), cfg.vocab)
    extra = sequences(4, (9, 9), cfg.vocab)
    run = Paged(cfg, params, chunk)
    full = [np.concatenate([p, e]) for p, e in zip(prompts, extra)]
    want = [np.asarray(REF.logits_at(params, rcfg, f, np.arange(len(f))))
            for f in full]
    for s, prompt in enumerate(prompts):
        for position, logits in run.prefill(s, prompt):
            np.testing.assert_allclose(logits, want[s][position],
                                       rtol=0, atol=2e-5)
    for k in range(len(extra[0])):
        feeds = [(s, int(extra[s][k]), len(prompts[s]) + k) for s in range(2)]
        got = run.decode(feeds)
        for s, (_, _, position) in enumerate(feeds):
            np.testing.assert_allclose(got[s], want[s][position],
                                       rtol=0, atol=2e-5)
    assert max(len(f) for f in full) > 4 * TOPK
    run.cache.check()


# -- (b) the selected sets ------------------------------------------------------

def test_the_selected_sets_are_the_references(monkeypatch):
    """Layer by layer, query by query: what the chunk program lets a query
    read and what the decode program selects are the reference's sets."""
    config, cfg = toy(2)
    params = REF.init_params(5, config)
    prompt, extra = sequences(8, (70, 6), cfg.vocab)
    _, want = REF.logits_at(params, REF.model_cfg(config),
                            np.concatenate([prompt, extra]),
                            [0], with_selected=True)
    seen = {"chunk": [], "decode": []}
    select, top = paged_attention.select_in_context, \
        paged_attention.exact_top_k_mask

    def spy_chunk(scores, room, n_keys, topk):
        out = select(scores, room, n_keys, topk)
        jax.debug.callback(lambda m: seen["chunk"].append(np.asarray(m)), out,
                           ordered=True)
        return out

    def spy_decode(scores, k):
        out = top(scores, k)
        if scores.shape[0] == 2:        # the decode batch, not a chunk
            jax.debug.callback(lambda m: seen["decode"].append(np.asarray(m)),
                               out, ordered=True)
        return out

    monkeypatch.setattr(paged_attention, "select_in_context", spy_chunk)
    monkeypatch.setattr(paged_attention, "exact_top_k_mask", spy_decode)
    run = Paged(cfg, params, chunk=24)
    run.prefill(0, prompt)
    for k, tok in enumerate(extra):
        run.decode([(0, int(tok), len(prompt) + k)])
    jax.effects_barrier()
    n = len(prompt)
    chunks = [(at, min(24, n - at)) for at in range(0, n, 24)]
    assert len(seen["chunk"]) == 2 * len(chunks)
    for c, (at, m) in enumerate(chunks):
        for layer in range(2):
            got = seen["chunk"][2 * c + layer][:m, :n + len(extra)]
            assert (got == want[layer][at:at + m]).all(), (c, layer)
    assert len(seen["decode"]) == 2 * len(extra)
    for k in range(len(extra)):
        for layer in range(2):
            got = seen["decode"][2 * k + layer][0, :n + len(extra)]
            assert (got == want[layer][n + k]).all(), (k, layer)
            assert got.sum() == TOPK


@pytest.mark.parametrize("k", [1, 7, 50, 150])
def test_exact_top_k_keeps_the_lower_position_among_equals(k):
    rng = np.random.default_rng(k)
    scores = rng.integers(-3, 4, size=(6, 200)).astype(np.float32)
    scores[:, 150:] = -np.inf
    scores[2, :10] = -0.0               # either zero is the same score
    got = np.asarray(jax.jit(paged_attention.exact_top_k_mask)(
        jnp.asarray(scores), jnp.full((6,), k)))
    for row in range(6):
        _, idx = jax.lax.top_k(jnp.asarray(scores[row]) + 0.0, k)
        want = np.zeros(200, bool)
        want[np.asarray(idx)] = True
        assert (got[row] == want).all()


def _top_k_oracle(scores, k):
    """Stable descending order, ties to the lower column, both zeros as +0."""
    want = np.zeros(scores.shape, bool)
    for r, row in enumerate(scores):
        want[r, np.argsort(-(row + 0.0), kind="stable")[:k[r]]] = True
    return want


def _top_k_case(name):
    """-> (scores (R, S) f32, k (R,)): what ``exact_top_k_mask`` has to get
    right, at widths of one block of columns and of several."""
    rng = np.random.default_rng(len(name))
    if name == "random":
        s = rng.normal(size=(8, 384)).astype(np.float32)
        return s, rng.integers(1, 384, size=8)
    if name == "three_values":          # ties at the k-th value in every row
        s = rng.integers(0, 3, size=(8, 384)).astype(np.float32)
        return s, np.array([1, 5, 100, 128, 129, 200, 300, 383])
    if name == "all_equal":
        return np.full((6, 256), 0.25, np.float32), \
            np.array([1, 2, 127, 128, 129, 255])
    if name == "inf_tail_k_is_all_allowed":
        s = rng.normal(size=(6, 200)).astype(np.float32)
        allowed = np.array([1, 7, 64, 150, 199, 200])
        s[np.arange(200)[None, :] >= allowed[:, None]] = -np.inf
        return s, allowed
    if name == "k_zero":
        return rng.normal(size=(4, 256)).astype(np.float32), \
            np.zeros(4, np.int64)
    if name == "k_is_the_row":
        s = rng.integers(-2, 3, size=(4, 256)).astype(np.float32)
        return s, np.full(4, 256)
    if name == "both_zeros":            # -0.0 ties with +0.0: the lower column
        s = rng.integers(-1, 2, size=(6, 256)).astype(np.float32)
        s[:, ::3] = -0.0
        s[:, 1::3] = 0.0
        return s, np.array([1, 40, 100, 171, 200, 250])
    if name == "a_k_a_row":
        s = np.round(rng.normal(size=(16, 640)) * 4).astype(np.float32) / 4
        s[:, 600:] = -np.inf
        return s, np.arange(16) * 40
    if name in ("a_quarter_live", "a_half_live"):   # 1 and 2 tiles of a row
        live = 100 if name == "a_quarter_live" else 250
        s = np.round(rng.normal(size=(8, 512)) * 2).astype(np.float32) / 2
        s[:, live:] = -np.inf
        return s, np.array([0, 1, 17, 64, 65, 99, live - 1, live])
    raise ValueError(name)


@pytest.mark.parametrize("unit_rows", [None, 2, 4],
                         ids=["together", "units_of_4", "units_of_8"])
@pytest.mark.parametrize("case", [
    "random", "three_values", "all_equal", "inf_tail_k_is_all_allowed",
    "k_zero", "k_is_the_row", "both_zeros", "a_k_a_row", "a_quarter_live",
    "a_half_live"])
def test_exact_top_k_is_the_stable_sorts(case, unit_rows, monkeypatch):
    """Searched together (the decode step's few rows) and cut into tiles
    that are searched a unit at a time (a chunk's: more bytes than
    ``GROUP_BYTES``, here that many rows' scores)."""
    scores, k = _top_k_case(case)
    if unit_rows:
        monkeypatch.setattr(paged_attention, "GROUP_BYTES",
                            unit_rows * scores.shape[1] * 4)
    got = np.asarray(jax.jit(paged_attention.exact_top_k_mask)(
        jnp.asarray(scores), jnp.asarray(k, jnp.int32)))
    assert (got == _top_k_oracle(scores, k)).all()
    assert (got.sum(axis=1) == k).all()


@pytest.mark.parametrize("n_keys", [64, 65, 256, 257, 512, 513, 1024])
def test_select_in_context_is_the_full_width_selection(n_keys, monkeypatch):
    """At the edges of every reach (``topk`` 64 under a table of 1,024:
    nothing, one tile of 256, two, the whole), with ties and a chunk of more
    bytes than are searched together: the search as far as the context gives
    what the search of whole rows gives, and the host's table names the
    width."""
    topk, s_max, c = 64, 1024, 8
    monkeypatch.setattr(paged_attention, "GROUP_BYTES", 4 * 256 * 4)
    rng = np.random.default_rng(n_keys)
    qpos = n_keys - c + np.arange(c)
    kpos = np.arange(s_max)
    scores = np.round(rng.normal(size=(c, s_max)) * 8).astype(np.float32) / 8
    scores[(kpos[None, :] > qpos[:, None]) | (kpos[None, :] >= n_keys)] = -np.inf
    room = jnp.asarray(np.minimum(qpos + 1, topk), jnp.int32)
    got = jax.jit(paged_attention.select_in_context, static_argnums=3)(
        jnp.asarray(scores), room, jnp.int32(n_keys), topk)
    want = paged_attention._top_k_rows(jnp.asarray(scores), room)
    assert (np.asarray(got) == np.asarray(want)).all()
    assert (np.asarray(got).sum(axis=1) == np.asarray(room)).all()
    assert paged_attention.select_width(n_keys, s_max, topk) == (
        0 if n_keys <= topk else min(
            w for w in (256, 512, 1024) if n_keys <= w))


def _loops(jaxpr, around=()):
    """Every loop of a jaxpr, outermost first: -> [(the trip counts of the
    loops around it, its own (None where the program computes it), its body's
    jaxpr)]."""
    found = []
    for eqn in jaxpr.eqns:
        inner = around
        if eqn.primitive.name in ("while", "scan"):
            scan = eqn.primitive.name == "scan"
            trips = eqn.params["length"] if scan else None
            body = eqn.params["jaxpr" if scan else "body_jaxpr"].jaxpr
            found.append((around, trips, body))
            inner = around + (trips,)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _loops(sub, inner)
    return found


def test_the_chunk_program_holds_one_search_and_a_pass_reads_a_unit_once():
    """The serving cell's chunk (2,048 queries under a table of 33,280,
    top 2,048), traced and not run: one search whatever the context held,
    over units of 1,024 tile rows of 8,320 (as many as the context asks
    for: a trip count the program computes), 32 passes a unit, and a pass
    holds one reduction over the unit's scores and nothing else of their
    size but the compare."""
    rows, s_max, topk = 2048, 33280, 2048
    jaxpr = jax.make_jaxpr(
        lambda sc, room, n: paged_attention.select_in_context(
            sc, room, n, topk))(
        jax.ShapeDtypeStruct((rows, s_max), jnp.float32),
        jax.ShapeDtypeStruct((rows,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32)).jaxpr
    cond, = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    assert len(cond.params["branches"]) == 2
    loops = _loops(jaxpr)
    assert [(around, trips) for around, trips, _ in loops] == [
        ((), None), ((None,), 32)]
    unit = (paged_attention.GROUP_BYTES // (s_max // 4 * 4), s_max // 4)
    assert unit == (1024, 8320)
    body = loops[1][2]
    over_scores = [e.primitive.name for e in body.eqns if any(
        getattr(v.aval, "shape", None) == unit for v in e.invars)]
    assert over_scores == ["ge", "convert_element_type", "reduce_sum"]
    assert not [e for e in body.eqns
                if e.primitive.name in ("cumsum", "sort", "gather")]
    assert [paged_attention.select_width(n, s_max, topk)
            for n in (2048, 2049, 8320, 8321, 16640, 16641, 33280)] \
        == [0, 8320, 8320, 16640, 16640, 33280, 33280]


def test_compact_selected_lists_the_chosen_rows_in_order():
    rng = np.random.default_rng(1)
    sel = rng.random((3, 7, 8)) < 0.4
    table = rng.integers(1, 50, size=(3, 7)).astype(np.int32)
    rows, ok = jax.jit(lambda s, t: paged_attention.compact_selected(s, t, 20))(
        jnp.asarray(sel), jnp.asarray(table))
    for b in range(3):
        pos = np.flatnonzero(sel[b].reshape(-1))[:20]
        assert int(ok[b].sum()) == len(pos)
        assert (np.asarray(rows[b])[:len(pos)]
                == table[b][pos // 8] * 8 + pos % 8).all()


# -- (c) the dropless expert layer ---------------------------------------------

@pytest.mark.parametrize("tokens", [5, 50, 300])
def test_dropless_experts_match_the_per_token_definition(tokens):
    """Skewed routing: one expert gets most tokens, several get none; rows
    past the valid ones are routed nowhere. No pair is dropped."""
    d, f, e, k = 32, 16, 12, 4
    p = moe.init_dropless_params(jax.random.PRNGKey(0), d, f, e, std=0.3)
    # the first feature is 1 in every token and the router reads it as a
    # bias: expert 3 is in every token's four, experts 7 to 11 in none
    p["wr"] = p["wr"].at[0, 3].set(30.0).at[0, 7:].set(-30.0)
    y = jax.random.normal(jax.random.PRNGKey(1), (tokens, d)).at[:, 0].set(1.0)
    valid = jnp.arange(tokens) < tokens - 2
    out, hit, pairs = jax.jit(
        lambda y, p, v: moe.dropless_experts(y, p, k, v))(y, p, valid)
    topi, gates = moe.route_top_k(y, p["wr"], k)
    want = np.zeros((tokens, d), np.float64)
    used = set()
    for t in range(tokens - 2):
        for j in range(k):
            ex = int(topi[t, j])
            used.add(ex)
            gu = np.asarray(y[t], np.float64) @ np.asarray(p["wgu"][ex], np.float64)
            act = gu[:f] / (1 + np.exp(-gu[:f])) * gu[f:]
            want[t] += float(gates[t, j]) * (act @ np.asarray(p["wd"][ex], np.float64))
    np.testing.assert_allclose(np.asarray(out), want, atol=2e-5)
    assert int(pairs) == (tokens - 2) * k and int(hit) == len(used)
    counts = np.bincount(np.asarray(topi[:tokens - 2]).reshape(-1), minlength=e)
    if tokens >= 50:
        assert counts[3] == tokens - 2 and (counts[7:] == 0).all()
        assert len(used) <= 7 and counts[3] > 1.5 * np.sort(counts)[-2]


# -- (d) three pools under one set of tables and one budget ---------------------

def _engine(env, config, cfg, params, **kw):
    c = copy.copy(env.config)
    c.serve_kv_cache_mb = kw.pop("mb", 1)
    c.serve_kv_page_elems = PAGE
    return InferenceEngine(env, cfg, tp=1, params=params,
                           devices=env.devices[:1], config=c,
                           max_batch=kw.pop("max_batch", 3), queue_depth=16,
                           prefill_chunk=kw.pop("chunk", 24))


def test_the_index_pool_shares_the_tables_and_the_budget(env):
    """Admit, extend, evict and resume: the allocator stays sound, the
    budget's bytes are the three pools' page bytes, every request finishes
    with the tokens a run that never evicts gives."""
    config, cfg = toy(2)
    params = REF.init_params(2, config)
    prompts = sequences(6, (60, 45, 70, 30), cfg.vocab)

    def serve(mb):
        eng = _engine(env, config, cfg, params, mb=mb)
        cache = eng.cache
        pools = (eng.kpool, eng.vpool, eng.ipool)
        assert cache.page_bytes * (cache.num_pages + 1) == sum(
            p.nbytes for p in pools)
        reqs = [eng.submit(p, 24) for p in prompts]
        held = 0
        for _ in range(2000):
            eng.step()
            cache.check()
            assert cache.budget.bytes == cache.held_pages * cache.page_bytes
            held = max(held, cache.held_pages)
            if all(r.done() for r in reqs):
                break
        eng.close()
        assert cache.held_pages == 0 and cache.budget.bytes == 0
        return [r.result() for r in reqs], held, cache.num_pages

    from mlsl_tpu.core import stats

    roomy, held, _ = serve(mb=1)
    before = stats.SERVE_COUNTERS["kv_evictions"]
    page_bytes = kvc.PagedKVCache(cfg, page_elems=PAGE, budget_mb=1,
                                  max_len=CTX).page_bytes
    # a pool of 10 pages: one full context (8) fits, three sequences do not
    tight, _, pages = serve(mb=10.5 * page_bytes / (1 << 20))
    assert pages == 10 < held
    assert stats.SERVE_COUNTERS["kv_evictions"] > before
    assert tight == roomy


def test_an_engine_keeps_the_weights_it_is_handed(env):
    config, cfg = toy(1)
    params = REF.init_params(3, config)
    eng = _engine(env, config, cfg, params)
    for mine, given in zip(jax.tree.leaves(eng.params), jax.tree.leaves(params)):
        assert mine is given
    eng.close()


def test_chunked_prefill_leaves_one_span_a_chunk_and_the_counters(env):
    from mlsl_tpu import obs

    config, cfg = toy(2)
    eng = _engine(env, config, cfg, REF.init_params(4, config))
    obs.get_tracer().clear()
    prompts = sequences(9, (50, 24), cfg.vocab)
    reqs = [eng.submit(p, 5) for p in prompts]
    while not all(r.done() for r in reqs):
        eng.step()
    eng.close()
    events = [e for e in obs.get_tracer().snapshot() if e[2] == "serve"]
    chunks = [e[7] for e in events if e[1] == "serve.prefill.chunk"]
    assert [(c["req"], c["chunk"], c["offset"], c["tokens"], c["last"])
            for c in chunks] == [(0, 0, 0, 24, False), (0, 1, 24, 24, False),
                                 (0, 2, 48, 2, True), (1, 0, 0, 24, True)]
    assert all(c["expert_tokens"] == c["tokens"] * 2 * 2 for c in chunks)
    # how far the selection searched, as the exported table has it: every
    # chunk's context is longer than the toy's topk and its table is one tile
    assert [c["select_width"] for c in chunks] \
        == [paged_attention.select_width(c["offset"] + c["tokens"], CTX, TOPK)
            for c in chunks] == [CTX] * 4
    # one chunk a step at most, and an admission is the step of the first
    assert len({c["step"] for c in chunks}) == len(chunks)
    admits = [e[7] for e in events if e[1] == "serve.admit"]
    assert [(a["req"], a["prompt_tokens"], a["error"]) for a in admits] \
        == [(0, 50, None), (1, 24, None)]
    assert [a["step"] for a in admits] == [chunks[0]["step"], chunks[3]["step"]]
    decodes = [e[7] for e in events if e[1] == "serve.decode"]
    for dec in decodes:
        assert dec["selected_tokens"] <= dec["ctx_tokens"]
        assert dec["expert_tokens"] == dec["inflight"] * 2 * 2
        assert 1 <= dec["experts_hit"] <= dec["expert_tokens"]
        assert dec["pages_held"] and dec["pool_pages"] and dec["pages_gathered"]
    # the sequence still filling does not decode; once both are filled both do
    assert decodes[0]["inflight"] == 1 and max(
        dec["inflight"] for dec in decodes) == 2
    assert decodes[-1]["selected_tokens"] == TOPK * decodes[-1]["inflight"]


# -- (e) the precision the configuration states, and the one below ---------------

def test_bfloat16_stays_inside_and_float8_falls_outside_the_tolerance():
    """The program in bfloat16 (weights, pools, operands on the way into a
    product) against the reference in float32: every chunk's and every
    decode step's logits within TOLERANCE of the reference's; the reference
    itself in float8, the precision below, lies outside it."""
    config, cfg = toy(2, weights_dtype="bfloat16", kv_dtype="bfloat16",
                      compute_dtype="bfloat16")
    params = REF.init_params(21, config)
    rcfg = REF.model_cfg(config)
    prompt, extra = sequences(12, (90, 8), cfg.vocab)
    full = np.concatenate([prompt, extra])
    at = [23, 47, 71, 89] + list(range(90, 98))
    want = np.asarray(REF.logits_at(params, rcfg, full, at))
    low = np.asarray(REF.logits_at(params, rcfg, full, at, precision="fp8"))
    run = Paged(cfg, params, chunk=24, batch=1)
    got = [logits for _, logits in run.prefill(0, prompt)]
    got += [run.decode([(0, int(tok), 90 + k)])[0]
            for k, tok in enumerate(extra)]
    # the root of the mean square: a single flipped expert or selected
    # position (2 of 8 and 16 of 90 here) moves a row's logits by as much in
    # either precision, so the widest gap does not tell them apart
    worst = float(np.sqrt(np.mean(np.square(np.asarray(got) - want))))
    control = float(np.sqrt(np.mean(np.square(low - want))))
    print("bf16", worst, "fp8", control)
    assert worst < BF16_TOLERANCE < control


# -- the default block through the same chunk program ----------------------------

def test_gpt2s_block_prefilled_by_chunks_serves_the_unchunked_engines_tokens(env):
    """Any instance of the block may ask for chunks: GPT-2's (LayerNorm,
    learned positions, as many key-value heads as query heads, no indexer)
    through ``chunk_local`` and the live-list decode."""
    from mlsl_tpu.serve import engine as engine_mod

    cfg = tfm.TransformerConfig(vocab=256, d_model=32, n_heads=4, head_dim=8,
                                n_blocks=2, seq_len=64, dtype="float32")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    c = copy.copy(env.config)
    c.serve_kv_cache_mb, c.serve_kv_page_elems = 1, 8
    kw = dict(tp=1, params=params, devices=env.devices[:1], config=c,
              max_batch=2, queue_depth=8)
    chunked = InferenceEngine(env, cfg, prefill_chunk=12, **kw)
    prompts = sequences(2, (30, 17), cfg.vocab)
    reqs = [chunked.submit(p, 6) for p in prompts]
    while not all(r.done() for r in reqs):
        chunked.step()
    chunked.close()
    whole = InferenceEngine(env, cfg, **kw)
    for p, r in zip(prompts, reqs):
        gap, _ = engine_mod.oracle_logit_gap(whole, p, r.tokens)
        assert gap < 1e-4
    whole.close()
