"""Serving engine (mlsl_tpu/serve): the paged decode's tokens held against
the unpaged full-context oracle's logits, the ragged paged attention against
the dense masked reference, the live-page list against the allocator's
tables, free-list/eviction invariants under churn, the int8 paged codec vs
the dequantize oracle, SLA ladder
escalation/recovery/admission-rejection, chaos soak (degraded, never down),
knob validation, and the serving metric families on the telemetry plane."""

import dataclasses
import json

import numpy as np
import pytest

from mlsl_tpu import chaos, serve, supervisor
from mlsl_tpu.core import stats
from mlsl_tpu.log import MLSLError
from mlsl_tpu.models.transformer import TransformerConfig, kv_block_quant
from mlsl_tpu.ops import paged_attention
from mlsl_tpu.serve.engine import oracle_generate, oracle_logit_gap
from mlsl_tpu.serve.kv_cache import PagedKVCache


@pytest.fixture(autouse=True)
def _clear_chaos():
    chaos.clear()
    yield
    chaos.clear()


def _cfg(**kw):
    base = dict(vocab=64, d_model=32, n_heads=4, head_dim=8, n_blocks=2,
                seq_len=64, dtype="float32")
    base.update(kw)
    return TransformerConfig(**base)


def _prompts(cfg, n, rng_seed=0):
    rng = np.random.default_rng(rng_seed)
    return [rng.integers(1, cfg.vocab, size=int(rng.integers(3, 20)))
            .astype(np.int32) for _ in range(n)]


# -- paged decode correctness -------------------------------------------------

#: the benchmark's contract (``served_logit_gap_max``) at the CPU's precision:
#: the decode step sums over the live pages and the oracle's prefill over its
#: padded context, so float32 logits agree to rounding (seen: under 1e-5)
LOGIT_TOL = 1e-4


def served_logit_gap(eng, prompt, tokens):
    return oracle_logit_gap(eng, prompt, tokens)[0]


def test_paged_decode_logits_within_tolerance_of_unpaged_oracle(env):
    """The tentpole acceptance pin: every token the continuous-batched paged
    decode serves has an oracle logit within LOGIT_TOL of the oracle's best
    (f32 attention over f32-at-rest KV in both programs)."""
    cfg = _cfg()
    eng = serve.InferenceEngine(env, cfg, tp=1, seed=0)
    reqs = [eng.submit(p, 6) for p in _prompts(cfg, 5)]
    eng.run()
    for req, p in zip(reqs, _prompts(cfg, 5)):
        got = req.result(timeout=5)
        assert len(got) == 6
        assert served_logit_gap(eng, p, got) <= LOGIT_TOL
    assert all(r.state == "done" for r in reqs)
    eng.cache.check()
    assert len(eng.cache) == 0          # every sequence released its pages
    eng.close()


def test_paged_decode_logits_within_tolerance_tp2(env):
    """Same pin with the decode allreduces live on the model axis (routed
    through the selection table via algos.inline_allreduce) and the pools'
    heads sharded over it."""
    cfg = _cfg(n_heads=8)
    eng = serve.InferenceEngine(env, cfg, tp=2, seed=0)
    p = np.arange(1, 11, dtype=np.int32)
    req = eng.submit(p, 5)
    eng.run()
    got = req.result(timeout=5)
    assert len(got) == 5 and served_logit_gap(eng, p, got) <= LOGIT_TOL
    eng.close()


def _paged_case(case, rng):
    """(positions a slot, -1 = inactive; the list's capacity or None)."""
    fixed = {
        "one_live_sequence": ([-1, 37, -1, -1], None),
        "position_0": ([0, 20, -1, 5], None),
        # rows 0 and 15 of a last page, and a page begun this step
        "crossing_page_boundary": ([32, 47, 16, -1], None),
        "at_ctx_len_minus_1": ([127, 3, -1, 64], None),
        "list_fills_capacity": ([127, 127, 63, 63], 24),  # 8 + 8 + 4 + 4
    }
    return fixed.get(
        case, ([int(n) for n in rng.integers(0, 128, size=4)], None))


@pytest.mark.parametrize("case", [
    "one_live_sequence", "full_batch", "position_0", "crossing_page_boundary",
    "at_ctx_len_minus_1", "list_fills_capacity", "int8_pool", "tp2"])
def test_ragged_paged_attention_matches_dense_masked_reference(env, case):
    """ops.paged_attention.ragged_paged_attention, fed by the allocator's
    live list, against the dense form it replaced: every slot's padded page
    table gathered whole and masked past the slot's position."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from mlsl_tpu.comm.collectives import smap
    from mlsl_tpu.comm.mesh import MODEL_AXIS

    rng = np.random.default_rng(7)
    positions, cap = _paged_case(case, rng)
    hl, dh, page, chunk, layers, layer = 4, 8, 16, 4, 3, 1
    quant, tp = case == "int8_pool", 2 if case == "tp2" else 1
    cfg = _cfg(n_heads=hl, head_dim=dh, n_blocks=layers, seq_len=128)
    cache = PagedKVCache(cfg, page_elems=page, budget_mb=1, max_len=128,
                         quant=quant)
    live = [b for b, n in enumerate(positions) if n >= 0]
    for b in rng.permutation(live):          # scattered, unordered pages
        assert cache.admit(int(b), 1)
    for b in rng.permutation(live):
        assert cache.extend(int(b), positions[b] + 1)
    lst, n = cache.live_list(live, cap or -(-cache.num_pages // chunk) * chunk)
    assert cap is None or n == cap
    slot_of = np.asarray(live)[np.maximum(lst[1], 0)]    # owner -> batch slot
    lst[1] = np.where(lst[1] >= 0, slot_of, -1)
    npg = cache.num_pages + 1
    k = rng.normal(size=(layers, npg, page, hl, dh)).astype(np.float32)
    v = rng.normal(size=(layers, npg, page, hl, dh)).astype(np.float32)
    q = rng.normal(size=(len(positions), hl, dh)).astype(np.float32)
    pos = np.maximum(np.asarray(positions, np.int32), 0)
    scales = ()
    if quant:
        (kq, ks), (vq, vs) = kv_block_quant(k), kv_block_quant(v)
        k, v = (np.asarray(x, np.float32) * np.asarray(sc)[..., None]
                for x, sc in ((kq, ks), (vq, vs)))    # what the pool holds
        scales = tuple(jnp.asarray(sc).swapaxes(2, 3).reshape(layers, npg, -1)
                       for sc in (ks, vs))
        kp, vp = (jnp.asarray(x).reshape(layers, npg, page, -1)
                  for x in (kq, vq))
    else:
        kp, vp = (jnp.asarray(x).reshape(layers, npg, page, -1)
                  for x in (k, v))

    def attend(q, kp, vp, *scales):
        pages, owners, bases = jnp.asarray(lst)
        valid, mine = paged_attention.live_masks(
            owners, bases, jnp.asarray(pos), page)
        return paged_attention.ragged_paged_attention(
            q, kp, vp, layer, pages, owners, valid, mine, *scales,
            chunk=chunk)

    if tp == 1:
        got = jax.jit(attend)(jnp.asarray(q), kp, vp, *scales)
    else:
        mesh = env.create_distribution(1, tp).topology.mesh
        pool = P(None, None, None, MODEL_AXIS)
        got = jax.jit(smap(
            attend, mesh, in_specs=(P(None, MODEL_AXIS, None), pool, pool),
            out_specs=P(None, MODEL_AXIS, None), check=False,
        ))(jnp.asarray(q), kp, vp)
    got = np.asarray(got)

    for b, n_pos in enumerate(positions):
        if n_pos < 0:                        # owns no entry: reads zeros
            assert not got[b].any()
            continue
        table = cache.table_padded(b)
        ks_, vs_ = (x[layer][table].reshape(-1, hl, dh).astype(np.float64)
                    for x in (k, v))         # (M * page, Hl, Dh), dense
        s = np.einsum("hx,thx->ht", q[b].astype(np.float64) / np.sqrt(dh), ks_)
        s[:, np.arange(s.shape[1]) > n_pos] = -np.inf
        w = np.exp(s - s.max(axis=1, keepdims=True))
        want = np.einsum("ht,thx->hx", w / w.sum(axis=1, keepdims=True), vs_)
        np.testing.assert_allclose(got[b], want, rtol=2e-5, atol=2e-5)


def test_kv_block_quant_matches_dequantize_oracle():
    """The int8 paged codec is the ops/quant_kernels blockwise-ref contract
    with block = head_dim: quantize agrees with quantize_blocks_ref row for
    row, and the dequantize round-trip error is bounded by amax/254 per
    row (half an int8 step)."""
    from mlsl_tpu.ops.quant_kernels import (
        dequantize_blocks_ref, quantize_blocks_ref)

    rng = np.random.default_rng(1)
    x = rng.normal(size=(16, 4, 8)).astype(np.float32)
    x[0, 0] = 0.0                        # the amax==0 guard row
    q, s = kv_block_quant(x)
    q2, s2 = quantize_blocks_ref(x.reshape(-1, 8))
    np.testing.assert_array_equal(np.asarray(q).reshape(-1, 8), q2)
    np.testing.assert_array_equal(np.asarray(s).reshape(-1), s2)
    deq = np.asarray(dequantize_blocks_ref(q2, s2)).reshape(x.shape)
    amax = np.abs(x).max(axis=-1, keepdims=True)
    assert np.all(np.abs(deq - x) <= amax / 254 + 1e-7)


def test_int8_paged_decode_within_tolerance(env):
    """The int8-paged engine's first token is exact vs the f32 oracle
    (one quantized read cannot flip a well-separated argmax on this model)
    and the whole greedy stream stays in near-total agreement."""
    cfg = _cfg()
    qconfig = dataclasses.replace(env.config, serve_kv_quant=True)
    eng = serve.InferenceEngine(env, cfg, tp=1, seed=0, config=qconfig)
    p = np.arange(1, 13, dtype=np.int32)
    req = eng.submit(p, 8)
    eng.run()
    got = req.result(timeout=5)
    want = oracle_generate(eng, p, 8)
    assert got[0] == want[0]
    agree = sum(1 for a, b in zip(got, want) if a == b)
    assert agree >= len(want) - 1, (got, want)
    eng.close()


# -- the greedy choice, on the device ------------------------------------------


def _indexed_cfg():
    """A small block with grouped-query heads under an indexer and dropless
    experts: the engine prefills it by chunks and decodes through tables."""
    return TransformerConfig(
        vocab=64, d_model=32, n_heads=4, n_kv_heads=2, head_dim=8, n_blocks=2,
        seq_len=64, dtype="float32", norm="rms", positions="rope",
        qk_norm=True, mlp="experts", n_experts=4, moe_top_k=2,
        expert_width=16, index_heads=2, index_dim=8, index_topk=8)


@pytest.mark.parametrize("head", ["drawn", "two_equal_maxima", "nan"])
@pytest.mark.parametrize("pools", ["f32_pool", "int8_pool", "indexed"])
def test_the_programs_choose_the_token_the_hosts_argmax_chose(
        env, monkeypatch, pools, head):
    """The engine's programs return tokens, not logits, and the tokens are
    those of ``np.argmax``, the oracles' rule: on the slots and pools of
    every step of a short run the decode program's output is exactly
    ``np.argmax(decode_local's logits, axis=-1)`` for every slot (live or
    not), the chunk program's the argmax of ``chunk_local``'s, the prefill's
    the argmax of the oracle's; among equal maxima the lower index wins, a
    NaN wins over every number; and what a request is served is that chain."""
    import jax
    import jax.numpy as jnp

    from mlsl_tpu.models import transformer as tfm
    from mlsl_tpu.serve.engine import oracle_logits

    indexed, quant = pools == "indexed", pools == "int8_pool"
    cfg = _indexed_cfg() if indexed else _cfg()
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    w = params["final"]["head"]
    if head == "two_equal_maxima":
        # columns 5 and 9 alike and zeros elsewhere: a row of logits holds
        # its maximum at 5 and 9, or (their product negative) at the 62 zeros
        w = jnp.zeros_like(w).at[:, 5].set(w[:, 5]).at[:, 9].set(w[:, 5])
    elif head == "nan":
        w = w.at[:, 7].set(jnp.nan)
    params["final"]["head"] = w
    eng = serve.InferenceEngine(
        env, cfg, tp=1, params=params, max_batch=4,
        config=dataclasses.replace(env.config, serve_kv_quant=quant),
        prefill_chunk=8 if indexed else None)

    names = ("kscale", "vscale") if quant else ("ipool",) if indexed else ()
    decode_logits = jax.jit(lambda p, slots, live, k, v, *more: tfm.decode_local(
        p, slots, live, k, v, cfg, 1, **dict(zip(names, more)))[0])
    chunk_logits = jax.jit(lambda *args: tfm.chunk_local(*args, cfg, 1)[0])
    steps, chunks, chain = [], [], {}
    compiled = eng._decode_prog

    def decode_prog(dtype):
        prog = compiled(dtype)

        def call(*args):
            logits = np.asarray(decode_logits(*args))   # the pools are donated
            out = prog(*args)
            steps.append((logits, np.asarray(out[0])))
            for seq in eng._active.values():
                if not seq.prefilling:
                    chain.setdefault(seq.req.id, []).append(
                        int(np.argmax(logits[seq.slot])))
            return out
        return call

    def chunk_prog(*args):
        logits = np.asarray(chunk_logits(*args))
        out = chunk(*args)
        chunks.append((int(args[2]) + int(args[3]), logits, out[0]))
        return out

    monkeypatch.setattr(eng, "_decode_prog", decode_prog)
    if indexed:
        chunk = eng._chunk_prog
        monkeypatch.setattr(eng, "_chunk_prog", chunk_prog)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab, size=n).astype(np.int32)
               for n in (5, 11, 18)]
    reqs = [eng.submit(p, 5) for p in prompts]
    eng.run()
    assert all(r.state == "done" and len(r.tokens) == 5 for r in reqs)

    assert len(steps) >= 4
    for logits, tokens in steps:
        assert tokens.dtype == np.int32 and tokens.shape == (eng.max_batch,)
        np.testing.assert_array_equal(tokens, np.argmax(logits, axis=-1))
        for row, tok in zip(logits, tokens):
            if head == "two_equal_maxima":
                best = np.flatnonzero(row == row.max())
                assert len(best) >= 2 and tok == best[0]
                assert row[5] == row[9]
            elif head == "nan":
                assert tok == 7 and np.isnan(row[7])
    if indexed:
        for _, logits, tok in chunks:
            assert tok.dtype == jnp.int32 and tok.shape == ()
            assert int(tok) == int(np.argmax(logits))
        firsts = [int(tok) for end, _, tok in chunks if end in (5, 11, 18)]
    else:
        firsts = [int(np.argmax(oracle_logits(eng, p))) for p in prompts]
    assert [r.tokens[0] for r in reqs] == firsts
    assert [r.tokens[1:] for r in reqs] == [chain[r.id] for r in reqs]
    if head == "two_equal_maxima":
        # both kinds of tie were met: 5 before 9, and 0 before the other zeros
        assert {int(t) for _, toks in steps for t in toks} == {0, 5}
    eng.close()


# -- paged KV cache invariants ------------------------------------------------


def test_kv_cache_free_list_invariants_under_churn():
    cfg = _cfg()
    cache = PagedKVCache(cfg, page_elems=16, budget_mb=1, max_len=64)
    rng = np.random.default_rng(2)
    live = {}
    for seq_id in range(200):
        op = rng.integers(0, 3)
        if op == 0 or not live:
            n = int(rng.integers(1, 65))
            if cache.admit(seq_id, n):
                live[seq_id] = n
        elif op == 1:
            sid = int(rng.choice(list(live)))
            n = min(live[sid] + int(rng.integers(1, 20)), cache.ctx_len)
            if cache.extend(sid, n):
                live[sid] = n
        else:
            sid = int(rng.choice(list(live)))
            cache.release(sid, evict=bool(rng.integers(0, 2)))
            del live[sid]
        cache.check()
    for sid in list(live):
        cache.release(sid)
        cache.check()
    assert cache.free_pages == cache.num_pages
    assert cache.budget.bytes == 0


def test_live_list_matches_tables_under_churn():
    """The flat list the decode program walks is exactly the allocator's
    tables of the listed sequences, in the order given: pages, owners,
    token bases, count; padded with (0, -1, 0); the garbage page never
    listed. Under the churn above, eviction and resume included."""
    cfg = _cfg()
    cache = PagedKVCache(cfg, page_elems=16, budget_mb=1, max_len=64)
    cap = cache.num_pages + 3
    rng = np.random.default_rng(2)
    live, next_id = {}, 0
    for _ in range(200):
        op = rng.integers(0, 4)
        if op == 0 or not live:
            n = int(rng.integers(1, 65))
            if cache.admit(next_id, n):
                live[next_id] = n
            next_id += 1
        elif op == 1:
            sid = int(rng.choice(list(live)))
            n = min(live[sid] + int(rng.integers(1, 20)), cache.ctx_len)
            if cache.extend(sid, n):
                live[sid] = n
        elif op == 2:
            sid = int(rng.choice(list(live)))
            cache.release(sid, evict=bool(rng.integers(0, 2)))
            del live[sid]
        else:       # evicted, then resumed under a new id with its prefix
            sid = int(rng.choice(list(live)))
            cache.release(sid, evict=True)
            n = live.pop(sid)
            if cache.admit(next_id, min(n + 1, cache.ctx_len)):
                live[next_id] = min(n + 1, cache.ctx_len)
            next_id += 1
        order = [int(s) for s in rng.permutation(list(live))]
        lst, n = cache.live_list(order, cap)
        assert lst.shape == (3, cap) and lst.dtype == np.int32
        want = [(pg, slot, i * 16) for slot, sid in enumerate(order)
                for i, pg in enumerate(cache._tables[sid])]
        assert n == len(want) == cache.held_pages
        assert [tuple(e) for e in lst[:, :n].T] == want
        assert 0 not in lst[0, :n]
        assert (lst[:, n:] == np.array([[0], [-1], [0]])).all()
        for slot, sid in enumerate(order):   # a slot's entries cover its tokens
            assert (lst[1, :n] == slot).sum() == cache.pages_for(live[sid])
            assert cache.page_of(sid, live[sid] - 1) == cache._tables[sid][-1]
    with pytest.raises(MLSLError):
        cache.live_list(list(live), max(cache.held_pages - 1, 0))


def test_kv_cache_rejects_and_budget_floor():
    cfg = _cfg()
    # budget below one full-context sequence fails loudly at init
    with pytest.raises(MLSLError):
        PagedKVCache(cfg, page_elems=16, budget_mb=0.01, max_len=64)
    # page size must divide the context
    with pytest.raises(MLSLError):
        PagedKVCache(cfg, page_elems=24, budget_mb=4, max_len=64)
    # page_bytes = 2 blocks * 2 (K+V) * 16 * 4 heads * 8 * 4 B = 8 KiB;
    # 0.04 MB buys 5 pages — one full-context sequence (4) plus one
    cache = PagedKVCache(cfg, page_elems=16, budget_mb=0.04, max_len=64)
    assert cache.num_pages >= cache.max_pages_per_seq
    assert cache.admit(0, 64)            # one full-context sequence fits
    before = stats.SERVE_COUNTERS["kv_rejects"]
    assert not cache.admit(1, 64)        # the pool is drained
    assert stats.SERVE_COUNTERS["kv_rejects"] == before + 1
    cache.check()


def test_engine_eviction_preempts_youngest_and_resumes_within_tolerance(env):
    """Pool exhaustion mid-decode evicts the YOUNGEST sequence (pages
    freed, counted, kv.evict instant), requeues it with its generated
    prefix, and every token of the resumed output still lies within
    LOGIT_TOL of the oracle's best."""
    cfg = _cfg()
    eng = serve.InferenceEngine(env, cfg, tp=1, seed=0, max_batch=2)
    # shrink the pool to 5 pages (8 KiB each; one full sequence + 1): two
    # 2-page sequences collide on their third page and the younger yields
    eng.cache = PagedKVCache(cfg, page_elems=16, budget_mb=0.04,
                             max_len=64)
    assert cache_pages(eng) == 5
    p1, p2 = (np.arange(1, 31, dtype=np.int32),
              np.arange(2, 32, dtype=np.int32))
    r1, r2 = eng.submit(p1, 8), eng.submit(p2, 8)
    eng.run()
    assert stats.SERVE_COUNTERS["kv_evictions"] >= 1
    for r, p in ((r1, p1), (r2, p2)):
        got = r.result(timeout=5)
        assert len(got) == 8 and served_logit_gap(eng, p, got) <= LOGIT_TOL
    eng.cache.check()
    eng.close()


def cache_pages(eng):
    return eng.cache.num_pages


# -- SLA ladder ---------------------------------------------------------------


def test_sla_ladder_escalates_and_recovers():
    g = serve.SLAGovernor(max_batch=8, queue_depth=10, breach_ticks=2,
                          recover_ticks=3)
    assert g.batch_limit == 8 and g.admission_open
    g.observe(queue_len=9)               # > 0.75 * 10
    for _ in range(6):
        g.tick()
    assert g.rung == 3                   # climbed the whole ladder
    assert g.batch_limit == 4 and g.precision_shed
    assert not g.admission_open
    assert g.sheds == 3
    g.observe(queue_len=0)
    for _ in range(9):
        g.tick()
    assert g.rung == 0 and g.admission_open and g.recoveries == 3
    assert g.status()["state"] == "healthy"
    # the shed ledger reached the stats plane
    assert stats.SERVE_COUNTERS["shed_batch"] >= 1
    assert stats.SERVE_COUNTERS["shed_admission"] >= 1
    assert stats.SERVE_COUNTERS["recoveries"] >= 3


def test_submit_rejections_are_429_style(env):
    cfg = _cfg()
    eng = serve.InferenceEngine(env, cfg, tp=1, seed=0, queue_depth=2)
    eng.submit(np.arange(1, 5), 2)
    eng.submit(np.arange(1, 5), 2)
    with pytest.raises(serve.ServeOverloadError) as ei:   # queue full
        eng.submit(np.arange(1, 5), 2)
    assert ei.value.retry_after_s > 0
    eng.governor.force_shed("test")
    eng.governor.force_shed("test")
    eng.governor.force_shed("test")                       # -> shed_admission
    assert not eng.governor.admission_open
    with pytest.raises(serve.ServeOverloadError):
        eng.submit(np.arange(1, 3), 1)
    assert stats.SERVE_COUNTERS["rejected"] == 2
    # a prompt that cannot fit the context is a caller bug, not a 429
    with pytest.raises(MLSLError):
        eng.submit(np.arange(1, 60), 10)
    eng.close()


def test_straggler_candidate_counts_as_pressure(monkeypatch):
    g = serve.SLAGovernor(max_batch=4, queue_depth=8, breach_ticks=2,
                          recover_ticks=50)
    g.observe(straggler=True)
    g.tick()
    g.tick()
    assert g.rung == 1 and "straggler" in g.last_reason


# -- chaos soak: degraded, never down -----------------------------------------


def test_chaos_admit_fault_fails_one_request_closed(env):
    cfg = _cfg()
    eng = serve.InferenceEngine(env, cfg, tp=1, seed=0)
    chaos.plan("serve.admit", "error", times=1)
    reqs = [eng.submit(p, 4) for p in _prompts(cfg, 3)]
    eng.run()
    states = sorted(r.state for r in reqs)
    assert states == ["done", "done", "failed"]
    failed = next(r for r in reqs if r.state == "failed")
    with pytest.raises(Exception):
        failed.result(timeout=5)
    assert stats.SERVE_COUNTERS["failed"] == 1
    eng.cache.check()                    # no leaked pages from the failure
    eng.close()


def test_chaos_decode_transient_retries_in_place_within_tolerance(env):
    cfg = _cfg()
    eng = serve.InferenceEngine(env, cfg, tp=1, seed=0)
    chaos.plan("serve.decode", "error", exc=OSError, times=2)
    p = np.arange(1, 9, dtype=np.int32)
    req = eng.submit(p, 4)
    eng.run()
    got = req.result(timeout=5)
    assert len(got) == 4 and served_logit_gap(eng, p, got) <= LOGIT_TOL
    assert stats.SERVE_COUNTERS["retries"] >= 1
    assert serve.status()["state"] == "healthy"   # retry != shed
    eng.close()


def test_chaos_decode_loss_sheds_engine_survives(env):
    """A classified non-transient decode fault force-sheds the ladder and
    skips the step; the engine keeps scheduling and the queue drains."""
    from mlsl_tpu.log import MLSLDeviceLossError

    cfg = _cfg()
    eng = serve.InferenceEngine(env, cfg, tp=1, seed=0)
    chaos.plan("serve.decode", "error", exc=MLSLDeviceLossError, times=2)
    reqs = [eng.submit(p, 4) for p in _prompts(cfg, 3)]
    eng.run()
    assert all(r.state == "done" for r in reqs)
    assert eng.governor.sheds >= 1
    assert stats.SERVE_COUNTERS["shed_batch"] >= 1
    eng.close()


def test_chaos_decode_hang_breaches_tpot_and_sheds(env):
    """A hang is not an exception: the step is just slow, the TPOT window
    breaches the SLO, and the governor sheds — degraded, not down."""
    cfg = _cfg()
    eng = serve.InferenceEngine(env, cfg, tp=1, seed=0, tpot_p99_ms=30.0)
    eng.governor.breach_ticks = 1
    # the p99 window needs >= 8 TPOT samples before it will judge; 12
    # decode steps with hangs landing mid-stream guarantee a breach tick
    chaos.plan("serve.decode", "hang", seconds=0.12, after=4, times=3)
    reqs = [eng.submit(p, 12) for p in _prompts(cfg, 4)]
    eng.run()
    assert all(r.state == "done" for r in reqs)
    assert eng.governor.sheds >= 1
    assert not eng._pending and not eng._active   # the queue drained
    eng.close()


@pytest.mark.parametrize("hang", [False, True], ids=["plain", "decode_hangs"])
def test_offered_load_is_completed_or_rejected(env, hang):
    """More than the queue holds, offered in bursts between steps, with and
    without a chaos plan that hangs decode steps: every request is either
    completed or turned away with a 429 at the door — none fails, none is
    left behind, nothing escapes the engine — and the counters agree."""
    cfg = _cfg()
    eng = serve.InferenceEngine(env, cfg, tp=1, seed=0, queue_depth=4,
                                tpot_p99_ms=30.0 if hang else 0.0)
    if hang:
        eng.governor.breach_ticks = 1
        chaos.plan("serve.decode", "hang", seconds=0.05, after=2, times=3)
    prompts = _prompts(cfg, 18)
    reqs, rejected = [], 0
    while prompts or eng._pending or eng._active:
        burst, prompts = prompts[:6], prompts[6:]
        for p in burst:
            try:
                reqs.append(eng.submit(
                    p, 4, route="long" if len(p) > 12 else "short"))
            except serve.ServeOverloadError:
                rejected += 1
        eng.step()
    completed = sum(r.state == "done" for r in reqs)
    assert rejected > 0                          # the door was really shut
    assert completed + rejected == 18
    assert all(len(r.result(timeout=5)) == 4 for r in reqs)
    assert stats.SERVE_COUNTERS["rejected"] == rejected
    assert stats.SERVE_COUNTERS["completed"] == completed
    assert stats.SERVE_COUNTERS["failed"] == 0
    if hang:
        assert eng.governor.sheds >= 1           # degraded, and not down
    eng.cache.check()
    assert len(eng.cache) == 0
    eng.close()


# -- knobs --------------------------------------------------------------------


@pytest.mark.parametrize("field,bad", [
    ("serve_max_batch", 0),
    ("serve_kv_page_elems", 0),
    ("serve_kv_cache_mb", 0),
    ("serve_queue_depth", -1),
])
def test_serve_knob_validation(field, bad):
    from mlsl_tpu.config import Config

    with pytest.raises(MLSLError):
        Config(**{field: bad}).validate()
    Config().validate()                  # defaults stay valid


def test_serve_knobs_in_tuner_ranges():
    from mlsl_tpu.tuner.profile import KNOB_RANGES

    for k in ("serve_max_batch", "serve_kv_page_elems",
              "serve_kv_cache_mb", "serve_queue_depth"):
        assert k in KNOB_RANGES


# -- observability ------------------------------------------------------------


def test_serve_metric_families_and_healthz(env):
    from mlsl_tpu.obs import metrics as obs_metrics

    reg = obs_metrics.enable(every=1)
    try:
        cfg = _cfg()
        eng = serve.InferenceEngine(env, cfg, tp=1, seed=0)
        req = eng.submit(np.arange(1, 9), 3, route="short")
        eng.run()
        assert req.state == "done"
        reg.sample_families()
        text = reg.to_prometheus()
        for name in ("mlsl_serve_admitted", "mlsl_serve_completed",
                     "mlsl_serve_tokens_out", "mlsl_serve_decode_steps",
                     "mlsl_serve_queue_depth", "mlsl_serve_kv_free_pages",
                     "mlsl_serve_ttft_ms", "mlsl_serve_requests_total"):
            assert name in text, name
        # the governor rides /healthz through supervisor.status()
        st = supervisor.status()
        assert st["serve"]["state"] == "healthy"
        assert json.dumps(st)            # stays JSON-serializable
        eng.close()
        assert serve.status() == {"state": "off"}
    finally:
        obs_metrics.disable()


#: the serving span tree: child -> parent (both of one ``step``; the
#: children of ``serve.admit`` of one ``req`` too)
SPAN_PARENT = {
    "serve.schedule": "serve.step", "serve.admit": "serve.step",
    "serve.capacity": "serve.step", "serve.decode": "serve.step",
    "serve.retire": "serve.step",
    "serve.prefill": "serve.admit", "serve.kv_write": "serve.admit",
    "serve.first_token": "serve.admit",
    "serve.decode.prepare": "serve.decode",
    "serve.decode.dispatch": "serve.decode",
    "serve.decode.wait": "serve.decode", "serve.decode.sample": "serve.decode",
}
REQUEST_SCOPED = {"serve.admit", "serve.prefill", "serve.kv_write",
                  "serve.first_token", "serve.request"}


@pytest.mark.parametrize("evict", [False, True],
                         ids=["plain", "evicted_and_resumed"])
def test_serve_spans_on_timeline(env, monkeypatch, evict):
    """One span tree a step, in the ring without anybody arming it: names,
    ``step`` on every span and ``req`` on the request-scoped ones, children
    inside their parents, one ``serve.request`` a request, and the parts of
    a first token's time (queue wait, admission, the rest of the step)
    summing to the TTFT a caller measures round ``step()``."""
    import time

    from mlsl_tpu.obs import tracer as obs_trace
    from mlsl_tpu.obs.tracer import ARGS, CAT, DUR, NAME, PH, TRACK, TS

    tr = obs_trace.get_tracer()
    assert tr is not None               # armed by default (MLSL_TRACE unset)
    monkeypatch.setattr(paged_attention, "PAGES_PER_CHUNK", 2)
    cfg = _cfg()
    if evict:
        # the pool of test_engine_eviction_preempts_youngest_and_resumes_...
        eng = serve.InferenceEngine(env, cfg, tp=1, seed=0, max_batch=2)
        eng.cache = PagedKVCache(cfg, page_elems=16, budget_mb=0.04,
                                 max_len=64)
        prompts, new = [np.arange(1, 31, dtype=np.int32),
                        np.arange(2, 32, dtype=np.int32)], 8
    else:
        eng = serve.InferenceEngine(env, cfg, tp=1, seed=0)
        prompts, new = _prompts(cfg, 3), 4
    tr.clear()
    reqs = [eng.submit(p, new) for p in prompts]
    first_seen = {}                     # req id -> stamp after its step
    while not all(r.done() for r in reqs):
        eng.step()
        now = time.perf_counter_ns()
        for r in reqs:
            if r.tokens:
                first_seen.setdefault(r.id, now)
    eng.step()                          # idle: must leave no span
    assert all(r.state == "done" for r in reqs)
    spans = [e for e in tr.snapshot() if e[CAT] == "serve" and e[PH] == "X"]
    names = {e[NAME] for e in spans}
    assert names == set(SPAN_PARENT) | {"serve.step", "serve.request"}
    assert all("step" in e[ARGS] for e in spans)
    assert all("req" in e[ARGS] for e in spans if e[NAME] in REQUEST_SCOPED)
    steps = {e[ARGS]["step"]: e for e in spans if e[NAME] == "serve.step"}
    assert sorted(steps) == list(range(min(steps), min(steps) + len(steps)))

    def parent_of(e):
        want = SPAN_PARENT[e[NAME]]
        if want == "serve.step":
            return steps[e[ARGS]["step"]]
        hits = [p for p in spans if p[NAME] == want
                and p[ARGS]["step"] == e[ARGS]["step"]
                and p[TS] <= e[TS] and e[TS] + e[DUR] <= p[TS] + p[DUR]
                and ("req" not in e[ARGS] or p[ARGS]["req"] == e[ARGS]["req"])]
        assert len(hits) == 1, (e, hits)
        return hits[0]

    for e in spans:
        if e[NAME] in SPAN_PARENT:
            p = parent_of(e)
            assert p[TS] <= e[TS] and e[TS] + e[DUR] <= p[TS] + p[DUR], (e, p)
            assert e[TRACK] is None
    decodes = [e for e in spans if e[NAME] == "serve.decode"]
    for e in decodes:
        a = e[ARGS]
        # what the decode program walks: the held pages, rounded up to a
        # whole number of chunks, not the batch's padded tables
        assert a["pages_held"] <= a["pages_gathered"] \
            < a["pages_held"] + eng._chunk
        assert a["pages_gathered"] % eng._chunk == 0
        assert a["pages_gathered"] <= eng.max_batch * eng.cache.max_pages_per_seq
        assert a["pool_pages"] == eng.cache.num_pages
        assert 0 < a["pages_held"] <= a["pool_pages"]
        assert a["inflight"] <= a["tokens_live"] <= a["pages_held"] * 16
    waits = [e for e in spans if e[NAME] == "serve.decode.wait"]
    assert len(waits) == len(decodes)
    # what comes back a step: an int32 a slot, whatever the vocabulary
    assert all(e[ARGS]["bytes"] == eng.max_batch * 4 for e in waits)
    evicted = sum(e[ARGS]["evicted"] for e in spans
                  if e[NAME] == "serve.capacity")
    assert evicted == stats.SERVE_COUNTERS["kv_evictions"]
    assert (evicted >= 1) == evict
    assert eng.cache.held_pages == 0

    for r in reqs:
        done = [e for e in spans if e[NAME] == "serve.request"
                and e[ARGS]["req"] == r.id]
        assert len(done) == 1
        d = done[0]
        assert d[TRACK] == f"req/{r.id}" and d[TS] == r.t_submit
        assert d[ARGS]["outcome"] == "done" and d[ARGS]["tokens"] == new
        admits = [e for e in spans if e[NAME] == "serve.admit"
                  and e[ARGS]["req"] == r.id]
        assert [a[ARGS]["resumed"] for a in admits] \
            == [False] + [True] * (len(admits) - 1)
        a = admits[0]
        assert a[ARGS]["prompt_tokens"] == r.prompt.size
        step = steps[a[ARGS]["step"]]
        tail = step[TS] + step[DUR] - (a[TS] + a[DUR])
        parts = a[ARGS]["queue_wait_ns"] + a[DUR] + tail
        # the parts are stamped on one clock, so they close on the end of
        # the admitting step, which is when a caller can see the token
        assert parts == step[TS] + step[DUR] - r.t_submit
        nxt = steps.get(a[ARGS]["step"] + 1)
        measured = first_seen[r.id] - r.t_submit
        assert parts <= measured
        if nxt is not None:
            assert measured <= nxt[TS] - r.t_submit
        # the engine's own TTFT is to the first token's read-back
        first = [e for e in spans if e[NAME] == "serve.first_token"
                 and e[ARGS]["req"] == r.id][0]
        assert d[ARGS]["first_token_ns"] == pytest.approx(
            first[TS] + first[DUR] - r.t_submit, abs=1)
        assert r.ttft_ms == pytest.approx(d[ARGS]["first_token_ns"] / 1e6)
    resumed = [e for e in spans if e[NAME] == "serve.admit"
               and e[ARGS]["resumed"]]
    assert bool(resumed) == evict
    eng.close()


def test_decode_walks_chunks_in_step_with_held_pages(env, monkeypatch):
    """The decode program's work is data: a step with few held pages walks
    fewer chunks than one with many (``serve.decode``'s ``pages_gathered``,
    the count ``serve_kv_gather_useful_share`` reads)."""
    from mlsl_tpu.obs import tracer as obs_trace
    from mlsl_tpu.obs.tracer import ARGS, NAME

    monkeypatch.setattr(paged_attention, "PAGES_PER_CHUNK", 2)
    tr = obs_trace.get_tracer()
    cfg = _cfg()
    eng = serve.InferenceEngine(env, cfg, tp=1, seed=0)

    def walked(prompts, new):
        tr.clear()
        reqs = [eng.submit(p, new) for p in prompts]
        eng.run()
        assert all(r.state == "done" for r in reqs)
        return [(e[ARGS]["pages_held"], e[ARGS]["pages_gathered"])
                for e in tr.snapshot() if e[NAME] == "serve.decode"]

    few = walked([np.arange(1, 6, dtype=np.int32)], 3)         # 1 page held
    many = walked([np.arange(1, 41, dtype=np.int32)] * 6, 3)   # 3 pages each
    assert {g for _, g in few} == {2}
    assert {g for _, g in many} == {18}
    assert all(h <= g < h + 2 for h, g in few + many)
    eng.close()


def test_serve_stats_line(env, tmp_path, monkeypatch):
    monkeypatch.setenv("MLSL_STATS_DIR", str(tmp_path))
    cfg = _cfg()
    eng = serve.InferenceEngine(env, cfg, tp=1, seed=0)
    eng.submit(np.arange(1, 6), 2)
    eng.run()
    eng.governor.force_shed("stats-line probe")
    text = env.create_session().get_stats().print_()
    line = next(l for l in text.splitlines() if l.startswith("SERVE"))
    assert "admitted 1" in line and "completed 1" in line
    shed_log = (tmp_path / "mlsl_stats.log").read_text()
    assert "BATCH" in shed_log           # the immediate shed line
    eng.close()
