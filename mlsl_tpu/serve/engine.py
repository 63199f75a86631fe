"""Continuous-batching inference engine on the training stack.

One :class:`InferenceEngine` owns a 1 x tp slice of the mesh (dp = sp = 1 —
serving replicates across engines, not inside one), the sharded parameter
tree (the HybridTrainer device_put idiom), the paged KV pools as donated
device arrays (K, V, and the index keys of a model with an indexer), and
three compiled smap programs:

- **prefill** — one padded sequence -> the next token, its logits (an
  output only the unpaged oracle below fetches) + per-layer K/V. Padded to
  the full context length so there is exactly one compiled shape.
- **write** — scatter the prefill K/V into the paged pools through the
  sequence's page table (donation-enabled: the pools update in place in
  HBM). The int8 variant quantizes in-graph via ``kv_block_quant``.
- **decode** — one iteration-level step over the whole slot array
  (``models.transformer.decode_local``): every in-flight sequence advances
  one token per call, sequences join and retire between calls. Attention
  reads the pools in place through the flat list of the pages the live
  sequences hold, a chunk of pages a trip, so a step's work follows that
  list's length and not the batch's or the pool's capacity. It returns the
  token each slot chose and the pools, never the logits. Built per compute
  dtype so the SLA governor's precision shed (bf16) is just a different
  entry in the program cache — KV at rest stays f32/int8 either way, which
  is why recovery is numerically clean.

The engine is greedy, and the choice is made inside the program that made
the logits (``_greedy``): a step reads back ``max_batch`` int32 (and, with
an indexer, the two expert counts), a prefill or a last chunk one, whatever
the vocabulary. A sampler with a temperature belongs at the same place.

With ``prefill_chunk`` (a model with grouped-query heads or an indexer
needs it; any model may ask) the first two give way to one **chunk** program
(``models.transformer.chunk_local``): an admitted prompt is prefilled that
many positions a step through the paged cache, each chunk writing its K, V
(and index keys) and attending to what the cache already holds of the
sequence plus itself; at most one chunk a step, one sequence prefilling at a
time, every sequence that has its first token decoding in every step. A
chunk returns the token after its last valid position in the logits' place;
the first token is the last chunk's. A latent-attention model
(``kv_lora_rank``) keeps one pool of latent rows in the place of K and V
(``lpool``) and runs the chunk and decode bodies of ``latent_bodies_of``.

Scheduling runs entirely on the caller's thread (``step()``/``run()``):
device dispatch from a worker thread is exactly what lint rule A202
exists to prevent, and serving does not need it — ``submit()`` is the only
cross-thread entry point and only touches the queue under a lock.

Fault story (chaos sites ``serve.admit`` / ``serve.decode``): admission
faults fail the one request closed; decode faults go through
``supervisor.classify`` — TRANSIENT retries with jittered backoff, FATAL
propagates, anything else force-sheds the SLA ladder and skips the step.
A chaos ``hang`` is not an exception at all — the step simply takes its
duration, the TPOT window breaches, and the governor sheds: degraded, not
down. KV pool donation stays safe under retry because every failure
injection point precedes the dispatch that consumes the pools.
"""

from __future__ import annotations

import collections
import threading
import time
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from mlsl_tpu import chaos, supervisor
from mlsl_tpu.analysis import witness
from mlsl_tpu.comm.collectives import smap
from mlsl_tpu.comm.mesh import MODEL_AXIS
from mlsl_tpu.core import stats
from mlsl_tpu.log import mlsl_assert
from mlsl_tpu.models import transformer as tfm
from mlsl_tpu.obs import metrics, tracer as obs_trace
from mlsl_tpu.obs import straggler as obs_straggler
from mlsl_tpu.ops import paged_attention
from mlsl_tpu.serve import kv_cache as kvc, sla

#: consecutive failed decode steps before the in-flight batch is failed
#: closed (the engine itself survives and keeps admitting)
_DECODE_FAIL_CAP = 8


@jax.named_scope("mlsl_head")
def _greedy(logits):
    """The greedy choice over the last axis, as int32: the first index of
    the maximum, a NaN counting as the maximum (``np.argmax``'s rule; the
    tests hold the programs to it)."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def decode_body_of(cfg, tp: int, comm, dtype, pools=()):
    """The decode program's body: ``models.transformer.decode_local`` with
    the tokens it chose where its logits stood. ``pools`` names the pools
    beside K and V: ``("kscale", "vscale")`` for int8 pools, ``("ipool",)``
    under an indexer. The head is replicated in decode mode, so under
    ``tp`` > 1 every rank makes the same choice."""

    def decode_body(params, slots, live, kpool, vpool, *more):
        logits, *rest = tfm.decode_local(
            params, slots, live, kpool, vpool, cfg, tp, comm=comm,
            dtype=dtype, **dict(zip(pools, more)))
        return (_greedy(logits), *rest)

    return decode_body


def latent_bodies_of(cfg, dtype=None):
    """The chunk and decode programs' bodies of a latent-attention model
    (one pool, the latent rows): ``chunk_local`` and ``decode_local`` with
    the chosen tokens where the logits stood; both return the pool and four
    counts (held experts hit, their pairs, shared tokens, latent rows
    read)."""

    def chunk_body(params, tokens, offset, n_valid, table, pool):
        logits, counts, pool = tfm.chunk_local(
            params, tokens, offset, n_valid, table, pool, None, None, cfg, 1)
        return _greedy(logits), counts, pool

    def decode_body(params, slots, tables, pool):
        logits, pool, counts = tfm.decode_local(
            params, slots, tables, pool, None, cfg, 1, dtype=dtype)
        return _greedy(logits), pool, counts

    return chunk_body, decode_body


def chunk_body_of(cfg, tp: int, comm):
    """The chunk program's body: ``models.transformer.chunk_local`` with the
    token after the chunk's last valid position where its logits stood; the
    index keys' pool is handed in under an indexer and not otherwise."""

    def chunk_body(params, tokens, offset, n_valid, table,
                   kpool, vpool, *ipool):
        logits, *rest = tfm.chunk_local(
            params, tokens, offset, n_valid, table, kpool, vpool,
            ipool[0] if ipool else None, cfg, tp, comm=comm)
        return (_greedy(logits), *rest)

    return chunk_body


@dataclass
class Request:
    """One generation request. ``submit()`` returns it immediately;
    ``result()`` blocks until the scheduler retires it."""

    prompt: np.ndarray
    max_new_tokens: int
    id: int = -1
    route: str = "default"
    eos_token: Optional[int] = None
    state: str = "queued"          # queued | active | done | failed
    tokens: List[int] = field(default_factory=list)
    error: Optional[BaseException] = None
    t_submit: int = 0              # time.perf_counter_ns(), the span ring's clock
    ttft_ms: Optional[float] = None
    _done: threading.Event = field(default_factory=threading.Event, repr=False)
    _resume: Optional[np.ndarray] = field(default=None, repr=False)

    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout: Optional[float] = None) -> List[int]:
        """Generated tokens (blocking). Raises the recorded error for a
        failed request."""
        mlsl_assert(self._done.wait(timeout), "request %d still in flight",
                    self.id)
        if self.state == "failed" and self.error is not None:
            raise self.error
        return list(self.tokens)


@dataclass
class _Seq:
    """Scheduler-internal in-flight sequence state."""

    req: Request
    seq_id: int
    slot: int
    position: int       # next KV write index == current context length
    last_token: int
    admitted_at: int    # admission counter: eviction preempts the youngest
    finished: bool = False
    # chunked prefill: the tokens to prefill, how many the cache holds, and
    # the number of the next chunk; a sequence decodes once filled
    prefix: Optional[np.ndarray] = None
    filled: int = 0
    chunks: int = 0

    @property
    def prefilling(self) -> bool:
        return self.prefix is not None and self.filled < self.prefix.size


class InferenceEngine:
    """Continuous batching + paged KV + SLA ladder over one model slice."""

    def __init__(self, env, cfg, tp: int = 1, params=None, seed: int = 0,
                 devices=None, config=None, max_batch: Optional[int] = None,
                 queue_depth: Optional[int] = None,
                 tpot_p99_ms: float = 0.0,
                 prefill_chunk: Optional[int] = None):
        self.env = env
        self.cfg = cfg
        self.tp = int(tp)
        self.config = config if config is not None else env.config
        mlsl_assert(cfg.n_heads % self.tp == 0, "heads %d %% tp %d",
                    cfg.n_heads, self.tp)
        self.dist = env.create_distribution(1, self.tp, devices=devices)
        self.mesh = self.dist.topology.mesh
        self.comm = (self.dist.model_group, self.config) \
            if self.tp > 1 else None

        self.specs = tfm.param_specs(cfg)
        if params is None:
            params = tfm.init_params(jax.random.PRNGKey(seed), cfg)

        def place(x, spec):
            # weights handed in where they belong stay as they are: a second
            # set of a model that fills half the chip would not fit
            sharding = NamedSharding(self.mesh, spec)
            if isinstance(x, jax.Array) \
                    and x.sharding.is_equivalent_to(sharding, x.ndim):
                return x
            return jax.device_put(x, sharding)

        self.params = jax.tree.map(
            place, params, self.specs, is_leaf=lambda x: isinstance(x, P))

        self.prefill_chunk = int(prefill_chunk or 0)
        self.indexed = bool(cfg.index_topk)
        self.latent = bool(cfg.kv_lora_rank)
        mlsl_assert(
            self.prefill_chunk
            or not (self.indexed or cfg.n_kv_heads or self.latent),
            "a grouped-query, indexer or latent-attention model prefills by "
            "chunks: pass prefill_chunk")
        self.quant = bool(self.config.serve_kv_quant)
        mlsl_assert(not (self.quant and self.prefill_chunk),
                    "chunked prefill writes unquantised pools")
        self.cache = kvc.PagedKVCache(
            cfg,
            page_elems=self.config.serve_kv_page_elems,
            budget_mb=self.config.serve_kv_cache_mb,
            max_len=cfg.seq_len,
            quant=self.quant,
        )
        self.ctx_len = self.cache.ctx_len   # the prefill's one padded shape
        self.max_batch = int(max_batch if max_batch is not None
                             else self.config.serve_max_batch)
        self.governor = sla.SLAGovernor(
            max_batch=self.max_batch,
            queue_depth=int(queue_depth if queue_depth is not None
                            else self.config.serve_queue_depth),
            tpot_p99_ms=tpot_p99_ms,
        )
        sla._set_active(self.governor)

        # KV pools: page 0 is the reserved garbage page (kv_cache.py), so
        # the page axis is num_pages + 1. A page's row is one token's heads
        # merged with head_dim (a lane-dense minor axis: the device keeps a
        # page contiguous, and a 64-wide one would be padded or transposed);
        # whole heads shard over 'model'. Scales: one a token and head, a
        # page's on one row, head-major for the same two reasons.
        npg, page = self.cache.num_pages + 1, self.cache.page_elems
        pool_shape = (cfg.n_blocks, npg, page, cfg.kv_heads * cfg.head_dim)
        self._pool_spec = P(None, None, None, MODEL_AXIS)
        self._scale_spec = P(None, None, MODEL_AXIS)
        # the decode program's live-page list: room for every page of the
        # pool, a whole number of the chunks the attention walks
        self._chunk = paged_attention.PAGES_PER_CHUNK
        self._list_cap = -(-self.cache.num_pages // self._chunk) * self._chunk
        kv_dt = jnp.int8 if self.quant else jnp.dtype(cfg.kv_dtype)
        if self.latent:
            # one pool of latent rows (a token's normed latent and rotated
            # key on whole lanes) under the same page tables; no K or V
            mlsl_assert(self.tp == 1, "latent attention serves one chip")
            self.kpool = self.vpool = self.ipool = None
            self.lpool = jax.device_put(
                jnp.zeros(pool_shape[:3] + (cfg.latent_row,), kv_dt),
                NamedSharding(self.mesh, P()))
        else:
            self.kpool = jax.device_put(
                jnp.zeros(pool_shape, kv_dt),
                NamedSharding(self.mesh, self._pool_spec))
            self.vpool = jax.device_put(
                jnp.zeros(pool_shape, kv_dt),
                NamedSharding(self.mesh, self._pool_spec))
            # the index keys of a model with an indexer: a third pool under
            # the same page tables (and the same budget:
            # kv_cache.page_bytes)
            self.ipool = jax.device_put(
                jnp.zeros(pool_shape[:3] + (cfg.index_row,), kv_dt),
                NamedSharding(self.mesh, P())) if self.indexed else None
        if self.quant:
            sshape = pool_shape[:2] + (cfg.n_heads * page,)
            self.kscale = jax.device_put(
                jnp.ones(sshape, jnp.float32),
                NamedSharding(self.mesh, self._scale_spec))
            self.vscale = jax.device_put(
                jnp.ones(sshape, jnp.float32),
                NamedSharding(self.mesh, self._scale_spec))

        self._build_programs()

        self._lock = witness.named_lock("serve.engine")
        self._pending: Deque[Request] = collections.deque()
        self._active: Dict[int, _Seq] = {}
        self._next_req_id = 0
        self._next_seq_id = 0
        self._admit_counter = 0
        self._decode_fails = 0
        self._t_start: Optional[int] = None     # perf_counter_ns of step 0
        self._tokens_total = 0
        self._step_no = -1      # counter of step() calls; every span's `step`

    # -- compiled programs -------------------------------------------------

    def _build_programs(self) -> None:
        cfg, tp, comm = self.cfg, self.tp, self.comm
        kv_spec = P(None, None, MODEL_AXIS)
        self._decode_cache: Dict[str, object] = {}
        if self.latent:
            chunk_body, _ = latent_bodies_of(cfg)
            self._chunk_prog = jax.jit(smap(
                chunk_body, self.mesh,
                in_specs=(self.specs, P(), P(), P(), P(), P()),
                out_specs=(P(), P(), P()), check=False,
            ), donate_argnums=(5,))
            return
        if self.prefill_chunk:
            pools = (self._pool_spec,) * 2 + ((P(),) if self.indexed else ())
            self._chunk_prog = jax.jit(smap(
                chunk_body_of(cfg, tp, comm), self.mesh,
                in_specs=(self.specs, P(), P(), P(), P()) + pools,
                out_specs=(P(), P()) + pools, check=False,
            ), donate_argnums=tuple(range(5, 5 + len(pools))))
            return

        def prefill_body(params, tokens, length):
            logits, k, v = tfm.prefill_local(params, tokens, length, cfg, tp,
                                             comm=comm)
            return _greedy(logits), logits, k, v

        self._prefill = jax.jit(smap(
            prefill_body, self.mesh,
            in_specs=(self.specs, P(), P()),
            out_specs=(P(), P(), kv_spec, kv_spec),
            check=False,
        ))

        page = self.cache.page_elems

        if self.quant:
            @jax.named_scope("mlsl_attn")
            def write_body(kpool, vpool, kscale, vscale, k, v, page_ids):
                m = page_ids.shape[0]
                heads = k.shape[:2] + (-1, cfg.head_dim)   # one scale a head
                kq, ksc = tfm.kv_block_quant(k.reshape(heads))
                vq, vsc = tfm.kv_block_quant(v.reshape(heads))
                shp = (cfg.n_blocks, m, page, -1)
                kpool = kpool.at[:, page_ids].set(kq.reshape(shp))
                vpool = vpool.at[:, page_ids].set(vq.reshape(shp))
                sshp = (cfg.n_blocks, m, -1)
                kscale = kscale.at[:, page_ids].set(
                    ksc.reshape(shp).swapaxes(2, 3).reshape(sshp))
                vscale = vscale.at[:, page_ids].set(
                    vsc.reshape(shp).swapaxes(2, 3).reshape(sshp))
                return kpool, vpool, kscale, vscale

            self._write = jax.jit(smap(
                write_body, self.mesh,
                in_specs=(self._pool_spec, self._pool_spec,
                          self._scale_spec, self._scale_spec,
                          kv_spec, kv_spec, P()),
                out_specs=(self._pool_spec, self._pool_spec,
                           self._scale_spec, self._scale_spec),
                check=False,
            ), donate_argnums=(0, 1, 2, 3))
        else:
            @jax.named_scope("mlsl_attn")
            def write_body(kpool, vpool, k, v, page_ids):
                m = page_ids.shape[0]
                shp = (cfg.n_blocks, m, page, -1)
                kpool = kpool.at[:, page_ids].set(k.reshape(shp))
                vpool = vpool.at[:, page_ids].set(v.reshape(shp))
                return kpool, vpool

            self._write = jax.jit(smap(
                write_body, self.mesh,
                in_specs=(self._pool_spec, self._pool_spec,
                          kv_spec, kv_spec, P()),
                out_specs=(self._pool_spec, self._pool_spec),
                check=False,
            ), donate_argnums=(0, 1))

    def _decode_prog(self, dtype: str):
        prog = self._decode_cache.get(dtype)
        if prog is not None:
            return prog
        if self.latent:
            _, decode_body = latent_bodies_of(self.cfg, dtype)
            prog = self._decode_cache[dtype] = jax.jit(smap(
                decode_body, self.mesh, in_specs=(self.specs, P(), P(), P()),
                out_specs=(P(), P(), P()), check=False), donate_argnums=(3,))
            return prog
        pool, scale = self._pool_spec, self._scale_spec
        if self.quant:
            names, more = ("kscale", "vscale"), (scale, scale)
        elif self.indexed:
            names, more = ("ipool",), (P(),)
        else:
            names = more = ()
        in_specs = (self.specs, P(), P(), pool, pool) + more
        # the tokens, the pools, and the expert counts of a model that has them
        out_specs = (P(), pool, pool) + more \
            + ((P(),) if self.indexed else ())
        prog = jax.jit(
            smap(decode_body_of(self.cfg, self.tp, self.comm, dtype, names),
                 self.mesh, in_specs=in_specs, out_specs=out_specs,
                 check=False),
            donate_argnums=tuple(range(3, len(in_specs))),
        )
        self._decode_cache[dtype] = prog
        return prog

    # -- admission (any thread) --------------------------------------------

    def submit(self, prompt, max_new_tokens: int, route: str = "default",
               eos_token: Optional[int] = None) -> Request:
        """Queue a request. Raises :class:`~mlsl_tpu.serve.sla.
        ServeOverloadError` (429-style, with ``retry_after_s``) when the
        ladder closed admission or the queue is full — the two rejection
        reasons are distinct on the metrics plane."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        mlsl_assert(prompt.size >= 1, "empty prompt")
        mlsl_assert(max_new_tokens >= 1, "max_new_tokens must be >= 1")
        mlsl_assert(
            prompt.size + max_new_tokens <= self.ctx_len,
            "prompt %d + max_new %d exceeds the context length %d",
            prompt.size, max_new_tokens, self.ctx_len,
        )
        with self._lock:
            reason = None
            if not self.governor.admission_open:
                reason = "shed_admission"
            elif len(self._pending) >= self.governor.queue_depth:
                reason = "queue_full"
            if reason is not None:
                stats.record_serve("rejected")
                m = metrics._registry
                if m is not None:
                    m.inc("mlsl_serve_rejected_total", 1.0,
                          route=route, reason=reason)
                raise sla.ServeOverloadError(
                    f"admission rejected ({reason}); retry after "
                    f"{self.governor.retry_after_s}s",
                    retry_after_s=self.governor.retry_after_s,
                )
            req = Request(prompt=prompt, max_new_tokens=int(max_new_tokens),
                          id=self._next_req_id, route=route,
                          eos_token=eos_token,
                          t_submit=time.perf_counter_ns())
            self._next_req_id += 1
            self._pending.append(req)
            stats.record_serve("admitted")
            return req

    # -- scheduler (caller thread only) ------------------------------------

    def step(self) -> int:
        """One scheduler iteration: observe/tick the SLA ladder, admit up
        to the rung's batch limit, advance every in-flight sequence one
        token, retire the finished. Returns the number of in-flight
        sequences after the step.

        With the span ring armed (the default) a step leaves one tree:
        ``serve.step`` over ``serve.schedule``, a ``serve.admit`` an
        admitted request (``serve.prefill``, ``serve.kv_write``,
        ``serve.first_token``), ``serve.capacity``, ``serve.decode``
        (``.prepare``, ``.dispatch``, ``.wait``, ``.sample``) and
        ``serve.retire``. Every span carries ``step``, the request-scoped
        ones ``req`` too; all stamps are the ring's (``perf_counter_ns``).
        A step that finds nothing queued and nothing in flight leaves no
        span: an idle engine must not flush the flight recorder."""
        tr = obs_trace._tracer
        t_step = time.perf_counter_ns()
        self._step_no += 1
        if self._t_start is None:
            self._t_start = t_step
        sentinel = obs_straggler.get_active()
        straggler = (sentinel is not None
                     and sentinel.shed_candidate() is not None)
        with self._lock:
            qlen = len(self._pending)
        self.governor.observe(queue_len=qlen, straggler=straggler)
        self.governor.tick()
        if not qlen and not self._active:
            tr = None
        if tr is not None:
            tr.complete("serve.schedule", "serve", t_step, step=self._step_no)

        self._admit()
        if self._active:
            self._decode_step()
        t0 = time.perf_counter_ns()
        retired = self._retire()
        self._gauges()
        if tr is not None:
            tr.complete("serve.retire", "serve", t0, step=self._step_no,
                        retired=retired)
            tr.complete("serve.step", "serve", t_step, step=self._step_no,
                        inflight=len(self._active), queued=qlen)
        return len(self._active)

    def run(self, deadline_s: Optional[float] = None,
            until_idle: bool = True, max_steps: Optional[int] = None,
            idle_sleep_s: float = 0.001) -> None:
        """Drive ``step()`` until idle (default), a deadline, or a step
        budget — whichever comes first."""
        t0 = time.monotonic()
        steps = 0
        while True:
            n = self.step()
            steps += 1
            with self._lock:
                idle = n == 0 and not self._pending
            if until_idle and idle:
                return
            if deadline_s is not None \
                    and time.monotonic() - t0 >= deadline_s:
                return
            if max_steps is not None and steps >= max_steps:
                return
            if n == 0:
                time.sleep(idle_sleep_s)

    # -- internals ---------------------------------------------------------

    def _admit(self) -> None:
        if self.prefill_chunk:
            self._admit_chunked()
            return
        while len(self._active) < self.governor.batch_limit:
            t0 = time.perf_counter_ns()
            with self._lock:
                if not self._pending:
                    return
                req = self._pending.popleft()
            seq_id = self._next_seq_id
            self._next_seq_id += 1
            resumed = req._resume is not None
            prefix = req._resume if resumed else req.prompt
            admitted_kv = False
            error = None
            try:
                chaos.inject("serve.admit", req_id=req.id)
                if not self.cache.admit(seq_id, prefix.size + 1):
                    # pool backpressure: leave it queued, stop admitting
                    with self._lock:
                        self._pending.appendleft(req)
                    return
                admitted_kv = True
                self._prefill_seq(req, seq_id, prefix)
            except Exception as e:  # fail this one request closed
                error = type(e).__name__
                if admitted_kv:
                    self.cache.release(seq_id)
                self._active.pop(seq_id, None)
                self._fail(req, e)
                m = metrics._registry
                if m is not None:
                    m.inc("mlsl_serve_requests_total", 1.0,
                          route=req.route, outcome="failed")
            tr = obs_trace._tracer
            if tr is not None:
                tr.complete("serve.admit", "serve", t0, step=self._step_no,
                            req=req.id, seq=seq_id,
                            prompt_tokens=int(prefix.size),
                            queue_wait_ns=t0 - req.t_submit,
                            resumed=resumed, error=error)

    def _admit_chunked(self) -> None:
        """At most one chunk a step, one sequence prefilling at a time: the
        next chunk of the sequence that is being filled, else the first
        chunk of the oldest queued request (``serve.admit`` is the step in
        which a request leaves the queue and runs its first chunk)."""
        filling = [s for s in self._active.values() if s.prefilling]
        if filling:
            seq = min(filling, key=lambda s: s.admitted_at)
            try:
                self._run_chunk(seq)
            except Exception as e:      # fail this one request closed
                self._drop(seq, e)
            return
        if len(self._active) >= self.governor.batch_limit:
            return
        t0 = time.perf_counter_ns()
        with self._lock:
            if not self._pending:
                return
            req = self._pending.popleft()
        seq_id = self._next_seq_id
        self._next_seq_id += 1
        resumed = req._resume is not None
        prefix = req._resume if resumed else req.prompt
        seq = error = None
        try:
            chaos.inject("serve.admit", req_id=req.id)
            if not self.cache.admit(seq_id, prefix.size + 1):
                with self._lock:        # pool backpressure: leave it queued
                    self._pending.appendleft(req)
                return
            seq = _Seq(req=req, seq_id=seq_id, slot=-1,
                       position=int(prefix.size), last_token=-1,
                       admitted_at=self._admit_counter, prefix=prefix)
            self._admit_counter += 1
            self._active[seq_id] = seq
            self._run_chunk(seq)
        except Exception as e:
            error = type(e).__name__
            if seq is None:
                self._fail(req, e)
            else:
                self._drop(seq, e)
        tr = obs_trace._tracer
        if tr is not None:
            tr.complete("serve.admit", "serve", t0, step=self._step_no,
                        req=req.id, seq=seq_id,
                        prompt_tokens=int(prefix.size),
                        queue_wait_ns=t0 - req.t_submit,
                        resumed=resumed, error=error)

    def _drop(self, seq: _Seq, e: BaseException) -> None:
        """Fail one admitted request closed: its pages back, out of the
        batch."""
        self._active.pop(seq.seq_id, None)
        self.cache.release(seq.seq_id)
        self._fail(seq.req, e)
        m = metrics._registry
        if m is not None:
            m.inc("mlsl_serve_requests_total", 1.0,
                  route=seq.req.route, outcome="failed")

    def _run_chunk(self, seq: _Seq) -> None:
        """Prefill the sequence's next ``prefill_chunk`` positions through
        the paged cache (one compiled shape: the last chunk is padded and
        its padding masked). The last chunk yields the first token."""
        tr = obs_trace._tracer
        step, req = self._step_no, seq.req
        t0 = time.perf_counter_ns()
        size, at = self.prefill_chunk, seq.filled
        n = min(size, seq.prefix.size - at)
        last = at + n >= seq.prefix.size
        tokens = np.zeros((size,), np.int32)
        tokens[:n] = seq.prefix[at:at + n]
        table = np.asarray(self.cache.table_padded(seq.seq_id), np.int32)
        if self.latent:
            out = self._chunk_prog(self.params, tokens, np.int32(at),
                                   np.int32(n), table, self.lpool)
            self.lpool = out[2]
        else:
            pools = (self.kpool, self.vpool) \
                + ((self.ipool,) if self.indexed else ())
            out = self._chunk_prog(self.params, tokens, np.int32(at),
                                   np.int32(n), table, *pools)
            self.kpool, self.vpool = out[2:4]
            if self.indexed:
                self.ipool = out[4]
        # the width this chunk's selection searches, for its span: host
        # arithmetic while the device runs the chunk
        width = paged_attention.select_width(
            at + n, table.size * self.cache.page_elems,
            self.cfg.index_topk) if tr is not None and self.indexed else 0
        # blocks until the chunk is done: the expert counts come back, and
        # with them the last chunk's token, which is the first token
        tok, counts = jax.device_get(out[:2]) if last \
            else (None, np.asarray(out[1]))
        seq.filled += n
        seq.chunks += 1
        stats.record_serve("prefill_chunks")
        if tr is not None:
            more = dict(shared_tokens=int(counts[2]),
                        latent_rows=int(counts[3])) if self.latent else {}
            t0 = tr.complete(
                "serve.prefill.chunk", "serve", t0, step=step, req=req.id,
                chunk=seq.chunks - 1, offset=at, tokens=n, last=last,
                experts_hit=int(counts[0]), expert_tokens=int(counts[1]),
                select_width=width, **more)
        if not last:
            return
        t_first = tr.complete("serve.first_token", "serve", t0, step=step,
                              req=req.id) \
            if tr is not None else time.perf_counter_ns()
        self._first_token(seq, int(tok), t_first)

    def _first_token(self, seq: _Seq, tok: int, t_first: int) -> None:
        """A prefilled sequence has its first token: count it, time it, and
        let the sequence decode (or finish, if one token was all)."""
        req = seq.req
        stats.record_serve("prefills")
        stats.record_serve("tokens_out")
        self._tokens_total += 1
        if req.ttft_ms is None:         # a resumed request keeps its first
            req.ttft_ms = (t_first - req.t_submit) / 1e6
            m = metrics._registry
            if m is not None:
                m.observe("mlsl_serve_ttft_ms", req.ttft_ms,
                          route=req.route)
        req._resume = None
        req.state = "active"
        req.tokens.append(tok)
        seq.last_token = tok
        if (req.eos_token is not None and tok == req.eos_token) \
                or len(req.tokens) >= req.max_new_tokens \
                or seq.position >= self.ctx_len:
            seq.finished = True

    def _prefill_seq(self, req: Request, seq_id: int,
                     prefix: np.ndarray) -> None:
        tr = obs_trace._tracer
        step, rid = self._step_no, req.id
        t0 = time.perf_counter_ns()
        n = int(prefix.size)
        tokens = np.zeros((self.ctx_len,), np.int32)
        tokens[:n] = prefix
        tok, _, k, v = self._prefill(
            self.params, jnp.asarray(tokens), jnp.int32(n))
        if tr is not None:
            t0 = tr.complete("serve.prefill", "serve", t0, step=step, req=rid)
        page_ids = jnp.asarray(
            np.asarray(self.cache.table_padded(seq_id), np.int32))
        if self.quant:
            self.kpool, self.vpool, self.kscale, self.vscale = self._write(
                self.kpool, self.vpool, self.kscale, self.vscale,
                k, v, page_ids)
        else:
            self.kpool, self.vpool = self._write(
                self.kpool, self.vpool, k, v, page_ids)
        if tr is not None:
            t0 = tr.complete("serve.kv_write", "serve", t0, step=step,
                             req=rid, pages=self.cache.pages_for(n + 1))
        tok = int(tok)      # blocks until the prefill is done: 4 bytes
        t_first = tr.complete("serve.first_token", "serve", t0, step=step,
                              req=rid) \
            if tr is not None else time.perf_counter_ns()
        seq = _Seq(req=req, seq_id=seq_id, slot=-1, position=n,
                   last_token=tok, admitted_at=self._admit_counter)
        self._admit_counter += 1
        self._first_token(seq, tok, t_first)
        self._active[seq_id] = seq

    def _fail(self, req: Request, e: BaseException) -> None:
        """Fail one request closed."""
        req.state = "failed"
        req.error = e
        self._finish(req)
        stats.record_serve("failed")

    def _finish(self, req: Request) -> None:
        """The request is done or failed: its ``serve.request`` span (from
        ``submit()``, on the track ``req/<id>``), then wake whoever waits."""
        tr = obs_trace._tracer
        if tr is not None:
            first = None if req.ttft_ms is None else int(req.ttft_ms * 1e6)
            tr.complete("serve.request", "serve", req.t_submit,
                        track=f"req/{req.id}", step=self._step_no,
                        req=req.id, outcome=req.state,
                        tokens=len(req.tokens), first_token_ns=first)
        req._done.set()

    def _evict_youngest(self) -> None:
        """Preempt the youngest in-flight sequence: free its pages, stash
        prompt + everything generated as the resume prefix, put it back at
        the FRONT of the queue (it has seniority over never-started work)."""
        seq = max(self._active.values(), key=lambda s: s.admitted_at)
        self._active.pop(seq.seq_id)
        self.cache.release(seq.seq_id, evict=True)
        req = seq.req
        req._resume = np.concatenate(
            [req.prompt, np.asarray(req.tokens, np.int32)])
        req.state = "queued"
        with self._lock:
            self._pending.appendleft(req)

    def _ensure_capacity(self) -> int:
        """Every live sequence needs pages covering its next KV write; a
        pool that cannot extend evicts the youngest until it can. The
        budget invariant (num_pages >= max_pages_per_seq) guarantees this
        terminates with at least one sequence still running. Returns the
        number of sequences it evicted."""
        evicted = 0
        for seq in sorted(self._active.values(), key=lambda s: s.admitted_at):
            while seq.seq_id in self._active \
                    and not self.cache.extend(seq.seq_id, seq.position + 1):
                self._evict_youngest()
                evicted += 1
        return evicted

    def _decode_step(self) -> None:
        tr = obs_trace._tracer
        step = self._step_no
        t0 = time.perf_counter_ns()
        evicted = self._ensure_capacity()
        if tr is not None:
            tr.complete("serve.capacity", "serve", t0, step=step,
                        evicted=evicted)
        if not self._active:
            return
        t_decode = t0 = time.perf_counter_ns()
        live = sorted((s for s in self._active.values() if not s.prefilling),
                      key=lambda s: s.admitted_at)
        if not live:            # the one sequence in flight is still filling
            return
        # a column a slot: token, position, the page the position lies in
        # (inactive slots: zeros, so their writes land on the garbage page)
        slots = np.zeros((3, self.max_batch), np.int32)
        for i, seq in enumerate(live):
            seq.slot = i
            slots[:, i] = (seq.last_token, seq.position,
                           self.cache.page_of(seq.seq_id, seq.position))
        if self.indexed or self.latent:
            # a page table a slot; the pages the index keys (or the latent
            # rows) are read from
            width = self.cache.max_pages_per_seq
            pages = np.zeros((self.max_batch, width), np.int32)
            for i, seq in enumerate(live):
                pages[i] = self.cache.table_padded(seq.seq_id)
            gathered = self.max_batch * width
        else:
            pages, held = self.cache.live_list(
                [seq.seq_id for seq in live], self._list_cap)
            gathered = -(-held // self._chunk) * self._chunk
        dtype = "bfloat16" if self.governor.precision_shed else None
        prog = self._decode_prog(dtype or self.cfg.dtype)
        args = (jnp.asarray(slots), jnp.asarray(pages))
        if tr is not None:
            tr.complete("serve.decode.prepare", "serve", t0, step=step)
        attempt = 0
        while True:
            t_try = time.perf_counter_ns()
            try:
                # a chaos 'hang' here is a slow step, not an exception: it
                # lands inside the timed window, breaches the TPOT SLO, and
                # the governor sheds — the degraded-not-down path
                chaos.inject("serve.decode", inflight=len(live))
                out = prog(self.params, *args, self.lpool) if self.latent \
                    else prog(self.params, *args, self.kpool, self.vpool,
                              *((self.kscale, self.vscale) if self.quant
                                else (self.ipool,) if self.indexed else ()))
                break
            except Exception as e:
                cls = supervisor.classify(e)
                if cls is supervisor.ErrorClass.TRANSIENT \
                        and attempt < self.config.comm_retries:
                    stats.record_serve("retries")
                    time.sleep(supervisor.jittered_backoff(
                        self.config.comm_retry_backoff_s, attempt))
                    attempt += 1
                    continue
                self._decode_fault(e)
                return
        t0 = tr.complete("serve.decode.dispatch", "serve", t_try, step=step,
                         attempt=attempt) \
            if tr is not None else t_try
        counts = None
        if self.latent:
            tokens, self.lpool, counts = out
        elif self.quant:
            tokens, self.kpool, self.vpool, self.kscale, self.vscale = out
        elif self.indexed:
            tokens, self.kpool, self.vpool, self.ipool, counts = out
        else:
            tokens, self.kpool, self.vpool = out
        # blocks until the step is done: a token a slot comes back, and the
        # expert counts with it
        tokens, counts = jax.device_get((tokens, counts))
        t0 = tr.complete("serve.decode.wait", "serve", t0, step=step,
                         bytes=tokens.nbytes) \
            if tr is not None else time.perf_counter_ns()
        step_ms = (t0 - t_try) / 1e6
        self._decode_fails = 0
        if attempt > 0:
            stats.record_serve("recoveries")
        self.governor.observe(tpot_ms=step_ms)
        m = metrics._registry
        if m is not None:
            m.observe("mlsl_serve_tpot_ms", step_ms)
        stats.record_serve("decode_steps")
        stats.record_serve("tokens_out", len(live))
        self._tokens_total += len(live)
        tokens_live = 0
        for seq in live:
            tok = int(tokens[seq.slot])
            tokens_live += seq.position + 1
            seq.position += 1
            seq.last_token = tok
            seq.req.tokens.append(tok)
            if (seq.req.eos_token is not None
                    and tok == seq.req.eos_token) \
                    or len(seq.req.tokens) >= seq.req.max_new_tokens \
                    or seq.position >= self.ctx_len:
                seq.finished = True
        if tr is not None:
            tr.complete("serve.decode.sample", "serve", t0, step=step)
            more = {}
            if self.indexed:
                top = self.cfg.index_topk
                more = dict(
                    ctx_tokens=tokens_live,
                    selected_tokens=sum(min(top, s.position) for s in live),
                    experts_hit=int(counts[0]), expert_tokens=int(counts[1]))
            elif self.latent:
                more = dict(
                    ctx_tokens=tokens_live, experts_hit=int(counts[0]),
                    expert_tokens=int(counts[1]), shared_tokens=int(counts[2]),
                    latent_rows=int(counts[3]))
            tr.complete("serve.decode", "serve", t_decode, step=step,
                        inflight=len(live), tokens_live=tokens_live,
                        pages_held=self.cache.held_pages,
                        pages_gathered=gathered,
                        pool_pages=self.cache.num_pages, **more)

    def _decode_fault(self, e: BaseException) -> None:
        cls = supervisor.classify(e)
        if cls is supervisor.ErrorClass.FATAL:
            raise e
        self._decode_fails += 1
        self.governor.force_shed(f"decode fault: {cls.name}")
        if self._decode_fails < _DECODE_FAIL_CAP:
            return
        # the batch is wedged: fail it closed, keep the engine alive
        for seq in list(self._active.values()):
            self._active.pop(seq.seq_id)
            self.cache.release(seq.seq_id)
            self._fail(seq.req, e)
        self._decode_fails = 0

    def _retire(self) -> int:
        m = metrics._registry
        finished = [s for s in self._active.values() if s.finished]
        for seq in finished:
            self._active.pop(seq.seq_id)
            self.cache.release(seq.seq_id)
            seq.req.state = "done"
            self._finish(seq.req)
            stats.record_serve("completed")
            if m is not None:
                m.inc("mlsl_serve_requests_total", 1.0,
                      route=seq.req.route, outcome="done")
        return len(finished)

    def _gauges(self) -> None:
        m = metrics._registry
        if m is None:
            return
        with self._lock:
            qlen = len(self._pending)
        m.set("mlsl_serve_queue_depth", float(qlen))
        m.set("mlsl_serve_inflight", float(len(self._active)))
        m.set("mlsl_serve_kv_free_pages", float(self.cache.free_pages))
        m.set("mlsl_serve_batch_limit", float(self.governor.batch_limit))
        if self._t_start is not None:
            dt = (time.perf_counter_ns() - self._t_start) / 1e9
            if dt > 0:
                m.set("mlsl_serve_tokens_per_s", self._tokens_total / dt)

    def close(self) -> None:
        """Detach the SLA governor from the module registry (tests and
        multi-engine processes)."""
        if sla.get_active() is self.governor:
            sla._set_active(None)


def oracle_logits(engine: InferenceEngine, seq) -> np.ndarray:
    """Next-token logits (V,) of the UNPAGED oracle for the token sequence
    ``seq``: the engine's own compiled prefill over the whole sequence — no
    KV cache, no pages."""
    seq = np.asarray(seq, np.int32).reshape(-1)
    tokens = np.zeros((engine.ctx_len,), np.int32)
    tokens[:seq.size] = seq
    _, logits, _, _ = engine._prefill(
        engine.params, jnp.asarray(tokens), jnp.int32(seq.size))
    return np.asarray(logits)


def oracle_logit_gap(engine: InferenceEngine, prompt, tokens):
    """How far served ``tokens`` lie from the UNPAGED oracle's choices:
    (the widest gap by which a token's oracle logit lies under the oracle's
    best, the largest logit magnitude met), the oracle fed the prompt and
    the served tokens before each. A gap of 0 = token for token the oracle's
    own greedy chain; the magnitude is what a relative tolerance scales by."""
    prompt = np.asarray(prompt, np.int32).reshape(-1)
    served = np.asarray(tokens, np.int32).reshape(-1)
    gap = top = 0.0
    for k, tok in enumerate(served):
        logits = oracle_logits(engine, np.concatenate([prompt, served[:k]]))
        gap = max(gap, float(logits.max() - logits[tok]))
        top = max(top, float(np.abs(logits).max()))
    return gap, top


def oracle_generate(engine: InferenceEngine, prompt, max_new_tokens: int,
                    eos_token: Optional[int] = None) -> List[int]:
    """The UNPAGED oracle: greedy decode by re-running the engine's own
    compiled prefill over the growing full sequence each step
    (``oracle_logits``). The tests and chip_smoke.py hold the paged engine's
    tokens against this oracle's logits within a tolerance: the decode step
    sums over the live pages, the prefill over its padded context."""
    seq = list(np.asarray(prompt, np.int32).reshape(-1))
    out: List[int] = []
    for _ in range(max_new_tokens):
        tok = int(np.argmax(oracle_logits(engine, seq)))
        out.append(tok)
        seq.append(tok)
        if eos_token is not None and tok == eos_token:
            break
        if len(seq) >= engine.ctx_len:
            break
    return out
