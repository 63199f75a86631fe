"""Paged KV cache: the feed cache's admission model, generalized to pages.

The feed cache (data/cache.py) is admission-capped with no eviction because
epoch replay touches every entry exactly once. Serving breaks that
assumption: sequences arrive and retire continuously, hold wildly different
context lengths, and a single long sequence must not wedge the pool. So the
KV side keeps the same :class:`~mlsl_tpu.data.cache.AdmissionBudget`
admit-or-reject contract underneath, and adds what serving needs on top:

- **fixed-size HBM pages** — the pool is ``(n_blocks, num_pages+1, page,
  kv_heads * head_dim)`` per K and V (and ``(..., page, index_row)`` for the
  index keys of a model with an indexer, under the same tables), owned by the engine as donated device
  arrays; this class is the host-side allocator (free-list + page tables)
  and never touches device memory itself. Page granularity kills the
  fragmentation that per-sequence max-length slabs would cause: a
  16-token-context sequence holds 1 page, not seq_len/page of them.
- **per-sequence page tables** — ``table_padded()`` hands the engine's KV
  write a fixed-width int32 scatter index (padded with page 0), and
  ``live_list()`` hands the decode program one flat list of the pages the
  in-flight sequences hold, each with its owner slot and the token index
  of its first row, padded to a fixed capacity: the compiled programs have
  static shapes, and the decode step's work follows the list's live length.
- **page 0 is reserved garbage** — never allocated, never counted against
  the budget, never listed. Padded prefill scatter-writes and inactive
  batch slots land there; nothing reads it into attention.
- **eviction** — ``release(evict=True)`` is the preemption path: the engine
  evicts the youngest active sequence when a decode step cannot extend,
  re-queues it for a resume-prefill, and the freed pages go back on the
  free-list AND the budget.

The int8 variant (``quant=True``, rides ops/quant_kernels semantics via
``models.transformer.kv_block_quant``) stores 1 byte/element plus one f32
scale per (token, head): the page-bytes math below is the single source of
truth for how many pages a given ``MLSL_SERVE_KV_CACHE_MB`` buys.
"""

from __future__ import annotations

import itertools
from typing import Dict, List, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from mlsl_tpu.data.cache import AdmissionBudget
from mlsl_tpu.log import MLSLError, mlsl_assert
from mlsl_tpu.obs import tracer as obs_trace


class PagedKVCache:
    """Host-side page allocator for the serving engine's KV pools.

    ``cfg`` is the model's TransformerConfig (page bytes depend on
    n_blocks/n_heads/head_dim); ``page_elems`` tokens per page
    (MLSL_SERVE_KV_PAGE_ELEMS); ``budget_mb`` the HBM budget
    (MLSL_SERVE_KV_CACHE_MB); ``max_len`` the context ceiling (defaults to
    cfg.seq_len, the one padded shape the engine's prefill compiles)."""

    def __init__(self, cfg, *, page_elems: int, budget_mb: float,
                 max_len: int = 0, quant: bool = False):
        self.page_elems = int(page_elems)
        self.quant = bool(quant)
        self.ctx_len = int(max_len) if max_len else int(cfg.seq_len)
        mlsl_assert(
            self.ctx_len % self.page_elems == 0,
            f"context length {self.ctx_len} must be a multiple of "
            f"MLSL_SERVE_KV_PAGE_ELEMS={self.page_elems} (the compiled "
            "KV write scatters the padded prefill as whole pages)",
        )
        self.max_pages_per_seq = self.ctx_len // self.page_elems
        # bytes for ONE page across all layers and all pools, counted from
        # the configuration: K and V a key-value head (int8 stores 1 byte an
        # element plus a f32 scale per (token, head); else ``kv_dtype``'s
        # width), and the index key a token where the model has an indexer
        # (the third pool lives under the same tables and the same budget).
        elem = 1 if self.quant else jnp.dtype(cfg.kv_dtype).itemsize
        scale = 4 if self.quant else 0
        heads = cfg.kv_heads
        index = cfg.index_row       # lanes stored, zeros above index_dim
        self.page_bytes = cfg.n_blocks * self.page_elems * (
            2 * heads * (cfg.head_dim * elem + scale) + index * elem)
        self.budget = AdmissionBudget(int(budget_mb * (1 << 20)))
        self.num_pages = self.budget.budget_bytes // self.page_bytes
        if self.num_pages < self.max_pages_per_seq:
            raise MLSLError(
                f"MLSL_SERVE_KV_CACHE_MB={budget_mb} buys {self.num_pages} "
                f"pages of {self.page_bytes} B but one full-context sequence "
                f"needs {self.max_pages_per_seq}; raise the budget or lower "
                "seq_len/MLSL_SERVE_KV_PAGE_ELEMS"
            )
        # page ids 1..num_pages; popped from the tail so allocation order is
        # 1, 2, 3, ... (stable ids make the churn tests readable). Page 0 is
        # the reserved garbage page and never appears here.
        self._free: List[int] = list(range(self.num_pages, 0, -1))
        self._tables: Dict[int, List[int]] = {}

    # -- helpers -----------------------------------------------------------

    def pages_for(self, n_tokens: int) -> int:
        return -(-int(n_tokens) // self.page_elems)

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def held_pages(self) -> int:
        """Pages that some sequence holds (the garbage page is not one)."""
        return self.num_pages - len(self._free)

    def __len__(self) -> int:
        return len(self._tables)

    # -- allocation --------------------------------------------------------

    def admit(self, seq_id: int, n_tokens: int) -> bool:
        """Allocate pages for a sequence entering the batch with
        ``n_tokens`` of context. False = rejected (free-list or budget —
        both count as a kv reject; the engine leaves the request queued)."""
        from mlsl_tpu.core import stats

        mlsl_assert(seq_id not in self._tables,
                    f"seq {seq_id} already admitted")
        need = self.pages_for(n_tokens)
        if need > len(self._free) or not self.budget.admit(
                need * self.page_bytes):
            stats.record_serve("kv_rejects")
            return False
        self._tables[seq_id] = [self._free.pop() for _ in range(need)]
        stats.record_serve("kv_pages_alloc", need)
        return True

    def extend(self, seq_id: int, n_tokens: int) -> bool:
        """Grow a sequence's table to cover ``n_tokens`` total context.
        Decode calls this every step; it is a no-op until the position
        crosses a page boundary. False = pool exhausted (the engine's
        preemption/eviction path fires)."""
        from mlsl_tpu.core import stats

        table = self._tables[seq_id]
        need = self.pages_for(n_tokens) - len(table)
        if need <= 0:
            return True
        if need > len(self._free) or not self.budget.admit(
                need * self.page_bytes):
            stats.record_serve("kv_rejects")
            return False
        table.extend(self._free.pop() for _ in range(need))
        stats.record_serve("kv_pages_alloc", need)
        return True

    def release(self, seq_id: int, evict: bool = False) -> None:
        """Return a sequence's pages to the free-list and the budget.
        ``evict=True`` is the preemption path (counted separately, with a
        ``kv.evict`` instant on the obs timeline — an eviction is the
        engine trading one sequence's progress for the batch's)."""
        from mlsl_tpu.core import stats

        table = self._tables.pop(seq_id)
        self._free.extend(reversed(table))
        self.budget.release(len(table) * self.page_bytes)
        stats.record_serve("kv_pages_freed", len(table))
        if evict:
            stats.record_serve("kv_evictions")
            tr = obs_trace._tracer
            if tr is not None:
                tr.instant("kv.evict", "serve", seq=seq_id,
                           pages=len(table))

    def table_padded(self, seq_id: int) -> List[int]:
        """Fixed-width page table for the compiled KV write: the live
        pages, padded to ``max_pages_per_seq`` with the garbage page 0."""
        table = self._tables[seq_id]
        return table + [0] * (self.max_pages_per_seq - len(table))

    def page_of(self, seq_id: int, position: int) -> int:
        """The pool page that holds token ``position`` of a sequence."""
        return self._tables[seq_id][position // self.page_elems]

    def live_list(self, seq_ids: Sequence[int],
                  capacity: int) -> Tuple[np.ndarray, int]:
        """The flat list the decode program walks: every page the sequences
        ``seq_ids`` hold, in that order and each table in its own, as a
        (3, capacity) int32 array of rows (pool page, owner = the sequence's
        index in ``seq_ids``, token index of the page's first row), padded
        with (0, -1, 0); and the number of live entries."""
        tables = [self._tables[s] for s in seq_ids]
        lens = np.fromiter(map(len, tables), np.int64, len(tables))
        n = int(lens.sum())
        mlsl_assert(n <= capacity, "%d live pages exceed the list's %d",
                    n, capacity)
        out = np.zeros((3, capacity), np.int32)
        out[0, :n] = np.fromiter(
            itertools.chain.from_iterable(tables), np.int32, n)
        out[1, :n] = np.repeat(np.arange(len(tables)), lens)
        out[1, n:] = -1
        first = np.repeat(np.cumsum(lens) - lens, lens)
        out[2, :n] = (np.arange(n) - first) * self.page_elems
        return out, n

    # -- invariants (tests) ------------------------------------------------

    def check(self) -> None:
        """Assert the allocator's invariants; the churn tests call this
        after every operation."""
        held = [p for t in self._tables.values() for p in t]
        mlsl_assert(len(held) == len(set(held)),
                    "page allocated to two sequences")
        mlsl_assert(0 not in held, "garbage page 0 was allocated")
        mlsl_assert(not (set(held) & set(self._free)),
                    "page simultaneously held and free")
        mlsl_assert(len(held) + len(self._free) == self.num_pages,
                    "pages leaked or duplicated")
        mlsl_assert(
            all(1 <= p <= self.num_pages for p in held + self._free),
            "page id out of range")
        mlsl_assert(self.budget.bytes == len(held) * self.page_bytes,
                    "budget accounting out of sync with the free-list")
