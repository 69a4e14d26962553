"""Paged KV-cache pool: block allocator, prefix cache, paged serving pool.

The dense :class:`~repro.serving.engine.SlotPool` reserves ``max_len``
positions per slot up front, so a pool sized for long requests strands most
of its memory on short ones.  This module replaces that with vLLM-style
paging: one shared device pool of fixed-size KV pages
(``[n_layers, n_pages, page_size, Hk·dh]`` per leaf), per-request page
tables, and a host-side free-list allocator that grows a request's table
page-by-page as decode advances.  Admission is bounded by *free pages*, not
free rows, so concurrency at a fixed memory budget scales with actual
sequence lengths instead of the worst case.

Three mechanisms ride on the page indirection:

* **Prefix caching** — completed prefills are remembered keyed by a running
  hash of the prompt; a new request whose prompt extends a cached prefix
  skips the prefill for the shared pages entirely (full hit: first token is
  sampled from the entry's cached last-position logits; partial hit: only
  the suffix is teacher-forced through the pool).  Sharing is
  copy-on-write at page granularity: an unaligned tail page is copied
  before the new request may write into it, so sharers never collide.
* **Commitment admission** — ``can_admit`` reserves the request's whole
  page need (``ceil(total_len / page_size)``) against the free list at
  admission; on-demand growth then draws on that reservation, so decode
  can never deadlock mid-request on an empty free list.
* **Cold-page quantization** (optional, **lossy**) — prefix entries idle
  for ``cold_horizon`` admissions have their pages encoded through the
  wire codecs (``repro.transport.codecs`` int8/int4), freeing the pages;
  a later hit decodes them back into fresh pages.  Off by default
  (``cold_horizon=None``) because dequantized history is no longer
  bit-exact with a fresh prefill.

The **trash page** convention: the device pool is created with one extra
page (id ``n_pages``) that the allocator never hands out.  Idle rows keep
their page-table row pointed at it, so the fixed-shape decode chunk can
advance every row unconditionally — writes from vacant or finished rows
land in the trash page (or clamp inside the row's own last page via
``caps``) and are never validly read.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import maybe_span
from repro.serving.queue import Request


class PagesExhausted(RuntimeError):
    """Admission was attempted without enough uncommitted free pages."""


# ---------------------------------------------------------------------------
# Page allocator
# ---------------------------------------------------------------------------

class PageAllocator:
    """Free-list page allocator with refcounts and admission commitments.

    Pages are shared (prefix cache + any number of requests), so each holder
    retains a reference; a page returns to the free list only when the last
    holder releases it.  ``commit`` reserves pages for an admitted request
    before they are physically allocated — ``available()`` is what a *new*
    admission may claim, keeping on-demand growth deadlock-free.
    """

    def __init__(self, n_pages: int):
        if n_pages <= 0:
            raise ValueError("n_pages must be >= 1")
        self.n_pages = n_pages
        # LIFO: low page ids hand out first (stable tests, warm reuse)
        self.free: List[int] = list(range(n_pages - 1, -1, -1))
        self.refs: Dict[int, int] = {}
        self.committed = 0

    def available(self) -> int:
        """Free pages not already promised to an admitted request."""
        return len(self.free) - self.committed

    # -- commitments ---------------------------------------------------------

    def commit(self, n: int) -> None:
        if n > self.available():
            raise PagesExhausted(
                f"commit({n}) exceeds available ({self.available()})")
        self.committed += n

    def uncommit(self, n: int) -> None:
        if n > self.committed:
            raise RuntimeError(f"uncommit({n}) exceeds committed "
                               f"({self.committed})")
        self.committed -= n

    # -- pages ---------------------------------------------------------------

    def alloc(self, n: int, committed: bool = True) -> List[int]:
        """Pop ``n`` pages (each at refcount 1).  ``committed=True`` draws
        on a prior :meth:`commit` reservation; ``committed=False`` (cache
        revival) must fit in what admissions have not reserved."""
        if committed:
            if n > self.committed:
                raise RuntimeError(
                    f"alloc({n}) draws past the commitment ({self.committed})")
            self.committed -= n
        elif n > self.available():
            raise PagesExhausted(
                f"alloc({n}, committed=False) exceeds available "
                f"({self.available()})")
        ids = [self.free.pop() for _ in range(n)]
        for pid in ids:
            self.refs[pid] = 1
        return ids

    def retain(self, pid: int) -> None:
        self.refs[pid] += 1

    def release(self, pid: int) -> int:
        """Drop one reference; returns 1 if the page went back to the free
        list, 0 if other holders remain.  Double-free raises."""
        if pid not in self.refs:
            raise KeyError(f"release of unallocated page {pid}")
        self.refs[pid] -= 1
        if self.refs[pid] == 0:
            del self.refs[pid]
            self.free.append(pid)
            return 1
        return 0

    def check(self) -> None:
        """Invariants (property tests): full partition, no overlap, and
        commitments covered by the free list."""
        if len(self.free) + len(self.refs) != self.n_pages:
            raise AssertionError("page leak: free + live != total")
        if set(self.free) & set(self.refs):
            raise AssertionError("page on free list while referenced")
        if not 0 <= self.committed <= len(self.free):
            raise AssertionError("commitments exceed the free list")


# ---------------------------------------------------------------------------
# Prefix cache
# ---------------------------------------------------------------------------

def _prefix_digests(prompt: np.ndarray) -> List[bytes]:
    """Running blake2b over the prompt: ``out[i]`` keys tokens ``[: i+1]``.
    One pass (``hashlib`` digests do not finalize), so probing every prefix
    length is O(T0) total."""
    h = hashlib.blake2b(digest_size=16)
    out: List[bytes] = []
    for t in prompt:
        h.update(int(t).to_bytes(4, "little", signed=True))
        out.append(h.digest())
    return out


@dataclasses.dataclass
class PrefixEntry:
    """One cached prompt prefill: its prompt pages + last-position logits.

    ``tail`` (when the prompt is not page-aligned) holds only
    ``tail_valid`` valid positions — readers must COW-copy it before
    writing at their own frontier.  Cold entries hold codec payloads
    instead of pages (``full_pages`` empty, ``tail`` None)."""
    digest: bytes
    n_tok: int
    full_pages: List[int]
    tail: Optional[int]
    tail_valid: int
    logits: Any                        # [1, 1, V] device, prefill last pos
    last_used: int = 0
    hits: int = 0
    cold: bool = False
    payloads: Optional[List[Dict[str, Any]]] = None
    n_full: int = 0                    # layout memo for cold revival
    had_tail: bool = False

    def pages(self) -> List[int]:
        return self.full_pages + ([self.tail] if self.tail is not None
                                  else [])


class PrefixCache:
    """Host-side index of :class:`PrefixEntry` keyed by prompt digest.

    ``lookup`` probes every prefix length of the incoming prompt, longest
    first.  Entries are evicted LRU (``last_used`` is an admission counter,
    not wall time) when admissions need their pages or the entry bound is
    hit; evicting only releases the *cache's* references, so pages shared
    with in-flight requests stay alive until those requests finish.
    """

    def __init__(self, alloc: PageAllocator, max_entries: int = 128):
        self.alloc = alloc
        self.max_entries = max_entries
        self.entries: Dict[bytes, PrefixEntry] = {}
        self.clock = 0                 # admission counter (LRU + cold age)
        self.evictions = 0

    def lookup(self, prompt: np.ndarray) -> Optional[PrefixEntry]:
        ds = _prefix_digests(prompt)
        for i in range(len(ds) - 1, -1, -1):
            e = self.entries.get(ds[i])
            if e is not None:
                return e
        return None

    def insert(self, prompt: np.ndarray, pages: List[int], logits,
               page_size: int) -> Optional[PrefixEntry]:
        """Remember a freshly prefilled prompt.  ``pages`` is the owning
        row's page list; the cache retains its own reference on each prompt
        page so they outlive the request."""
        digest = _prefix_digests(prompt)[-1]
        existing = self.entries.get(digest)
        if existing is not None:
            existing.last_used = self.clock
            return existing
        n_tok = int(len(prompt))
        n_full = n_tok // page_size
        tail = pages[n_full] if n_tok % page_size else None
        full = list(pages[:n_full])
        for pid in full + ([tail] if tail is not None else []):
            self.alloc.retain(pid)
        e = PrefixEntry(digest=digest, n_tok=n_tok, full_pages=full,
                        tail=tail, tail_valid=n_tok % page_size,
                        logits=logits, last_used=self.clock)
        while len(self.entries) >= self.max_entries:
            lru = min(self.entries, key=lambda d: self.entries[d].last_used)
            self.evict_entry(lru)
        self.entries[digest] = e
        return e

    def evict_entry(self, digest: bytes) -> int:
        """Drop one entry; returns how many pages went back to the free
        list (0 for cold entries or pages still shared with requests)."""
        e = self.entries.pop(digest)
        freed = 0
        if not e.cold:
            for pid in e.pages():
                freed += self.alloc.release(pid)
        self.evictions += 1
        return freed

    def make_room(self, n_short: int) -> int:
        """Evict LRU entries until ~``n_short`` pages came free (or no hot
        entry remains)."""
        gained = 0
        while gained < n_short:
            hot = [d for d, e in self.entries.items() if not e.cold]
            if not hot:
                break
            lru = min(hot, key=lambda d: self.entries[d].last_used)
            gained += self.evict_entry(lru)
        return gained

    def reclaimable(self) -> int:
        """Pages that evicting every idle entry would free right now
        (refcount 1 = held only by the cache)."""
        return sum(1 for e in self.entries.values() if not e.cold
                   for pid in e.pages() if self.alloc.refs.get(pid) == 1)


# ---------------------------------------------------------------------------
# Device helpers (jitted once; page ids are traced scalars)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, donate_argnums=(0,))
def _copy_page(pool, src, dst):
    """Copy-on-write split: duplicate physical page ``src`` into ``dst``
    across every pool leaf.  The pool is donated (callers rebind the
    result), so only the touched page is written, not the whole pool."""
    return jax.tree_util.tree_map(lambda p: p.at[:, dst].set(p[:, src]),
                                  pool)


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_pages(pool, idx, values):
    """Scatter revived page contents into the pool.  Donated like
    :func:`_copy_page`, so only the ``idx`` pages are written in place
    instead of materializing a full pool-sized copy per leaf."""
    return jax.tree_util.tree_map(
        lambda leaf, v: leaf.at[:, idx].set(v.astype(leaf.dtype)),
        pool, values)


@jax.jit
def _set_row(tok, lengths, keys, temps, slot, tok0, length, key, temp):
    """Write one slot's decode-state row.  The slot index is traced — eager
    ``.at[int].set`` would bake it in and recompile per slot."""
    return (tok.at[slot].set(tok0), lengths.at[slot].set(length),
            keys.at[slot].set(key), temps.at[slot].set(temp))


# ---------------------------------------------------------------------------
# Paged pool
# ---------------------------------------------------------------------------

class PagedPool:
    """Paged drop-in for :class:`~repro.serving.engine.SlotPool`.

    Same host interface (``admit`` / ``evict`` / ``drain`` /
    ``decode_chunk`` / ``free_slots`` / ``n_active``) so
    :class:`~repro.serving.engine.ServingRuntime` drives either, plus
    ``can_admit`` (page-commitment check) and ``page_stats``.  Decode is
    ONE jitted executable per (plan, rows, max_pages, chunk): page tables,
    caps, and lengths are traced inputs, so admissions and page growth
    never recompile.
    """

    def __init__(self, session, plan, n_rows: int, *, n_pages: int,
                 page_size: int, max_pages: int, prefix_cache: bool = True,
                 cold_horizon: Optional[int] = None,
                 cold_codec: str = "int8", max_entries: int = 128):
        if n_pages < max_pages:
            raise ValueError(
                f"n_pages ({n_pages}) < max_pages ({max_pages}): a "
                "max-length request could never be admitted")
        from repro.serving.engine import _placeholder_keys
        self.session = session
        self.plan = plan
        self.n_rows = n_rows
        self.n_pages = n_pages
        self.page_size = page_size
        self.max_pages = max_pages
        self.cold_horizon = cold_horizon
        self.cold_codec = cold_codec
        # +1 page: the trash page (id == n_pages) absorbing idle-row writes
        self.pool = session.init_page_pool(n_pages + 1, page_size)
        if cold_horizon is not None and "c_kv" in self.pool["kv"]:
            raise NotImplementedError(
                "cold-page quantization has no latent (MLA) page layout; "
                "serve this model with cold_horizon=None")
        # a model with routed experts reports the tokens each held expert
        # computed, per chunk (read on the host only when traced)
        self.has_experts = session.cfg.moe is not None
        self.trash = n_pages
        self.alloc = PageAllocator(n_pages)
        self.prefix = (PrefixCache(self.alloc, max_entries=max_entries)
                       if prefix_cache else None)
        self.page_table = np.full((n_rows, max_pages), self.trash, np.int32)
        self.row_pages: List[List[int]] = [[] for _ in range(n_rows)]
        self.row_committed = [0] * n_rows
        self.row_len = [0] * n_rows
        self.tok = jnp.zeros((n_rows,), jnp.int32)
        self.lengths = jnp.zeros((n_rows,), jnp.int32)
        self.keys = _placeholder_keys(n_rows)
        self.temps = jnp.zeros((n_rows,), jnp.float32)
        self.slots: List[Optional[Any]] = [None] * n_rows
        self.tracer = None                 # set by ServingRuntime._pool
        self.trace_worker = ""
        self.clock = time.monotonic        # the runtime's, set with tracer
        self.last_chunk: Optional[Dict[str, Any]] = None   # traced only
        self.stats = {"prefix_hits": 0, "prefix_misses": 0, "full_hits": 0,
                      "partial_hits": 0, "cow_splits": 0, "cold_pages": 0,
                      "dequant_pages": 0}
        # pages the in-flight admit alloc'd/retained (rollback journal)
        self._acquired: List[int] = []

    # -- occupancy -----------------------------------------------------------

    def free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s is None]

    @property
    def n_active(self) -> int:
        return sum(s is not None for s in self.slots)

    def _need(self, req: Request) -> int:
        return -(-req.total_len // self.page_size)

    def can_admit(self, req: Request) -> bool:
        """Whole-request page commitment against the free list (counting
        pages LRU prefix eviction could reclaim).  Conservative: prefix
        sharing would lower the true need, but a hit is only known at
        admission."""
        avail = self.alloc.available()
        if self.prefix is not None:
            avail += self.prefix.reclaimable()
        return self._need(req) <= avail

    # -- admission -----------------------------------------------------------

    def _reserve(self, n: int) -> bool:
        if self.alloc.available() < n and self.prefix is not None:
            self.prefix.make_room(n - self.alloc.available())
        if self.alloc.available() < n:
            return False
        self.alloc.commit(n)
        return True

    def admit(self, req: Request, slot: int, exec_key: str,
              extrapolated: bool, now: float):
        """Admit one request into ``slot``: prefix-cache probe, then the
        full-hit / partial-hit / miss path.  Commits the request's whole
        page need first, so later on-demand growth cannot starve."""
        from repro.serving.engine import _Active
        from repro.transport import plan_wire_bytes
        if self.slots[slot] is not None:
            raise RuntimeError(f"row {slot} is occupied")
        ps = self.page_size
        if req.total_len > self.max_pages * ps:
            raise ValueError(
                f"request needs {req.total_len} positions but the page "
                f"table is sized for {self.max_pages * ps}; raise "
                "ServingRuntime(max_len=)")
        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        T0 = int(prompt.shape[0])
        P0 = -(-T0 // ps)
        total = self._need(req)

        entry = None
        reserved = 0
        if self.prefix is not None:
            self.prefix.clock += 1
            entry = self.prefix.lookup(prompt)
            if entry is not None and entry.cold:
                entry = self._revive(entry)
            if entry is not None:
                # Shield the entry from the LRU sweep _reserve may run:
                # its last_used is otherwise bumped only by the hit
                # handlers, so make_room could evict it out from under us
                # and free the very pages the hit is about to retain.
                entry.last_used = self.prefix.clock
                reserved = total - len(entry.full_pages)
                if not self._reserve(reserved):
                    entry, reserved = None, 0  # pressure: fall back to miss
                elif self.prefix.entries.get(entry.digest) is not entry:
                    # make_room evicted it anyway (it was the only hot
                    # entry); its cache-only pages are free again and the
                    # hit is void — return the reservation, run as a miss
                    self.alloc.uncommit(reserved)
                    entry, reserved = None, 0

        if entry is None:
            reserved = total
            if not self._reserve(total):
                raise PagesExhausted(
                    f"admission needs {total} pages; "
                    f"{self.alloc.available()} available")

        committed0 = self.alloc.committed
        self._acquired = []
        try:
            if entry is None:
                pages, first_tok, prompt_wire = self._admit_miss(
                    prompt, P0, slot, req)
            elif entry.n_tok == T0:
                pages, first_tok = self._admit_full_hit(entry, slot, req, T0)
                prompt_wire = 0        # no prefill ran, nothing crossed wire
            else:
                pages, first_tok = self._admit_partial_hit(
                    entry, prompt, P0, slot, req)
                prompt_wire = T0 - entry.n_tok

            self.page_table[slot, :len(pages)] = pages
            self.row_pages[slot] = pages
            self.row_committed[slot] = total - P0
            self.row_len[slot] = T0
            wire = plan_wire_bytes(self.plan, self.session.cfg, 1,
                                   prompt_wire) if prompt_wire else 0
            act = _Active(request=req, admitted_ts=now, exec_key=exec_key,
                          extrapolated=extrapolated, first_tok=first_tok,
                          codec=(self.plan.effective_codec if wire else ""),
                          wire_bytes=wire)
            self.slots[slot] = act
        except BaseException:
            self._rollback_admit(slot, reserved, committed0)
            raise
        finally:
            self._acquired = []
        if self.prefix is not None and self.cold_horizon is not None:
            self._sweep_cold()
        return act

    def _rollback_admit(self, slot: int, reserved: int,
                        committed0: int) -> None:
        """Undo a failed admission: release every page it alloc'd or
        retained, return the unspent part of its reservation, and clear
        the row, so one bad admit cannot shrink the pool for everyone
        after it.  References the prefix cache took for itself (via
        ``insert``) are the cache's own and stay."""
        drawn = committed0 - self.alloc.committed
        for pid in self._acquired:
            self.alloc.release(pid)
        self.alloc.uncommit(reserved - drawn)
        self.slots[slot] = None
        self.row_pages[slot] = []
        self.row_committed[slot] = 0
        self.row_len[slot] = 0
        self.page_table[slot, :] = self.trash

    def _admit_miss(self, prompt, P0: int, slot: int, req: Request):
        """Prefill at page-aligned length, scatter into fresh pages, and
        remember the prompt in the prefix cache."""
        ps = self.page_size
        ids = self.alloc.alloc(P0)
        self._acquired.extend(ids)
        with maybe_span(self.tracer, "prefill", kind="serving",
                        worker=self.trace_worker,
                        prompt_len=int(prompt.shape[0]), hit="miss"):
            tok0, cache, key, logits = self.session.prime_slot(
                jnp.asarray(prompt[None]), total_len=P0 * ps,
                plan=self.plan, seed=req.seed,
                temperature=req.temperature, with_logits=True)
        with maybe_span(self.tracer, "admit", kind="serving",
                        worker=self.trace_worker, slot=slot, pages=P0):
            (self.pool, self.tok, self.lengths, self.keys, self.temps) = \
                self.session.admit_paged(self.pool, self.tok, self.lengths,
                                         self.keys, self.temps, cache,
                                         jnp.asarray(ids, jnp.int32), slot,
                                         tok0, len(prompt), key,
                                         req.temperature)
        if self.prefix is not None:
            self.stats["prefix_misses"] += 1
            self.prefix.insert(prompt, ids, logits, ps)
        return list(ids), tok0, len(prompt)

    def _cow_tail(self, entry: PrefixEntry) -> int:
        """COW split of an unaligned shared tail page: the admitting
        request writes at its frontier inside this page, so it gets a
        private copy (sharers keep reading the original)."""
        dst = self.alloc.alloc(1)[0]
        self._acquired.append(dst)
        with maybe_span(self.tracer, "cow_split", kind="serving",
                        worker=self.trace_worker, src=int(entry.tail),
                        dst=int(dst)):
            self.pool = _copy_page(self.pool, entry.tail, dst)
        self.stats["cow_splits"] += 1
        return dst

    def _admit_full_hit(self, entry: PrefixEntry, slot: int, req: Request,
                        T0: int):
        """Exact-prompt hit: zero prefill.  First token is sampled from the
        entry's cached logits with this request's own key — the same
        split/argmax/categorical tail a miss applies, so the token chain is
        identical."""
        pages = []
        for pid in entry.full_pages:
            self.alloc.retain(pid)
            self._acquired.append(pid)
            pages.append(pid)
        if entry.tail is not None:
            pages.append(self._cow_tail(entry))
        with maybe_span(self.tracer, "admit", kind="serving",
                        worker=self.trace_worker, slot=slot, hit="full"):
            (self.tok, self.lengths, self.keys, self.temps) = \
                self.session.hit_paged(self.tok, self.lengths, self.keys,
                                       self.temps, slot, entry.logits, T0,
                                       jax.random.key(req.seed),
                                       req.temperature)
        entry.hits += 1
        entry.last_used = self.prefix.clock
        self.stats["prefix_hits"] += 1
        self.stats["full_hits"] += 1
        return pages, self.tok[slot][None, None]

    def _admit_partial_hit(self, entry: PrefixEntry, prompt, P0: int,
                           slot: int, req: Request):
        """Prompt extends a cached prefix: retain the shared full pages,
        COW-copy the unaligned tail, then teacher-force only the suffix
        through the pool (scanned prefill ≡ single-pass for these
        families)."""
        n = entry.n_tok
        pages = []
        for pid in entry.full_pages:
            self.alloc.retain(pid)
            self._acquired.append(pid)
            pages.append(pid)
        if entry.tail is not None:
            pages.append(self._cow_tail(entry))
        grown = self.alloc.alloc(P0 - len(pages))
        self._acquired.extend(grown)
        pages.extend(grown)
        self.page_table[slot, :P0] = pages
        with maybe_span(self.tracer, "prefill", kind="serving",
                        worker=self.trace_worker, hit="partial",
                        cached=int(n),
                        prompt_len=int(prompt.shape[0])):
            tok0, self.pool, key, logits = self.session.suffix_paged(
                self.pool, jnp.asarray(self.page_table[slot:slot + 1]),
                jnp.asarray(prompt[None, n:]), jnp.asarray([n], jnp.int32),
                jax.random.key(req.seed), req.temperature, plan=self.plan)
        (self.tok, self.lengths, self.keys, self.temps) = _set_row(
            self.tok, self.lengths, self.keys, self.temps, slot, tok0[0, 0],
            len(prompt), key, float(req.temperature))
        entry.hits += 1
        entry.last_used = self.prefix.clock
        self.stats["prefix_hits"] += 1
        self.stats["partial_hits"] += 1
        if self.prefix is not None:
            self.prefix.insert(prompt, pages, logits, self.page_size)
        return pages, tok0

    # -- eviction ------------------------------------------------------------

    def evict(self, slot: int):
        act, self.slots[slot] = self.slots[slot], None
        for pid in self.row_pages[slot]:
            self.alloc.release(pid)
        self.alloc.uncommit(self.row_committed[slot])
        self.row_pages[slot] = []
        self.row_committed[slot] = 0
        self.row_len[slot] = 0
        self.page_table[slot, :] = self.trash
        return act

    def drain(self) -> List[Request]:
        """Drop every in-flight request (fault re-admission path)."""
        reqs = []
        for i, act in enumerate(self.slots):
            if act is not None:
                reqs.append(self.evict(i).request)
        return reqs

    # -- decode --------------------------------------------------------------

    def _ensure(self, row: int, n_steps: int) -> None:
        """Grow the row's page table to cover the whole next chunk, drawing
        on the commitment made at admission (never past the request's total
        need — once the request is done, extra steps clamp at ``caps``)."""
        ps = self.page_size
        act = self.slots[row]
        total = self._need(act.request)
        need = min(-(-(self.row_len[row] + n_steps) // ps), total)
        extra = need - len(self.row_pages[row])
        if extra > 0:
            ids = self.alloc.alloc(extra)
            start = len(self.row_pages[row])
            self.page_table[row, start:start + extra] = ids
            self.row_pages[row].extend(ids)
            self.row_committed[row] -= extra

    def decode_chunk(self, n_steps: int) -> float:
        """One chunk over all rows; appends tokens to active requests and
        returns the wall ms the chunk took (straggler signal).  With a
        tracer, ``last_chunk`` holds the chunk's phase stamps and counts
        (``engine.chunk_record``, plus ``pages_valid``: distinct pages
        holding valid tokens of busy rows after the chunk, ``pages_held``:
        pages off the free list, ``kv_blocks_walked`` of
        ``kv_blocks_table``: the paged kernel's blocks at the last step,
        and for a model with routed experts ``experts_touched`` of
        ``experts_held``: held experts that computed at least one token,
        summed over MoE layers and steps, of all of them, and
        ``expert_tokens``: the (token, expert) pairs they computed)."""
        traced = self.tracer is not None
        t0 = time.perf_counter()
        for row, act in enumerate(self.slots):
            if act is not None:
                self._ensure(row, n_steps)
        ps = self.page_size
        caps = np.asarray([max(len(p), 1) * ps - 1 for p in self.row_pages],
                          np.int32)
        toks, self.pool, self.lengths, self.keys, *experts = \
            self.session.paged_decode_chunk(
                self.pool, jnp.asarray(self.page_table), jnp.asarray(caps),
                self.tok, self.lengths, self.keys, self.temps,
                n_steps=n_steps, plan=self.plan,
                with_counts=traced and self.has_experts)
        self.tok = toks[:, -1]
        waited = self.clock() if traced else 0.0
        out = np.asarray(toks)
        wall_ms = 1e3 * (time.perf_counter() - t0)
        if traced:
            from repro.serving.engine import chunk_record
            rec = chunk_record(self, n_steps, waited, self.clock())
            # each row's attended length at the chunk's last step
            last = np.minimum(np.asarray(self.row_len) + n_steps - 1,
                              caps) + 1
        for i, act in enumerate(self.slots):
            if act is None:
                continue
            self.row_len[i] += n_steps
            if act.done:
                continue
            need = act.request.n_new - act.emitted
            act.tokens.extend(int(t) for t in out[i, :need])
        if traced:
            valid = set()
            for row, act in enumerate(self.slots):
                if act is not None:
                    valid.update(
                        self.row_pages[row][:-(-self.row_len[row] // ps)])
            rec["counts"].update(
                pages_valid=len(valid),
                pages_held=self.n_pages - len(self.alloc.free),
                **self._kv_blocks(last))
            if experts:
                tokens = np.asarray(experts[0])     # [steps, layers, held]
                rec["counts"].update(
                    experts_touched=int((tokens > 0).sum()),
                    experts_held=int(tokens.size),
                    expert_tokens=int(tokens.sum()))
            self.last_chunk = rec
        return wall_ms

    def _kv_blocks(self, lengths: np.ndarray) -> Dict[str, int]:
        """Blocks the paged decode kernel walks for rows of ``lengths``
        (``kv_blocks_walked``), and the blocks of the whole table
        (``kv_blocks_table``): the K/V kernel's or the latent kernel's,
        whichever reads this pool's leaves."""
        from repro.kernels.dispatch import paged_pages_per_block
        ppb = paged_pages_per_block(self.pool, self.max_pages)
        bk = ppb * self.page_size
        return {"kv_blocks_walked": int(sum(-(-lengths // bk))),
                "kv_blocks_table": self.n_rows * -(-self.max_pages // ppb)}

    # -- cold pages (lossy; off unless cold_horizon is set) ------------------

    def _codec(self):
        from repro.transport.codecs import CodecSpec, get_codec
        return get_codec(self.cold_codec), CodecSpec(param=0)

    def _sweep_cold(self) -> None:
        """Quantize pages of prefix entries idle past ``cold_horizon``
        admissions and return them to the free list.  The entry's valid
        region is stable (rows never write below their own frontier), so
        the snapshot is consistent even while sharers decode."""
        for e in list(self.prefix.entries.values()):
            if e.cold or self.prefix.clock - e.last_used < self.cold_horizon:
                continue
            codec, spec = self._codec()
            idx = jnp.asarray(e.pages(), jnp.int32)
            with maybe_span(self.tracer, "codec_encode", kind="serving",
                            worker=self.trace_worker,
                            codec=self.cold_codec,
                            pages=int(idx.shape[0]), cold=True):
                leaves, _ = jax.tree_util.tree_flatten(self.pool)
                e.payloads = [codec.encode(leaf[:, idx].astype(jnp.float32),
                                           spec) for leaf in leaves]
            e.n_full = len(e.full_pages)
            e.had_tail = e.tail is not None
            for pid in e.pages():
                self.alloc.release(pid)
            e.full_pages, e.tail, e.cold = [], None, True
            self.stats["cold_pages"] += int(idx.shape[0])

    def _revive(self, e: PrefixEntry) -> Optional[PrefixEntry]:
        """Dequantize a cold entry back into fresh (uncommitted) pages;
        under pressure the entry is dropped instead and the admission runs
        as a miss."""
        n = e.n_full + (1 if e.had_tail else 0)
        if self.alloc.available() < n:
            self.prefix.make_room(n - self.alloc.available())
        if self.alloc.available() < n:
            self.prefix.evict_entry(e.digest)
            return None
        codec, spec = self._codec()
        ids = self.alloc.alloc(n, committed=False)
        idx = jnp.asarray(ids, jnp.int32)
        with maybe_span(self.tracer, "codec_decode", kind="serving",
                        worker=self.trace_worker, codec=self.cold_codec,
                        pages=int(n), cold=True):
            leaves, treedef = jax.tree_util.tree_flatten(self.pool)
            values = jax.tree_util.tree_unflatten(treedef, [
                codec.decode(p, spec, dtype=leaf.dtype)
                for leaf, p in zip(leaves, e.payloads)])
            self.pool = _write_pages(self.pool, idx, values)
        e.full_pages = list(ids[:e.n_full])
        e.tail = ids[e.n_full] if e.had_tail else None
        e.cold, e.payloads = False, None
        self.stats["dequant_pages"] += n
        return e

    # -- telemetry -----------------------------------------------------------

    def page_stats(self) -> Dict[str, Any]:
        free = len(self.alloc.free)
        out = {"pages_total": self.n_pages, "pages_free": free,
               "pages_committed": self.alloc.committed,
               "page_occupancy": 1.0 - free / self.n_pages}
        out.update(self.stats)
        if self.prefix is not None:
            out["prefix_entries"] = len(self.prefix.entries)
            out["prefix_evictions"] = self.prefix.evictions
            looked = self.stats["prefix_hits"] + self.stats["prefix_misses"]
            out["prefix_hit_rate"] = (self.stats["prefix_hits"] / looked
                                      if looked else 0.0)
        return out
