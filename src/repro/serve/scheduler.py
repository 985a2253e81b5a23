"""Continuous-batching request scheduler + load-adaptive DynaTran controller.

Three host-side pieces, deliberately free of any JAX code so they unit-test
in microseconds:

* ``Request``            — one generation request with SLO/latency metrics.
* ``ContinuousScheduler``— FIFO admission at token granularity over a fixed
  slot count, page-table bookkeeping against one ``PageAllocator`` per page
  KIND ("full" tables grow append-only; "ring" tables for sliding-window
  layers hold a fixed ``ceil(window/P)+1``-page budget and RECYCLE — the
  page that slid fully out of the window is released to the allocator and a
  fresh page is linked into its table slot), and a youngest-first eviction
  policy (the oldest admitted request is never evicted, so admission order
  is starvation-free).  Slot-dense state kinds (rwkv6's recurrent state,
  whisper's cross-KV) need no page bookkeeping at all: the same
  admit/evict/cancel/replay paths run with an empty allocator dict, and
  slot assignment itself is the allocation.
* ``RhoController``      — the paper's accuracy/throughput trade-off closed
  at runtime: queue depth maps monotonically onto DynaTran's target
  sparsity rho (paper §III-A transfer curves make the knob nearly free), so
  the engine sheds accuracy for tokens/s exactly when it is overloaded.
"""
from __future__ import annotations

import dataclasses
import itertools
from collections import deque
from typing import Any, Iterator, Optional

from repro.models.kvcache import HostPageStore, PageAllocator, PrefixCache
from repro.serve.sampling import SamplingParams


@dataclasses.dataclass(eq=False)  # identity semantics: queue membership, handle use
class Request:
    """One generation request and its lifecycle metrics (times are
    ``time.perf_counter`` seconds; step counters are engine ticks).

    ``params`` carries the per-request decode policy (temperature / top-k /
    top-p / seed / stop set / max_new_tokens).  ``max_new_tokens`` and
    ``eos_id`` are kept as deprecated construction aliases: when ``params``
    is not given they build one; when it is, ``params`` wins and a
    non-negative ``eos_id`` is folded into its stop set.
    """

    rid: int
    prompt: list[int]
    max_new_tokens: int = 32  # deprecated alias; mirrors params.max_new_tokens
    eos_id: int = -1  # deprecated alias; folded into params.stop
    slo_s: Optional[float] = None  # end-to-end latency objective
    submit_time: float = 0.0
    params: Optional[SamplingParams] = None
    # per-request inputs beyond the prompt, named by the model's state
    # bundle (``StateBundle.required_inputs``): e.g. whisper's encoder
    # ``frames`` — consumed by the engine's admission hook
    inputs: dict = dataclasses.field(default_factory=dict)

    generated: list[int] = dataclasses.field(default_factory=list)
    slot: Optional[int] = None
    admit_stamp: int = -1  # admission order (monotone; re-stamped on re-admit)
    prefill_pos: int = 0  # replay tokens already cached
    cache_len: int = 0  # K/V entries currently live for this request
    ready: bool = False  # prefill complete, decoding
    pending_token: Optional[int] = None  # next token to feed the decode step
    evictions: int = 0
    cancelled: bool = False
    shed: bool = False  # rejected by a router's admission control (never decoded)
    tenant: Optional[str] = None  # router tenant label (None when engine-direct)
    shared_tokens: int = 0  # prefix-cache tokens linked at the LAST admission
    # engine rho epoch at the LAST admission: prefix-cache registration is
    # gated on it so pages filled before a fleet-level ``set_target_rho``
    # retarget never enter the cache alongside pages filled after it
    rho_epoch: int = 0
    first_token_time: Optional[float] = None
    finish_time: Optional[float] = None
    # page-table state, owned by the scheduler: kind -> page list.  "full"
    # tables are append-ordered (position t lives in entry t // P); "ring"
    # tables are slot-indexed circular arrays (position t in entry
    # (t // P) % budget).  ``ring_hi`` counts page-intervals ever started.
    tables: dict[str, list[int]] = dataclasses.field(default_factory=dict)
    ring_hi: int = 0
    # set by the engine at submit(); streaming/cancel route through it
    _engine: Any = dataclasses.field(default=None, repr=False)
    # memoized PrefixCache.chain_keys(prompt) — the prompt is immutable
    # after submit, and a queue head blocked on pages retries admission
    # (and so the cache lookup) every engine tick
    _prefix_keys: Any = dataclasses.field(default=None, repr=False)
    # host-tier handoff: a drained replica attaches the request's spilled
    # page snapshot here so the adopting engine can seed its own host tier
    # and restore instead of replaying (``engine.adopt`` consumes it)
    _spill: Any = dataclasses.field(default=None, repr=False)

    def __post_init__(self):
        if self.params is None:
            stop = frozenset({self.eos_id}) if self.eos_id >= 0 else frozenset()
            self.params = SamplingParams(max_new_tokens=self.max_new_tokens, stop=stop)
        else:
            if self.eos_id >= 0:
                self.params = self.params.with_stop(self.eos_id)
            self.max_new_tokens = self.params.max_new_tokens

    @property
    def stop_ids(self) -> frozenset[int]:
        """The request's stop-token set (from its ``SamplingParams``)."""
        return self.params.stop

    @property
    def replay(self) -> list[int]:
        """Tokens that must be in the cache before decode can (re)start:
        the prompt plus all generated tokens except the last (which is the
        pending decode input).  Keyed sampling (seed, step) and greedy
        decoding both make eviction + replay bit-exact with the
        uninterrupted run — replayed tokens are fed back, never
        re-sampled."""
        return self.prompt + self.generated[:-1] if self.generated else self.prompt

    @property
    def done(self) -> bool:
        """True once the request finished (budget, stop token, or cancel)."""
        return self.finish_time is not None

    # --- streaming handle -------------------------------------------------
    def tokens(self) -> Iterator[int]:
        """Stream this request's tokens as the engine emits them.  Yields
        every generated token (including any already emitted), driving
        ``engine.step()`` while the request is in flight; returns when the
        request finishes, hits a stop token, or is cancelled."""
        if self._engine is None:
            raise RuntimeError(f"request {self.rid} is not attached to an engine")
        i = 0
        while True:
            while i < len(self.generated):
                yield self.generated[i]
                i += 1
            if self.done:
                return
            self._engine.step()

    def cancel(self) -> None:
        """Cancel this request: its pages are released immediately (whether
        queued, mid-prefill, decoding, or evicted) and its stream ends."""
        if self._engine is None:
            raise RuntimeError(f"request {self.rid} is not attached to an engine")
        self._engine.cancel(self)

    def latency(self) -> Optional[float]:
        """Submit-to-finish wall time in seconds (None while unfinished)."""
        return None if self.finish_time is None else self.finish_time - self.submit_time

    def ttft(self) -> Optional[float]:
        """Time to first token in seconds (None before the first token)."""
        return None if self.first_token_time is None else self.first_token_time - self.submit_time

    def slo_met(self) -> Optional[bool]:
        """Whether latency met the request's SLO (None if no SLO/unfinished)."""
        if self.slo_s is None:
            return None
        lat = self.latency()
        return None if lat is None else lat <= self.slo_s


class ContinuousScheduler:
    """Slot + page bookkeeping for token-granularity continuous batching.

    Admission is strict FIFO: the queue head is admitted as soon as a slot
    is free and every per-kind allocator can hold its replay (+1 decode
    token).  Under page pressure the *youngest* admitted request is evicted
    and re-queued at the FRONT of the queue, so relative order is preserved
    and the oldest request always runs to completion — no starvation.

    With a ``prefix_cache``, admission first links the longest cached
    page-aligned prefix of the prompt into the request's "full" table
    (refcount bump, no allocation, no prefill for those tokens) and the
    engine registers freshly prefilled prompt pages back into the cache.
    Any page in a request's WRITE range whose refcount is > 1 — shared with
    another sequence or retained by the cache — is forked copy-on-write
    before the write: a private page is allocated, a device-side page copy
    is queued on ``pending_copies`` (the engine drains it before its next
    jitted call), and the shared page's refcount drops by one.  Under
    allocation pressure the cache's LRU entries are reclaimed before any
    live request is evicted.
    """

    def __init__(
        self,
        slots: int,
        allocators: dict[str, PageAllocator],
        budgets: dict[str, int],
        max_len: int,
        prefix_cache: Optional[PrefixCache] = None,
        page_size: Optional[int] = None,
        host_store: Optional[HostPageStore] = None,
        spill_fn: Any = None,
        restore_fn: Any = None,
    ):
        self.slots = slots
        self.allocators = allocators
        self.budgets = budgets
        self.max_len = max_len
        # slot-dense-only bundles (rwkv6) have no allocators: slot
        # assignment is the allocation, and page bookkeeping is vacuous
        self.page_size = page_size or (next(iter(allocators.values())).page_size if allocators else 1)
        self.prefix_cache = prefix_cache
        self.pending_copies: list[tuple[int, int]] = []  # "full"-kind (src, dst) COW forks
        # host page tier (the evict ladder's middle rung, engine-wired):
        # ``spill_fn(req) -> payload|None`` fetches the request's device
        # pages to host; ``restore_fn(payload, {kind: pages})`` uploads a
        # payload back onto freshly allocated pages, EAGERLY (it drains any
        # queued COW copies first, so device ops apply in queue order and a
        # restored page is never read or forked before its content lands)
        self.host_store = host_store
        self.spill_fn = spill_fn
        self.restore_fn = restore_fn
        # monotonic tier counters (the engine's metrics preserve them
        # across clear_history, like total_tokens)
        self.spills = 0  # evictions whose pages reached the host tier
        self.spilled_pages = 0
        self.restores = 0  # re-admissions served from the host tier
        self.restored_pages = 0
        self.tier_replays = 0  # re-admissions that fell back to prompt replay
        self.prefix_linked_tokens = 0  # prompt tokens linked from the prefix cache
        self.queue: deque[Request] = deque()
        self.active: dict[int, Request] = {}  # slot -> request
        self._free_slots = list(range(slots - 1, -1, -1))
        self._stamps = itertools.count()

    @property
    def queue_depth(self) -> int:
        """Requests waiting for admission (slots/pages) — the rho signal."""
        return len(self.queue)

    @property
    def num_active(self) -> int:
        """Requests currently holding an engine slot."""
        return len(self.active)

    def _peak_pages(self, kind: str, tokens: int) -> int:
        """Pages a request holding ``tokens`` cache entries occupies in
        ``kind``'s pool (ring tables never exceed their fixed budget)."""
        return min(self.allocators[kind].pages_for(tokens), self.budgets[kind])

    def submit(self, req: Request) -> None:
        """Enqueue ``req`` for admission, validating that its worst-case
        token count fits ``max_len`` and every page-kind budget."""
        max_tokens = len(req.prompt) + req.max_new_tokens
        if max_tokens > self.max_len:
            raise ValueError(f"request {req.rid}: {max_tokens} tokens exceeds max_len")
        for kind, alloc in self.allocators.items():
            if self._peak_pages(kind, max_tokens) > alloc.num_pages - 1:
                raise ValueError(f"request {req.rid}: {kind} page pool cannot hold {max_tokens} tokens")
        self.queue.append(req)

    def _alloc_pages(self, kind: str, rid: int, n: int) -> Optional[list[int]]:
        """Allocate through the cache-reclaim fallback: when the pool is dry,
        LRU prefix-cache entries are dropped (cheapest memory to give back —
        no live request loses state) until the allocation fits or the cache
        is drained.  A reclaimed entry only frees its page if no live
        sequence still shares it, hence the loop."""
        alloc = self.allocators[kind]
        while True:
            pages = alloc.alloc(rid, n)
            if pages is not None:
                return pages
            if self.prefix_cache is None or kind != "full" or not self.prefix_cache.reclaim():
                return None

    def _cow_write_range(self, req: Request, table: list[int], write_start: int) -> bool:
        """Fork every "full" page in ``req``'s write range whose refcount is
        > 1: the request is about to write positions >= ``write_start``, and
        a write must never land on a page another sequence (or the prefix
        cache) can read.  The fork allocates a private page, queues a
        device-side page copy, and drops the request's link on the shared
        page.  Returns False if the pool cannot supply a fork page."""
        alloc = self.allocators["full"]
        for idx in range(write_start // self.page_size, len(table)):
            if alloc.refcount(table[idx]) <= 1:
                continue
            fresh = self._alloc_pages("full", req.rid, 1)
            if fresh is None:
                return False
            self.pending_copies.append((table[idx], fresh[0]))
            alloc.release(req.rid, table[idx])
            table[idx] = fresh[0]
        return True

    def _link_prefix(self, req: Request) -> int:
        """Link the longest cached prefix of ``req.prompt`` into its "full"
        table (refcounted, copy-on-write), reading THROUGH the host tier:
        chain entries whose device pages were reclaimed but whose contents
        were spilled are restored onto fresh pages and re-registered, so a
        cached chain survives device pressure.  Returns the position
        prefill should start from.  A fresh request whose WHOLE prompt is
        cached still recomputes its last prompt token (the engine needs its
        logits to emit the first generated token); that token's K/V write
        lands in the last shared page, which ``_cow_write_range`` then
        forks."""
        req.shared_tokens = 0
        if self.prefix_cache is None:
            return 0
        if req._prefix_keys is None:
            req._prefix_keys = self.prefix_cache.chain_keys(req.prompt)
        pages = self.prefix_cache.lookup_keys(req._prefix_keys)
        if pages:
            self.allocators["full"].share(req.rid, pages)
            req.tables.setdefault("full", []).extend(pages)
        n_linked = len(pages) + self._readmit_prefix_chain(req, len(pages))
        if not n_linked:
            return 0
        shared = n_linked * self.page_size
        if not req.generated and shared == len(req.prompt):
            start = len(req.prompt) - 1
        else:
            start = min(shared, len(req.replay))
        req.shared_tokens = shared
        return start

    def _readmit_prefix_chain(self, req: Request, start: int) -> int:
        """Host-tier read-through for the prefix cache: extend ``req``'s
        device chain (cached entries ``keys[:start]`` already linked) with
        spilled chain entries, restoring each onto a fresh page linked into
        ``req``'s table and re-registered via ``PrefixCache.readmit`` so
        later requests hit it on-device again.  Stops at the first miss or
        when the pool runs dry (the remainder prefills normally).  Returns
        the number of pages readmitted."""
        cache = self.prefix_cache
        if cache is None or cache.host_store is None or self.restore_fn is None:
            return 0
        keys = req._prefix_keys
        n = 0
        for i in range(start, len(keys)):
            if not cache.host_probe(keys[i]):
                break
            pages = self._alloc_pages("full", req.rid, 1)
            if pages is None:
                break
            payload = cache.host_take(keys[i])
            if payload is None:
                # the alloc above may reclaim cache entries, whose write-
                # behind spill can LRU-drop the entry we just probed
                self.allocators["full"].release(req.rid, pages[0])
                break
            self.restore_fn(payload, {"full": pages})
            cache.readmit(keys[i], pages[0], keys[i - 1] if i else None)
            req.tables.setdefault("full", []).extend(pages)
            n += 1
        return n

    def _ensure(
        self,
        req: Request,
        target_tokens: int,
        write_start: Optional[int] = None,
        log: Optional[list] = None,
    ) -> bool:
        """Grow ``req``'s tables to hold ``target_tokens`` cache entries and
        fork any shared page in the write range (positions >=
        ``write_start``, defaulting to ``req.cache_len``).  Returns False
        (keeping partial progress — ``_ensure`` is resumable) when an
        allocator runs dry.

        ``log`` (the speculative-grow undo journal) records every RING
        advance as ``(kind, hi, slot, old_page, new_page)`` with ``hi`` the
        pre-increment ``ring_hi`` and ``old_page`` None for a first-lap
        append — :meth:`truncate` replays it backwards to rewind rejected
        speculation.  Full-table growth needs no journal (append-only:
        rewinding is trimming to ``pages_for``), and copy-on-write forks
        are deliberately NOT journaled — a fork in the write range may
        carry accepted writes, and keeping it is never incorrect, only a
        page of possible waste."""
        if write_start is None:
            write_start = req.cache_len
        for kind, alloc in self.allocators.items():
            budget = self.budgets[kind]
            table = req.tables.setdefault(kind, [])
            if kind == "full":
                need = self._peak_pages(kind, target_tokens) - len(table)
                if need > 0:
                    pages = self._alloc_pages(kind, req.rid, need)
                    if pages is None:
                        return False
                    table.extend(pages)
                if not self._cow_write_range(req, table, write_start):
                    return False
            else:  # ring: fill the first lap, then recycle in place
                hi = -(-target_tokens // self.page_size)
                while req.ring_hi < hi:
                    if len(table) == budget and hi - req.ring_hi > budget:
                        # skipping whole laps is sound once the table is
                        # fully linked: only the trailing ``budget``
                        # intervals decide which page sits in each slot
                        # (a long replay would otherwise churn O(replay/P)
                        # recycles at admission).  Unreachable under a
                        # journaled grow: speculation advances ring_hi by
                        # at most ceil((k+1)/P)+1 <= budget intervals.
                        assert log is None, "lap-skip inside a journaled grow"
                        req.ring_hi = hi - budget
                        continue
                    slot = req.ring_hi % budget
                    if len(table) <= slot:
                        pages = alloc.alloc(req.rid, 1)
                        if pages is None:
                            return False
                        if log is not None:
                            log.append((kind, req.ring_hi, slot, None, pages[0]))
                        table.append(pages[0])
                    else:
                        # the page in this slot holds only positions that
                        # slid fully out of the window (ring capacity is
                        # window + lookahead + at least one page): release
                        # it, then re-link a fresh page — the release
                        # guarantees the alloc can be satisfied
                        alloc.release(req.rid, table[slot])
                        pages = alloc.alloc(req.rid, 1)
                        assert pages is not None, "alloc after release cannot fail"
                        if log is not None:
                            log.append((kind, req.ring_hi, slot, table[slot], pages[0]))
                        table[slot] = pages[0]
                    req.ring_hi += 1
        return True

    def _drop_pages(self, req: Request) -> None:
        # a queued COW copy whose destination page is being freed must not
        # outlive it: the page could be re-allocated before the engine
        # drains, and the stale copy would scatter foreign K/V into it
        # (fork destinations are always refcount-1 pages of this request,
        # so filtering on table membership is exact)
        dropped = set(req.tables.get("full", ()))
        if dropped and self.pending_copies:
            self.pending_copies = [(s, d) for s, d in self.pending_copies if d not in dropped]
        for alloc in self.allocators.values():
            alloc.free(req.rid)
        req.tables = {}
        req.ring_hi = 0

    def _spill(self, req: Request) -> None:
        """Write-behind half of the evict ladder: snapshot ``req``'s device
        page contents plus the replay-relevant cursors into the host tier
        under ``("req", rid)``, BEFORE ``_drop_pages`` recycles the page
        ids.  A spill that cannot happen (no tier, engine veto, payload
        over budget) is silent — eviction falls back to prompt replay,
        exactly as before the tier existed."""
        if self.host_store is None or self.spill_fn is None or not req.tables:
            return
        payload = self.spill_fn(req)
        if payload is None:
            return
        n_pages = sum(len(t) for t in req.tables.values())
        snap = {
            "pages": payload,
            "counts": {kind: len(t) for kind, t in req.tables.items()},
            "ring_hi": req.ring_hi,
            "cache_len": req.cache_len,
            "prefill_pos": req.prefill_pos,
            "ready": req.ready,
            "pending_token": req.pending_token,
            "n_pages": n_pages,
        }
        if self.host_store.put(("req", req.rid), snap, pages=n_pages):
            self.spills += 1
            self.spilled_pages += n_pages

    def _restore(self, req: Request) -> bool:
        """Re-admission through the host tier: allocate fresh device pages
        for every spilled kind, upload the snapshot onto them (eagerly, via
        the engine's ``restore_fn``), and resume ``req`` exactly where
        eviction froze it — O(pages moved), no replay.  Returns False with
        the snapshot left in the store when a pool cannot supply the pages
        yet: the caller stops admitting and retries next tick (falling
        through to replay would both waste the snapshot and re-prefill
        tokens the tier already holds)."""
        snap = self.host_store.peek(("req", req.rid))
        fresh: dict[str, list[int]] = {}
        for kind, n in snap["counts"].items():
            pages = self._alloc_pages(kind, req.rid, n) if n else []
            if pages is None:
                self._drop_pages(req)  # roll back the partial reservation
                return False
            fresh[kind] = pages
        snap = self.host_store.take(("req", req.rid))
        self.restore_fn(snap["pages"], fresh)
        req.tables = {kind: list(pages) for kind, pages in fresh.items()}
        req.ring_hi = snap["ring_hi"]
        req.prefill_pos = snap["prefill_pos"]
        req.cache_len = snap["cache_len"]
        req.ready = snap["ready"]
        req.pending_token = snap["pending_token"]
        req.shared_tokens = 0
        self.restores += 1
        self.restored_pages += snap["n_pages"]
        return True

    def admit_ready(self) -> list[Request]:
        """Admit queue heads while a slot and enough pages are available.
        A queue head with a host-tier snapshot is RESTORED (pages uploaded
        back, decode resumes where eviction froze it).  Otherwise cached
        prefix pages are linked first (so only the tail allocates); a
        request whose whole replay is already cached — a re-admitted
        request hitting its own prompt pages — skips prefill entirely and
        resumes decoding from its last generated token."""
        admitted = []
        while self.queue and self._free_slots:
            req = self.queue[0]
            if req.cancelled:
                self.queue.popleft()
                self._drop_pages(req)
                if self.host_store is not None:
                    self.host_store.pop(("req", req.rid))
                continue
            if self.host_store is not None and self.host_store.contains(("req", req.rid)):
                if not self._restore(req):
                    break  # pool pressure: retry next tick, snapshot stays put
                start = None  # restored: cursors came from the snapshot
            else:
                start = self._link_prefix(req)
                if not self._ensure(req, len(req.replay) + 1, write_start=start):
                    self._drop_pages(req)  # roll back the partial reservation
                    break
                if self.host_store is not None and req.evictions:
                    self.tier_replays += 1  # spill failed or snapshot LRU-dropped
                if self.prefix_cache is not None:  # metrics: count committed admissions only
                    self.prefix_cache.lookups += 1
                    if req.shared_tokens:
                        self.prefix_cache.hits += 1
                        self.prefix_cache.pages_shared += req.shared_tokens // self.page_size
                        self.prefix_linked_tokens += req.shared_tokens
            self.queue.popleft()
            req.slot = self._free_slots.pop()
            req.admit_stamp = next(self._stamps)
            if start is not None:
                req.prefill_pos = start
                req.cache_len = start
                req.ready = start >= len(req.replay)
                if req.ready:  # fully cached replay: resume decode directly
                    req.pending_token = req.generated[-1]
            self.active[req.slot] = req
            admitted.append(req)
        return admitted

    def cancel(self, req: Request) -> None:
        """Release ``req``'s slot and pages immediately, wherever it is in
        its lifecycle: queued (holds nothing), mid-prefill or decoding
        (slot + pages), or evicted (queued again, holds nothing)."""
        if req.slot is not None:
            self.finish(req)
        else:
            try:
                self.queue.remove(req)
            except ValueError:
                pass
            self._drop_pages(req)
        if self.host_store is not None:  # a cancelled snapshot will never restore
            self.host_store.pop(("req", req.rid))

    def register_prefix(self, req: Request) -> int:
        """Offer ``req``'s complete freshly prefilled prompt pages to the
        prefix cache.  The engine calls this after EVERY prefill chunk
        (vLLM-style): a page is registered the moment its last prompt token
        lands, so peers still mid-prefill — including requests admitted in
        the same tick — can link it via ``refresh_prefix`` instead of
        computing their own copy.  Only pages holding exclusively prompt
        positions are cacheable — the partial tail page (written by later
        prefill/decode steps) never is."""
        if self.prefix_cache is None:
            return 0
        n = min(req.prefill_pos, len(req.prompt)) // self.page_size
        table = req.tables.get("full", [])
        if n == 0 or len(table) < n:
            return 0
        if req._prefix_keys is None:
            req._prefix_keys = self.prefix_cache.chain_keys(req.prompt)
        return self.prefix_cache.insert(req.prompt, table[:n], keys=req._prefix_keys)

    def refresh_prefix(self, req: Request) -> None:
        """Mid-prefill cache re-check (the other half of incremental
        registration): link pages that peers registered AFTER ``req``'s
        admission, deduping identical prompts inside a single admission
        wave.  Two moves, both exact (the cache only runs with fixed taus):

        * pages this request has fully written swap to their cached twins
          — the content is bit-identical by construction, so the private
          copy is freed immediately;
        * cached pages covering positions NOT yet prefilled are linked and
          prefill skips ahead; the boundary page (the next write lands in
          it) is forked copy-on-write first, so no shared page is written.
        """
        if self.prefix_cache is None or req.slot is None or req.ready:
            return
        table = req.tables.get("full")
        if not table:
            return
        if req._prefix_keys is None:
            req._prefix_keys = self.prefix_cache.chain_keys(req.prompt)
        pages = self.prefix_cache.lookup_keys(req._prefix_keys)
        if not pages:
            return
        alloc = self.allocators["full"]
        p = self.page_size
        relinked = 0

        def swap(i: int) -> int:
            if table[i] == pages[i]:
                return 0
            alloc.share(req.rid, [pages[i]])
            alloc.release(req.rid, table[i])
            table[i] = pages[i]
            return 1

        cur = req.prefill_pos // p
        # fully-written pages: never written again (full tables are
        # append-only), so swapping to the cached twin is unconditionally safe
        for i in range(min(len(pages), cur, len(table))):
            relinked += swap(i)
        # skip-ahead: cached pages covering unprefilled positions.  A fresh
        # request still recomputes its LAST prompt token (the engine needs
        # its logits for the first generated token), mirroring admission.
        cap = len(req.replay) if req.generated else len(req.prompt) - 1
        new_pos = min(len(pages) * p, cap)
        if new_pos > req.prefill_pos:
            bp = new_pos // p  # boundary page: the next write lands here
            # pin the chain segment about to be linked/copied: the fork
            # allocation below may reclaim prefix-cache entries under pool
            # pressure, and a reclaimed entry of THIS chain would otherwise
            # free the very pages we hold only by lookup
            pinned = pages[cur : min(bp + 1, len(pages))]
            for pg in pinned:
                alloc.retain(pg)
            try:
                if bp < len(pages) and bp < len(table):
                    # it must carry the cached content up to ``new_pos`` but
                    # will be written from there on: fork, don't share
                    fresh = self._alloc_pages("full", req.rid, 1)
                    if fresh is None:  # pool dry: keep prefilling normally
                        return
                    self.pending_copies.append((pages[bp], fresh[0]))
                    alloc.release(req.rid, table[bp])
                    table[bp] = fresh[0]
                    relinked += 1
                for i in range(cur, min(bp, len(table))):
                    relinked += swap(i)
            finally:
                for pg in pinned:
                    alloc.drop(pg)
                if relinked:
                    self.prefix_cache.relinked_pages += relinked
                    relinked = 0
            req.prefill_pos = new_pos
            req.cache_len = new_pos
            if new_pos > req.shared_tokens:
                self.prefix_linked_tokens += new_pos - req.shared_tokens
                req.shared_tokens = new_pos
            req.ready = new_pos >= len(req.replay)
            if req.ready:  # fully-cached replay: resume decode directly
                req.pending_token = req.generated[-1]
        if relinked:
            self.prefix_cache.relinked_pages += relinked

    def prefill_candidates(self) -> list[Request]:
        """Active requests with replay tokens left to cache, oldest first —
        one batched prefill call serves all of them."""
        pending = [r for r in self.active.values() if not r.ready]
        return sorted(pending, key=lambda r: r.admit_stamp)

    def decode_rows(self) -> list[Request]:
        """Prefill-complete active requests in admission order — the rows
        the engine batches into the next decode step."""
        return sorted((r for r in self.active.values() if r.ready), key=lambda r: r.admit_stamp)

    def grow(self, req: Request, new_tokens: int = 1, log: Optional[list] = None) -> bool:
        """Ensure ``req`` has pages for its next ``new_tokens`` cache
        entries, evicting younger requests if a pool is exhausted.
        Returns False if ``req`` itself was evicted to make room for older
        work.  ``log`` journals ring advances for :meth:`truncate` (the
        speculative-rollback path)."""
        # never reserve past the request's own token budget: surplus
        # decode-window writes beyond it are routed out of bounds and
        # dropped, so they need no backing
        budget = len(req.prompt) + req.max_new_tokens
        target = min(req.cache_len + new_tokens, budget, self.max_len)
        while True:
            if self._ensure(req, target, log=log):
                return True
            victim = self._youngest_victim()
            if victim is None:
                raise RuntimeError("page pool exhausted with a single active request")
            self.evict(victim)
            if victim is req:
                return False

    def truncate(self, req: Request, new_len: int, log: Optional[list] = None) -> None:
        """Rewind ``req``'s page bookkeeping to ``new_len`` cache entries —
        the host half of speculative rollback (the device half zeroes the
        span; see ``transformer.paged_rollback_chunk``).  Rollback here is
        eviction's little sibling: where evict+replay truncates to ZERO and
        rebuilds, this truncates to the accepted prefix in place.

        Full tables trim append-order back to ``pages_for(new_len)`` —
        trimmed pages hold only rejected positions (``new_len`` is at least
        one past the pre-speculation length, so admission's reservation and
        any linked prefix pages are never touched).  Ring tables replay the
        grow journal backwards for every advance at interval >=
        ``ceil(new_len / P)``: a first-lap append pops and releases; a
        recycle releases the speculative page and re-claims the exact page
        the advance displaced (its slot twin under a non-speculating
        schedule).  When that page was re-allocated meanwhile,
        ``PageAllocator.claim`` declines and any fresh page substitutes —
        sound because the displaced page's content was already out of the
        attention window when it was recycled (ring capacity covers
        window + lookahead), so nothing ever reads it again."""
        table = req.tables.get("full")
        if table is not None:
            alloc = self.allocators["full"]
            keep = self._peak_pages("full", new_len)
            while len(table) > keep:
                alloc.release(req.rid, table.pop())
        hi_keep = -(-new_len // self.page_size)
        for kind, hi, slot, old, new in reversed(log or []):
            if hi < hi_keep:
                break  # journal is ordered by hi: the rest is accepted
            alloc = self.allocators[kind]
            table = req.tables[kind]
            if old is None:  # first-lap append: undo is pop + release
                assert table[-1] == new, "journal out of sync with ring table"
                alloc.release(req.rid, table.pop())
            else:  # recycle: put the displaced page back in its slot
                alloc.release(req.rid, new)
                if not alloc.claim(req.rid, old):
                    repl = alloc.alloc(req.rid, 1)
                    assert repl is not None, "alloc after release cannot fail"
                    old = repl[0]
                table[slot] = old
            req.ring_hi -= 1
        req.cache_len = new_len

    def _youngest_victim(self) -> Optional[Request]:
        candidates = sorted(self.active.values(), key=lambda r: r.admit_stamp)
        return candidates[-1] if len(candidates) > 1 else None

    def evict(self, req: Request) -> None:
        """Release ``req``'s slot and pages and re-queue it at the front.
        With a host tier the page contents are spilled write-behind first
        (the evict ladder: spill -> replay), so re-admission restores
        O(pages) instead of replaying O(tokens)."""
        self._spill(req)
        self._drop_pages(req)
        self._release_slot(req)
        req.evictions += 1
        req.ready = False
        req.prefill_pos = 0
        req.cache_len = 0
        self.queue.appendleft(req)

    def drain(self, *, keep_queue: bool = False) -> list[Request]:
        """Release EVERY request for replay elsewhere (replica drain — the
        router's handoff hook): active requests are evicted in admission
        order (pages dropped, replay state reset exactly as :meth:`evict`
        does) and the queue is emptied behind them, so the returned list
        preserves FIFO order.  Generated tokens ride on the ``Request`` and
        replay through the standard evict+replay path on whichever engine
        re-admits them, so the handoff is lossless.  With a host tier, each
        drained request's spilled snapshot rides along on ``req._spill`` —
        ``engine.adopt`` seeds its own tier from it so the handoff restores
        instead of replaying.  ``keep_queue=True`` drains only the admitted
        requests (partial drain)."""
        out: list[Request] = []
        for req in sorted(self.active.values(), key=lambda r: r.admit_stamp):
            self._spill(req)
            self._drop_pages(req)
            self._release_slot(req)
            req.evictions += 1
            req.ready = False
            req.prefill_pos = 0
            req.cache_len = 0
            out.append(req)
        if not keep_queue:
            out.extend(r for r in self.queue if not r.cancelled)
            self.queue.clear()
        if self.host_store is not None:
            for req in out:
                snap = self.host_store.take(("req", req.rid))
                if snap is not None:
                    req._spill = snap
        return out

    def finish(self, req: Request) -> None:
        """Release a finished request's pages and slot (prefix-cached page
        chains stay behind under their retention refs)."""
        self._drop_pages(req)
        self._release_slot(req)

    def _release_slot(self, req: Request) -> None:
        if req.slot is not None:
            del self.active[req.slot]
            self._free_slots.append(req.slot)
            req.slot = None

    def page_tables(self, req: Request) -> dict[str, list[int]]:
        """The request's page table per kind, zero-padded to the kind's
        budget (page 0 is the reserved trash page, masked out by attention
        lengths)."""
        out = {}
        for kind, budget in self.budgets.items():
            pages = req.tables.get(kind, [])
            out[kind] = pages + [0] * (budget - len(pages))
        return out


class RhoController:
    """Feedback controller closing the paper's accuracy/throughput loop.

    Maps queue depth monotonically onto a target sparsity in
    [rho_min, rho_max] (linear ramp between ``depth_lo`` and ``depth_hi``),
    then first-order-smooths toward it with coefficient ``ema``.  For a
    fixed internal state, a deeper queue never yields a lower rho — the
    monotonicity the scheduler tests pin down.
    """

    def __init__(
        self,
        rho_min: float = 0.0,
        rho_max: float = 0.7,
        depth_lo: int = 1,
        depth_hi: int = 16,
        ema: float = 0.5,
    ):
        if not 0.0 <= rho_min <= rho_max < 1.0:
            raise ValueError("need 0 <= rho_min <= rho_max < 1")
        self.rho_min = rho_min
        self.rho_max = rho_max
        self.depth_lo = depth_lo
        self.depth_hi = depth_hi
        self.ema = ema
        self.rho = rho_min

    def target(self, queue_depth: int) -> float:
        """Raw (unsmoothed) rho for ``queue_depth``: linear from ``rho_min``
        at ``depth_lo`` to ``rho_max`` at ``depth_hi``, clamped."""
        span = max(self.depth_hi - self.depth_lo, 1)
        frac = min(max((queue_depth - self.depth_lo) / span, 0.0), 1.0)
        return self.rho_min + frac * (self.rho_max - self.rho_min)

    def update(self, queue_depth: int) -> float:
        """EMA-step the controller toward ``target(queue_depth)`` and
        return the smoothed rho."""
        self.rho += self.ema * (self.target(queue_depth) - self.rho)
        return self.rho


def pct(xs: list, q: float):
    """Nearest-rank percentile of a sorted list (None when empty)."""
    return xs[min(len(xs) - 1, int(q * len(xs)))] if xs else None


def summarize(requests: list[Request]) -> dict:
    """Tokens, latency percentiles and evictions over finished requests
    (cancelled requests never completed and are left out)."""
    done = [r for r in requests if r.done and not r.cancelled]
    lats = sorted(r.latency() for r in done)
    return {
        "tokens": sum(len(r.generated) for r in done),
        "p50_latency_s": pct(lats, 0.50),
        "p99_latency_s": pct(lats, 0.99),
        "evictions": sum(r.evictions for r in done),
    }
