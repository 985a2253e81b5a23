"""KV / SSM state caches for serving.

Layout: stacked over layers (leading L axis) so the decode step scans layers
exactly like training does.  Attention caches are [L, B, T, Hkv, D]; for
all-sliding-window models T is the window size (ring buffer); SSM/hybrid
models additionally carry recurrent state.

Sharding: T (sequence) shards over "data" when batch is too small to fill it
(the long_500k decode cells), Hkv over "model" — see launch/sharding.py.
"""
from __future__ import annotations

import dataclasses
import hashlib
from collections import deque
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DecodeState:
    """Everything the serve step carries between tokens."""

    k: Array | None  # [L, B, T, Hkv, D] (None for attention-free models)
    v: Array | None
    ssm: Any  # model-specific recurrent state pytree (or None)
    length: Array  # [B] int32: tokens currently in the cache

    def tree_flatten(self):
        return (self.k, self.v, self.ssm, self.length), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def init_attention_cache(
    layers: int, batch: int, max_len: int, n_kv: int, head_dim: int, dtype=jnp.bfloat16
) -> tuple[Array, Array]:
    shape = (layers, batch, max_len, n_kv, head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def update_layer_cache(k_cache: Array, v_cache: Array, k_new: Array, v_new: Array, length: Array, *, ring: bool) -> tuple[Array, Array]:
    """Insert one step's K/V ([B, 1, Hkv, D]) at position ``length`` (per
    batch row).  ``ring=True`` wraps modulo T (sliding-window models)."""
    b, t = k_cache.shape[0], k_cache.shape[1]
    pos = length % t if ring else length
    rows = jnp.arange(b)
    k_cache = k_cache.at[rows, pos].set(k_new[:, 0])
    v_cache = v_cache.at[rows, pos].set(v_new[:, 0])
    return k_cache, v_cache


def cache_bytes(layers: int, batch: int, max_len: int, n_kv: int, head_dim: int, elem_bytes: int = 2) -> int:
    return 2 * layers * batch * max_len * n_kv * head_dim * elem_bytes


# ---------------------------------------------------------------------------
# Decode-state KINDS: the registry behind the serving stack's DecodeState
# abstraction.  Every per-sequence quantity a model carries between decode
# steps is an instance of one registered kind, and the serve layers (engine,
# scheduler, TP sharding, CLI) iterate over a model's declared *bundle* of
# kinds instead of hard-coding "page pools + optional SSM side-state".
#
# A kind answers the four questions the serving stack asks of any state:
#
# * alloc/release (host side) — ``paged`` kinds are backed by a
#   ``PageAllocator`` + per-request page tables (``page_kind`` names the
#   allocator: "full" or "ring"); slot-dense kinds are allocated by slot
#   assignment itself (the scheduler's slot IS the allocation, O(1)/seq).
# * scatter/gather (jitted) — paged kinds route through the entry_* pool
#   ops below; slot-dense kinds index their dense per-slot arrays directly
#   inside the family's paged_decode_step / paged_prefill_chunk.
# * share? (``shareable``) — prefix-cache eligibility: is the state a pure
#   per-position function of the token prefix?  Full-attention pages (bf16
#   AND int8 — quantisation is per-position) are; ring pages (content
#   depends on the write cursor), recurrent SSM state, and encoder cross-KV
#   (depends on per-request frames) are not.  The engine enables prefix
#   caching only when EVERY kind in the bundle is shareable.
# * shard_spec (TP) — how the kind's device arrays shard over the mesh
#   "model" axis: "kv_heads" (page pools split per KV head, page ids
#   shard-invariant) or "replicated" (slot-dense state is tiny and rides
#   whole on every shard).  launch/sharding.py maps this to PartitionSpecs.
#
# Adding a state kind (MoE expert caches, multimodal encoder caches, ...)
# is a registry entry plus a family bundle declaration — not an engine
# rewrite.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StateKind:
    """One registered kind of per-sequence decode state."""

    name: str
    paged: bool  # PageAllocator-backed (vs slot-dense)
    shareable: bool  # prefix-cache eligible (pure function of the prefix)
    tp: str  # "kv_heads" | "replicated" — launch/sharding.py maps to specs
    page_kind: str | None = None  # allocator key for paged kinds
    # side-array kind emitted at scatter time (DynaTran "kv" occupancy bits
    # riding the parent kind's page ids; see init_occupancy below)
    occupancy_kind: str | None = None

    @property
    def spillable(self) -> bool:
        """Host-tier eligibility: a kind can spill to the ``HostPageStore``
        iff it is paged AND registered extract/insert ops in
        ``PAGE_TIER_OPS`` (slot-dense state has no page granularity to move;
        it is replayed instead)."""
        return self.paged and self.name in PAGE_TIER_OPS


STATE_KINDS: dict[str, StateKind] = {}


def register_state_kind(kind: StateKind) -> StateKind:
    if kind.paged and kind.page_kind is None:
        raise ValueError(f"paged state kind {kind.name!r} needs a page_kind")
    STATE_KINDS[kind.name] = kind
    return kind


# DynaTran KV occupancy: one bit per cached position, 1 = live.  A "kv"-site
# policy marks a position dead at scatter time when max|k| < tau_kv; the
# paged decode attention then masks dead positions and SKIPS all-dead pages
# outright.  Occupancy is per-POSITION (not per-KV-head), so under TP it is
# replicated while its parent pools shard on the head axis.
register_state_kind(StateKind("kv-occupancy", paged=False, shareable=True, tp="replicated"))

register_state_kind(StateKind("paged-full", paged=True, shareable=True, tp="kv_heads", page_kind="full", occupancy_kind="kv-occupancy"))
register_state_kind(StateKind("paged-int8", paged=True, shareable=True, tp="kv_heads", page_kind="full", occupancy_kind="kv-occupancy"))
register_state_kind(StateKind("paged-ring", paged=True, shareable=False, tp="kv_heads", page_kind="ring", occupancy_kind="kv-occupancy"))
# slot-dense recurrent state: hymba's Mamba side-state and rwkv6's
# wkv/token-shift state — O(1) per sequence, reset/replayed at admission
register_state_kind(StateKind("slot-ssm", paged=False, shareable=False, tp="replicated"))
# slot-dense encoder cross-attention KV (whisper): computed ONCE at
# admission from the request's frames, read-only thereafter
register_state_kind(StateKind("slot-cross", paged=False, shareable=False, tp="replicated"))


@dataclasses.dataclass(frozen=True)
class StateComponent:
    """One named component of a model's decode state (name keys the device
    pytree; kind keys the registry)."""

    name: str
    kind: str

    @property
    def state_kind(self) -> StateKind:
        return STATE_KINDS[self.kind]


@dataclasses.dataclass(frozen=True)
class StateBundle:
    """A model family's declared per-sequence decode state: what the serve
    stack iterates over instead of hard-coding storage classes.

    ``required_inputs`` names per-request inputs beyond the prompt (e.g.
    whisper's encoder ``frames``); ``admit_compute`` marks bundles whose
    slot-dense state is computed once at admission (the engine runs the
    family's ``admit_slot`` hook for every admitted request).
    """

    components: tuple[StateComponent, ...]
    required_inputs: tuple[str, ...] = ()
    admit_compute: bool = False

    def kinds(self) -> list[StateKind]:
        return [c.state_kind for c in self.components]

    @property
    def paged(self) -> bool:
        return any(k.paged for k in self.kinds())

    @property
    def shareable(self) -> bool:
        """Prefix-cache eligibility of the WHOLE bundle: there must be
        shareable pages to link, and no component may carry per-sequence
        state a cached page cannot reproduce."""
        kinds = self.kinds()
        return any(k.paged for k in kinds) and all(k.shareable for k in kinds)

    @property
    def spillable(self) -> bool:
        """Host-tier eligibility of the WHOLE bundle: an evicted request is
        restorable from host memory only when EVERY kind it carries can
        spill — one slot-dense component (SSM state, encoder cross-KV)
        forces full prompt replay, so spilling the paged part alone would
        buy nothing and still pay the copies."""
        kinds = self.kinds()
        return bool(kinds) and all(k.spillable for k in kinds)

    def describe(self) -> str:
        return " + ".join(c.kind for c in self.components)


# ---------------------------------------------------------------------------
# Paged KV cache: fixed-size pages, free-list allocator, per-sequence page
# tables.  Sequences share one global pool, so total memory scales with live
# tokens instead of slots * max_len — the structural requirement for
# token-granularity continuous batching (vLLM-style paging).
#
# Two page KINDS, derived from the attention pattern:
#
# * "full"  — append-only tables of ``max_len / P`` pages: position t lives
#   in table entry t // P.
# * "ring"  — sliding-window layers get a fixed budget of
#   ``ceil(window / P) + 1`` pages used as a circular array over a logical
#   ring of capacity C = budget * P: position t lives in table entry
#   (t % C) // P.  Because C >= window + P, the slot being overwritten
#   always holds a key that slid fully out of the window, so cache memory
#   scales with ``window`` rather than ``max_len``.  The scheduler recycles
#   the dead page through the allocator (free + re-link) whenever a write
#   crosses into a previously used table slot.
#
# int8-quantised caches store a pool entry as {"q": int8 [.., P, Hkv, D],
# "scale": bf16 [.., P, Hkv]} — per-(position, head) absmax scales in a
# parallel scale pool, dequantised on the gather path with exactly the dense
# cache's ops so paged decode stays bitwise-identical to the dense reference.
# ---------------------------------------------------------------------------

TRASH_PAGE = 0  # reserved scratch page: masked-out rows scatter here


class PageAllocator:
    """Host-side free-list allocator over a fixed pool of KV pages, with
    per-page REFCOUNTS so sequences can share read-only prefix pages.

    Page ``TRASH_PAGE`` (index 0) is reserved as a write sink for inactive
    batch rows, so a jitted decode step can always run full-width: rows with
    no live sequence point their whole page table at the trash page and their
    writes land there harmlessly.

    A page's refcount is the number of links to it: one per sequence table
    entry (``alloc``/``share``) plus one if the prefix cache retains it
    (``retain``).  A page returns to the free list only when its last link
    drops.  Writers must hold the ONLY link (refcount 1) — the scheduler
    enforces this by forking shared pages copy-on-write before any write.
    """

    def __init__(self, num_pages: int, page_size: int):
        if num_pages < 2:
            raise ValueError("need at least 2 pages (page 0 is reserved)")
        self.num_pages = num_pages
        self.page_size = page_size
        # deque: alloc pops the hot end, freed pages prepend to the cold end —
        # both O(1) on the per-token ring-recycle path, and a release/alloc
        # pair never degenerates to an identity swap
        self._free: deque[int] = deque(range(num_pages - 1, TRASH_PAGE, -1))
        self._owned: dict[int, list[int]] = {}  # seq id -> page links, in order
        self._ref: dict[int, int] = {}  # page -> live link count

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def allocated(self) -> set[int]:
        """Pages with at least one live link (invariant: disjoint from the
        free list, together they tile pages 1..num_pages-1)."""
        return set(self._ref)

    @property
    def total_refs(self) -> int:
        return sum(self._ref.values())

    def refcount(self, page: int) -> int:
        return self._ref.get(page, 0)

    def pages_for(self, tokens: int) -> int:
        """Pages needed to hold ``tokens`` cache entries."""
        return -(-tokens // self.page_size)

    def alloc(self, seq_id: int, n: int = 1) -> list[int] | None:
        """Append ``n`` fresh pages (refcount 1) to ``seq_id``'s table; None
        (no-op) if the pool cannot satisfy the request."""
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._ref[p] = 1
        self._owned.setdefault(seq_id, []).extend(pages)
        return pages

    def share(self, seq_id: int, pages: list[int]) -> None:
        """Link already-allocated ``pages`` into ``seq_id``'s table,
        bumping each refcount — the shared-prefix admission path.  The new
        owner must treat them as read-only until forked."""
        for p in pages:
            if p not in self._ref:
                raise ValueError(f"cannot share unallocated page {p}")
            self._ref[p] += 1
        self._owned.setdefault(seq_id, []).extend(pages)

    def retain(self, page: int) -> None:
        """Add an anonymous link (the prefix cache's retention ref)."""
        if page not in self._ref:
            raise ValueError(f"cannot retain unallocated page {page}")
        self._ref[page] += 1

    def drop(self, page: int) -> bool:
        """Drop an anonymous link; True if the page went back to the free
        list (no sequence links it either)."""
        return self._decref(page)

    def _decref(self, page: int, *, hot: bool = False) -> bool:
        """Drop one link; at zero the page joins the free list — the COLD
        end by default (ring recycling and COW forks must rotate through
        the pool), the HOT end for whole-sequence frees (a finished
        request's pages are the natural ones to hand out next)."""
        self._ref[page] -= 1
        if self._ref[page] == 0:
            del self._ref[page]
            if hot:
                self._free.append(page)
            else:
                self._free.appendleft(page)
            return True
        return False

    def owned(self, seq_id: int) -> list[int]:
        return list(self._owned.get(seq_id, ()))

    def free(self, seq_id: int) -> int:
        """Drop all of ``seq_id``'s links; pages with no remaining link
        (not shared, not cache-retained) return to the free list (hot end:
        they are reused first)."""
        pages = self._owned.pop(seq_id, [])
        for p in reversed(pages):
            self._decref(p, hot=True)
        return len(pages)

    def release(self, seq_id: int, page: int) -> None:
        """Drop ONE of ``seq_id``'s links — the ring recycling path (the
        page that slid fully out of the window) and the copy-on-write fork
        path (the writer's link on a still-shared page).  A page whose last
        link drops joins the COLD end of the free list (``alloc`` pops the
        hot end), so pages genuinely rotate through the pool."""
        self._owned[seq_id].remove(page)
        self._decref(page)

    def claim(self, seq_id: int, page: int) -> bool:
        """Claim one SPECIFIC page off the free list (refcount 1, linked to
        ``seq_id``) — the speculative-rollback un-recycle: undoing a ring
        advance must re-link exactly the page the advance released, because
        the table slot's twin (a decode that never speculated) still points
        at it.  Returns False, a no-op, when the page is no longer free
        (re-allocated in the meantime); the caller falls back to ``alloc``
        — any page works there, since the un-recycled slot's content is
        out-of-window by the ring-lookahead invariant and is never read."""
        try:
            self._free.remove(page)
        except ValueError:
            return False
        self._ref[page] = 1
        self._owned.setdefault(seq_id, []).append(page)
        return True


class HostPageStore:
    """Host-memory page tier: a budgeted, insertion-ordered LRU map from
    opaque keys to spilled page payloads (numpy trees fetched off-device by
    the engine).  This is the middle rung of the memory ladder

        device pools  →  host store  →  replay

    Eviction under device pressure SPILLS a request's pages here instead of
    discarding them; re-admission restores them with a ``device_put`` —
    O(pages moved) instead of O(tokens replayed).  Prompt replay remains the
    fallback whenever this tier is full (``put`` returns False) or the
    payload was LRU-dropped before re-admission (``take`` returns None).

    Keys are namespaced tuples chosen by the callers: ``("req", rid)`` for a
    whole evicted request's snapshot, ``("prefix", chain_key)`` for a single
    prefix-cache page.  The store never inspects payloads beyond sizing them
    (anything exposing ``.nbytes``, nested in dicts/lists, is accounted).

    Host-side only: this class never touches jax (enforced by reprolint
    HD201) — device transfers live in the engine, which hands payloads in
    and takes them out.
    """

    def __init__(self, budget_bytes: int):
        self.budget_bytes = int(budget_bytes)
        self._entries: dict[Any, tuple[Any, int, int]] = {}  # key -> (payload, nbytes, pages)
        self.bytes_used = 0
        self.pages_held = 0
        # monotonic op counters (survive engine.clear_history by contract)
        self.puts = 0
        self.takes = 0
        self.rejects = 0  # payload alone exceeded the budget
        self.lru_drops = 0  # entries evicted to make room for a newer put

    @property
    def entries(self) -> int:
        return len(self._entries)

    @staticmethod
    def payload_bytes(payload) -> int:
        """Size of a spilled payload: summed ``.nbytes`` over a tree of
        dicts/lists/tuples of array-likes."""
        if hasattr(payload, "nbytes"):
            return int(payload.nbytes)
        if isinstance(payload, dict):
            return sum(HostPageStore.payload_bytes(v) for v in payload.values())
        if isinstance(payload, (list, tuple)):
            return sum(HostPageStore.payload_bytes(v) for v in payload)
        return 0

    def contains(self, key) -> bool:
        """Membership WITHOUT recency effects (mirrors ``probe_keys``)."""
        return key in self._entries

    def peek(self, key):
        """Return ``key``'s payload without removing it (no counters) —
        callers size a restore's page allocation off the snapshot before
        committing the ``take``."""
        ent = self._entries.get(key)
        return None if ent is None else ent[0]

    def put(self, key, payload, *, pages: int = 0) -> bool:
        """Store ``payload`` under ``key``, evicting oldest entries to fit
        the budget.  Returns False (payload NOT stored, ``rejects`` bumped)
        when the payload alone exceeds the whole budget — the caller falls
        back to replay.  Re-putting a live key replaces it."""
        nbytes = self.payload_bytes(payload)
        if nbytes > self.budget_bytes:
            self.rejects += 1
            return False
        self.pop(key)
        while self.bytes_used + nbytes > self.budget_bytes and self._entries:
            self.pop(next(iter(self._entries)))
            self.lru_drops += 1
        self._entries[key] = (payload, nbytes, pages)
        self.bytes_used += nbytes
        self.pages_held += pages
        self.puts += 1
        return True

    def take(self, key):
        """Pop and return ``key``'s payload (None on miss) — the restore
        path.  Payloads are single-use: a restored request that gets evicted
        again is re-spilled fresh (its pages have grown since)."""
        ent = self._entries.pop(key, None)
        if ent is None:
            return None
        self.bytes_used -= ent[1]
        self.pages_held -= ent[2]
        self.takes += 1
        return ent[0]

    def pop(self, key) -> None:
        """Discard ``key`` silently (cancel, replace, invalidation) — no
        restore is counted."""
        ent = self._entries.pop(key, None)
        if ent is not None:
            self.bytes_used -= ent[1]
            self.pages_held -= ent[2]

    def clear(self) -> None:
        """Drop every entry (rho-epoch bump: spilled K/V were written at the
        old taus and must not serve the new epoch)."""
        self._entries.clear()
        self.bytes_used = 0
        self.pages_held = 0

    def stats(self) -> dict:
        return {
            "entries": self.entries,
            "bytes_used": self.bytes_used,
            "budget_bytes": self.budget_bytes,
            "pages_held": self.pages_held,
            "puts": self.puts,
            "takes": self.takes,
            "rejects": self.rejects,
            "lru_drops": self.lru_drops,
        }


class PrefixCache:
    """Hash-of-prefix → page-chain cache over one ``PageAllocator``: requests
    whose prompts share a page-aligned token prefix link the SAME physical
    pages instead of re-allocating and re-prefilling them.

    Only **full-attention** pages are shareable: a full page's K/V at
    positions ``[i*P, (i+1)*P)`` is a pure function of the token prefix (for
    bf16 AND int8 pools — quantisation is per-position), so any request with
    the same prefix reads bit-identical values through it.  Ring pages are
    per-sequence (their content depends on the sequence's own write cursor)
    and SSM side-state is per-slot recurrent state; neither is cacheable, so
    the engine enables this cache only for all-"full" layouts without SSM
    state.  K/V also depend on the DynaTran taus: the engine disables the
    cache under ADAPTIVE rho (pages filled at one rho must not serve a
    request arriving at another); a fixed rho keeps taus constant, so
    sharing stays exact there.

    Entries form chains: the key for an ``i``-page prefix is a digest folded
    over the previous key and the page's tokens, inserts extend contiguously
    from the root, and reclaim drops LEAF entries only (LRU order) — so a
    cache hit is always a contiguous prefix walk.  Each cached page holds one
    retention ref in the allocator; pages shared with live sequences survive
    a reclaim (the entry is dropped, the page stays until its owners finish).
    """

    def __init__(self, alloc: PageAllocator):
        self.alloc = alloc
        self.page_size = alloc.page_size
        self._page: dict[bytes, int] = {}  # key -> page id
        self._parent: dict[bytes, bytes | None] = {}
        self._children: dict[bytes, int] = {}  # key -> cached child count
        self._stamp: dict[bytes, int] = {}  # key -> last-use tick (LRU)
        self._tick = 0
        # host read-through (engine-wired when tiering is on): ``host_store``
        # is a HostPageStore, ``_spill_page`` an engine callable that fetches
        # one device page's content to host (page id -> payload, or None).
        # With both set, ``reclaim`` spills a dropped entry's content under
        # ("prefix", key) so a later admission can restore the chain via
        # ``host_probe``/``host_take``/``readmit`` instead of re-prefilling.
        self.host_store: HostPageStore | None = None
        self._spill_page = None
        self.host_spills = 0  # entries written through to the host tier
        self.host_restores = 0  # entries readmitted from the host tier
        # metrics, counted by the scheduler per successful admission (an
        # admission blocked on pages retries its lookup every tick — those
        # retries must not inflate the hit rate)
        self.lookups = 0
        self.hits = 0  # admissions that linked >= 1 cached page
        self.pages_shared = 0  # cumulative page links served (pages saved)
        # pages linked MID-prefill (vLLM-style incremental sharing): a
        # request still prefilling swaps/links pages a peer registered
        # after its admission — same-tick bursts dedupe through this
        self.relinked_pages = 0

    @property
    def cached_pages(self) -> int:
        return len(self._page)

    def chain_keys(self, tokens: list[int]) -> list[bytes]:
        """One digest per COMPLETE page of ``tokens``, each folded over its
        parent — chains collide only when the whole prefix matches.  Pure
        in ``tokens``: callers with an immutable prompt (the scheduler)
        memoize the result so admission retries don't re-hash."""
        keys, prev = [], b"prefix-root"
        p = self.page_size
        for i in range(len(tokens) // p):
            h = hashlib.blake2b(prev, digest_size=16)
            h.update(np.asarray(tokens[i * p : (i + 1) * p], np.int64).tobytes())
            prev = h.digest()
            keys.append(prev)
        return keys

    def lookup(self, tokens: list[int]) -> list[int]:
        """Longest cached page chain for this prompt (possibly empty).  The
        caller links the returned pages via ``alloc.share``."""
        return self.lookup_keys(self.chain_keys(tokens))

    def lookup_keys(self, keys: list[bytes]) -> list[int]:
        """``lookup`` over precomputed ``chain_keys`` (the memoized path)."""
        self._tick += 1
        pages = []
        for key in keys:
            page = self._page.get(key)
            if page is None:
                break
            self._stamp[key] = self._tick
            pages.append(page)
        return pages

    def probe_keys(self, keys: list[bytes]) -> int:
        """Length of the cached chain for ``keys`` WITHOUT touching the LRU
        stamps.  The multi-replica router probes every replica's cache per
        routing decision (prefix affinity); a probe that bumped recency
        would let routing *queries* distort reclaim order on replicas the
        request never lands on."""
        n = 0
        for key in keys:
            if key not in self._page:
                break
            n += 1
        return n

    def insert(self, tokens: list[int], pages: list[int], keys: list[bytes] | None = None) -> int:
        """Register ``pages`` (a prefix of the owner's full-kind table —
        pages COMPLETELY filled by prefill, registered as each one fills)
        as this prompt's page chain; existing entries are kept (first writer
        wins — contents are identical by construction).  ``keys`` passes
        precomputed ``chain_keys`` (callers registering chunk-by-chunk
        memoize them).  Returns the number of newly cached pages."""
        self._tick += 1
        added = 0
        parent: bytes | None = None
        for i, key in enumerate(keys if keys is not None else self.chain_keys(tokens)):
            if i >= len(pages):
                break
            if key in self._page:
                parent = key
                continue
            self._page[key] = pages[i]
            self._parent[key] = parent
            self._children[key] = 0
            self._stamp[key] = self._tick
            if parent is not None:
                self._children[parent] += 1
            self.alloc.retain(pages[i])
            parent = key
            added += 1
        return added

    def _drop_entry(self, key: bytes) -> None:
        page = self._page.pop(key)
        parent = self._parent.pop(key)
        del self._children[key]
        del self._stamp[key]
        if parent is not None:
            self._children[parent] -= 1
        # write-behind: spill the page's content to the host tier BEFORE the
        # retention ref drops (the pool slot may be reused immediately).
        # Page content is immutable once cached (COW forks writers), so a
        # copy taken at drop time is exact.
        if self.host_store is not None and self._spill_page is not None:
            payload = self._spill_page(page)
            if payload is not None and self.host_store.put(("prefix", key), payload, pages=1):
                self.host_spills += 1
        self.alloc.drop(page)

    def host_probe(self, key: bytes) -> bool:
        """Does the host tier hold a spilled page for chain ``key``?  No
        recency effects (mirrors ``probe_keys``)."""
        return self.host_store is not None and self.host_store.contains(("prefix", key))

    def host_take(self, key: bytes):
        """Pop chain ``key``'s spilled payload from the host tier (None on
        miss).  The caller allocates a fresh device page, queues the upload,
        and re-registers the entry via ``readmit``."""
        return None if self.host_store is None else self.host_store.take(("prefix", key))

    def readmit(self, key: bytes, page: int, parent: bytes | None) -> None:
        """Re-register a chain entry restored from the host tier onto fresh
        device page ``page`` (already allocated to the restoring sequence —
        this adds the cache's retention ref, exactly like ``insert``).
        ``parent`` is the preceding chain key (None at the root); callers
        walk chains in order, so the parent entry is always present when
        non-None."""
        self._tick += 1
        self._page[key] = page
        self._parent[key] = parent
        self._children[key] = 0
        self._stamp[key] = self._tick
        if parent is not None:
            self._children[parent] += 1
        self.alloc.retain(page)
        self.host_restores += 1

    def reclaim(self) -> bool:
        """Drop the least-recently-used LEAF entry (no cached children — so
        chains stay contiguous).  Returns False when the cache is empty.
        The page only reaches the free list if no live sequence shares it,
        so a caller looping ``reclaim()`` under allocation pressure may need
        several drops before a page actually frees."""
        leaves = [k for k, n in self._children.items() if n == 0]
        if not leaves:
            return False
        self._drop_entry(min(leaves, key=lambda k: self._stamp[k]))
        return True

    def drop_all(self) -> None:
        """Drop every entry (engine shutdown / rho-epoch flush): releases
        all retention refs so the allocator can drain to empty once live
        requests finish.  Spill is bypassed — a flushed cache's contents are
        invalid (epoch bump) or moot (shutdown), and the engine clears the
        host store itself when epochs change."""
        store, self.host_store = self.host_store, None
        try:
            while self.reclaim():
                pass
        finally:
            self.host_store = store

    def stats(self) -> dict:
        return {
            "lookups": self.lookups,
            "hits": self.hits,
            "hit_rate": self.hits / self.lookups if self.lookups else 0.0,
            "pages_shared": self.pages_shared,
            "relinked_pages": self.relinked_pages,
            "host_spills": self.host_spills,
            "host_restores": self.host_restores,
        }


@dataclasses.dataclass(frozen=True)
class PagedLayout:
    """Static description of how a model's attention pattern maps onto page
    pools: one kind per pattern slot, per-kind per-sequence page budgets.

    Hashable and shape-only, so it can close over jitted step functions
    without retracing.
    """

    page_size: int
    max_len: int
    slot_kinds: tuple[str, ...]  # per pattern slot: "full" | "ring"
    window: int = 0  # sliding-window size (0 when no ring slots)
    # decode lookahead: multi-step decode windows reserve (and recycle) ring
    # pages up to ``lookahead`` tokens ahead of the oldest in-window key, so
    # the ring budget must span window + lookahead - 1 tokens or a recycled
    # page could still hold keys the window's FIRST step needs
    lookahead: int = 1

    def __post_init__(self):
        if self.max_len % self.page_size:
            raise ValueError("max_len must be a multiple of page_size")
        if "ring" in self.slot_kinds and not (0 < self.window < self.max_len):
            raise ValueError("ring slots need 0 < window < max_len")

    @classmethod
    def for_config(cls, cfg, max_len: int, page_size: int, lookahead: int = 1) -> "PagedLayout":
        """Derive the layout from a ModelConfig-like object.  A sliding slot
        pages as a ring only when the window actually truncates the cache
        (window < max_len); otherwise it is indistinguishable from full."""
        kinds = tuple(
            "ring" if (pat == "sliding" and 0 < cfg.window < max_len) else "full"
            for pat in cfg.attention_pattern
        )
        return cls(page_size=page_size, max_len=max_len, slot_kinds=kinds,
                   window=cfg.window if "ring" in kinds else 0, lookahead=lookahead)

    @property
    def kinds(self) -> tuple[str, ...]:
        """Unique kinds, "full" first when present."""
        return tuple(k for k in ("full", "ring") if k in self.slot_kinds)

    def budget(self, kind: str) -> int:
        """Pages per sequence for one kind: the page table width.  Ring
        tables hold ceil(window/P) + 1 pages (+ decode lookahead), so ring
        memory scales with ``window`` instead of ``max_len``."""
        if kind == "ring":
            return min(
                -(-(self.window + self.lookahead - 1) // self.page_size) + 1,
                self.max_len // self.page_size,
            )
        return self.max_len // self.page_size

    @property
    def ring_capacity(self) -> int:
        """Logical ring length in tokens (C = ring budget * page size)."""
        return self.budget("ring") * self.page_size


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PagedKV:
    """Device-side page pools, one entry per attention-pattern slot.

    An entry is [n_cycles, num_pages, P, Hkv, D] (bf16 cache) or
    {"q": int8 [..., D], "scale": bf16 [n_cycles, num_pages, P, Hkv]}
    (quantised cache).  Pool sizes may differ per slot: ring slots get
    window-scaled pools.  Page tables and lengths are *not* carried here —
    the scheduler owns them host-side and passes fresh arrays into every
    jitted step (shapes are static, so there is no retrace).
    """

    k: dict[str, Any]
    v: dict[str, Any]

    def tree_flatten(self):
        keys = sorted(self.k)
        return tuple(self.k[i] for i in keys) + tuple(self.v[i] for i in keys), tuple(keys)

    @classmethod
    def tree_unflatten(cls, keys, children):
        n = len(keys)
        return cls(k=dict(zip(keys, children[:n])), v=dict(zip(keys, children[n:])))

    @property
    def page_size(self) -> int:
        first = next(iter(self.k.values()))
        return (first["q"] if isinstance(first, dict) else first).shape[2]

    def bytes(self) -> int:
        """Total pool bytes actually allocated (the memory-scaling bench)."""
        leaves = jax.tree_util.tree_leaves((self.k, self.v))
        return sum(x.size * x.dtype.itemsize for x in leaves)

    def shard_bytes(self) -> int:
        """Pool bytes resident on ONE device — ``bytes() / tp`` when the
        pools are KV-head-sharded over a tensor-parallel mesh, equal to
        ``bytes()`` unsharded (the per-shard memory claim the TP bench
        asserts)."""
        per_device: dict[int, int] = {}
        for x in jax.tree_util.tree_leaves((self.k, self.v)):
            for s in x.addressable_shards:
                per_device[s.device.id] = per_device.get(s.device.id, 0) + s.data.size * x.dtype.itemsize
        return max(per_device.values()) if per_device else 0


def init_paged_pools(
    layout: PagedLayout,
    n_cycles: int,
    num_pages: dict[str, int] | int,
    n_kv: int,
    head_dim: int,
    dtype=jnp.bfloat16,
    *,
    quant: bool = False,
) -> PagedKV:
    """Per-slot pools sized by page kind; ``num_pages`` maps kind -> pool
    pages (an int applies to every kind)."""
    if isinstance(num_pages, int):
        num_pages = {k: num_pages for k in layout.kinds}

    def entry(kind: str):
        shape = (n_cycles, num_pages[kind], layout.page_size, n_kv, head_dim)
        if quant:
            return {"q": jnp.zeros(shape, jnp.int8), "scale": jnp.zeros(shape[:-1], jnp.bfloat16)}
        return jnp.zeros(shape, dtype)

    k = {str(i): entry(kind) for i, kind in enumerate(layout.slot_kinds)}
    v = {str(i): entry(kind) for i, kind in enumerate(layout.slot_kinds)}
    return PagedKV(k=k, v=v)


def init_occupancy(layout: PagedLayout, n_cycles: int, num_pages: dict[str, int] | int) -> dict[str, Any]:
    """The "kv-occupancy" side arrays: per slot, bool [n_cycles, num_pages, P]
    with 1 = live, mirroring the parent pools' page axes (same page ids, no
    head/feature dims).  Initialised ALL-LIVE so with the "kv" site inactive
    (or tau_kv == 0) every dense-parity invariant holds with zero changes —
    bits only turn dead when a policy marks them at scatter time."""
    if isinstance(num_pages, int):
        num_pages = {k: num_pages for k in layout.kinds}
    return {
        str(i): jnp.ones((n_cycles, num_pages[kind], layout.page_size), jnp.bool_)
        for i, kind in enumerate(layout.slot_kinds)
    }


def occupancy_bit(k_new: Array, tau) -> Array:
    """Scatter-time DynaTran "kv" site: a cached position is *live* iff any
    key element survives the threshold (max over (Hkv, D) of |k| >= tau) —
    the per-position analogue of ``dynatran_prune``'s any(keep) tile mask.

    Must be computed from the FULL key (before any TP head slicing) so every
    shard agrees on the replicated bit.  ``k_new`` is [..., Hkv, D]; the
    result drops the last two axes."""
    mag = jnp.max(jnp.abs(k_new.astype(jnp.float32)), axis=(-2, -1))
    return mag >= tau


# ---------------------------------------------------------------------------
# Raw pool ops (one array).  Pool shape [num_pages, P, *rest] — K/V pools
# carry rest = (Hkv, D), int8 scale pools carry rest = (Hkv,).
# ---------------------------------------------------------------------------


def gather_pages(pool: Array, page_table: Array) -> Array:
    """jnp gather: pool [num_pages, P, *rest] + table [B, maxp] ->
    contiguous per-row cache view [B, maxp * P, *rest].

    Rows gathered through trash/stale pages carry garbage values; attention
    masks them by length, and because masked scores are exactly NEG_INF in
    both the paged and the dense path, downstream logits stay bitwise equal
    to the dense reference.
    """
    b, maxp = page_table.shape
    p = pool.shape[1]
    return pool[page_table].reshape(b, maxp * p, *pool.shape[2:])


def gather_pages_ring(pool: Array, page_table: Array, cur_pos: Array, window: int) -> Array:
    """Ring gather in DENSE-RING layout: [B, window, *rest] where entry j
    holds the key at absolute position a_j = L - ((L - j) mod window) for
    L = ``cur_pos`` (the newest written position, per row).

    This is exactly the layout of the dense ring cache (T == window, writes
    at t % T), so paged ring decode reads the same values in the same order
    and stays bitwise-identical to the dense reference.  Entries with
    a_j < 0 (cache not yet full) read arbitrary finite pool bytes and are
    masked by the caller's effective length, as in the dense path.
    """
    b, nring = page_table.shape
    n_pages, p = pool.shape[:2]
    cap = nring * p  # logical ring capacity C
    j = jnp.arange(window)
    a = cur_pos[:, None] - ((cur_pos[:, None] - j[None, :]) % window)  # [B, W]
    off = a % cap  # jnp modulo is non-negative, so stale (a < 0) entries stay in range
    page = jnp.take_along_axis(page_table, off // p, axis=1)  # [B, W]
    flat = pool.reshape(n_pages * p, *pool.shape[2:])
    return flat[page * p + off % p]


def scatter_token(pool: Array, page_table: Array, length: Array, new: Array) -> Array:
    """Write one step's per-row vectors ``new`` [B, *rest] at each row's
    current position (page = table[row][length // P], offset = length % P).

    Rows whose position falls past their table (retired rows kept in a
    full-width decode batch) are routed to an explicit out-of-bounds page
    index and dropped — XLA's gather would otherwise clamp ``length // P``
    to the LAST table entry and corrupt a live page.
    """
    p = pool.shape[1]
    b, maxp = page_table.shape
    rows = jnp.arange(b)
    idx = length // p
    page = page_table[rows, jnp.minimum(idx, maxp - 1)]
    page = jnp.where(idx < maxp, page, pool.shape[0])  # OOB sink -> dropped
    return pool.at[page, length % p].set(new.astype(pool.dtype), mode="drop")


def scatter_token_ring(pool: Array, page_table: Array, length: Array, new: Array) -> Array:
    """Ring write: position ``length`` lands at ring offset length % C
    (C = table width * P), overwriting the slot that slid out of the
    window.  Never out of range, so no OOB routing is needed."""
    p = pool.shape[1]
    b, nring = page_table.shape
    off = length % (nring * p)
    page = page_table[jnp.arange(b), off // p]
    return pool.at[page, off % p].set(new.astype(pool.dtype), mode="drop")


def scatter_chunk(pool: Array, page_table: Array, start: Array, new: Array, valid: Array) -> Array:
    """Scatter prefill chunks ``new`` [B, C, *rest] for a BATCH of
    sequences at absolute positions start[b]..start[b]+C-1.  ``valid``
    [B, C] masks padding tokens and inactive rows: their writes are routed
    out of bounds and dropped.  Rows write disjoint pages (each row has its
    own table), so the batched scatter never conflicts."""
    p = pool.shape[1]
    maxp = page_table.shape[1]
    pos = start[:, None] + jnp.arange(new.shape[1])[None, :]  # [B, C]
    idx = pos // p
    page = jnp.take_along_axis(page_table, jnp.minimum(idx, maxp - 1), axis=1)
    page = jnp.where(valid & (idx < maxp), page, pool.shape[0])  # OOB -> dropped
    return pool.at[page, pos % p].set(new.astype(pool.dtype), mode="drop")


def scatter_chunk_ring(pool: Array, page_table: Array, start: Array, new: Array, valid: Array) -> Array:
    """Batched ring chunk scatter: position t lands at ring offset t % C."""
    p = pool.shape[1]
    nring = page_table.shape[1]
    pos = start[:, None] + jnp.arange(new.shape[1])[None, :]  # [B, C]
    off = pos % (nring * p)
    page = jnp.take_along_axis(page_table, off // p, axis=1)
    page = jnp.where(valid, page, pool.shape[0])  # padding -> dropped
    return pool.at[page, off % p].set(new.astype(pool.dtype), mode="drop")


def copy_pool_pages(pool: Array, src: Array, dst: Array) -> Array:
    """Copy whole pages ``src[i] -> dst[i]`` within one pool
    [n_cycles, num_pages, P, *rest] — the copy-on-write fork: a sequence
    about to write a page whose refcount is > 1 gets a private duplicate
    first, so the write can never mutate a page visible to another sequence
    (or to the prefix cache).  Padding pairs (0, 0) copy the trash page onto
    itself, harmlessly, which lets callers bucket ``src``/``dst`` lengths to
    bound retracing."""
    return pool.at[:, dst].set(pool[:, src])


def entry_copy_pages(entry, src: Array, dst: Array):
    if isinstance(entry, dict):
        return {"q": copy_pool_pages(entry["q"], src, dst),
                "scale": copy_pool_pages(entry["scale"], src, dst)}
    return copy_pool_pages(entry, src, dst)


def entry_extract_pages(entry, pages: Array):
    """Gather whole pages ``entry[:, pages]`` out of one pool entry — the
    device half of a SPILL: the engine fetches the result to host with one
    ``device_get``.  Works per shard under TP (each shard extracts its own
    KV-head slice; the host payload keeps the shard axis)."""
    if isinstance(entry, dict):
        return {"q": entry["q"][:, pages], "scale": entry["scale"][:, pages]}
    return entry[:, pages]


def entry_insert_pages(entry, dst: Array, payload):
    """Scatter spilled ``payload`` (the matching ``entry_extract_pages``
    result) onto pages ``dst[i]`` of one pool entry — the device half of a
    RESTORE.  Padding pairs may target ``TRASH_PAGE`` with a zero payload
    (the trash page's content is garbage by contract), which lets callers
    pad ``dst`` to bucketed lengths and bound retracing."""
    if isinstance(entry, dict):
        return {"q": entry["q"].at[:, dst].set(payload["q"]),
                "scale": entry["scale"].at[:, dst].set(payload["scale"])}
    return entry.at[:, dst].set(payload)


# ---------------------------------------------------------------------------
# Entry ops: dispatch over bf16 pools (a bare array) vs int8 pools
# ({"q", "scale"}).  Quant/dequant mirror the dense cache's `_quant_update`
# and `_dequant` op-for-op, which is what keeps paged int8 decode
# bitwise-identical to the dense int8 reference.
# ---------------------------------------------------------------------------


def quantize_kv(new: Array) -> tuple[Array, Array]:
    """Per-(row, head) absmax int8 quantisation of ``new`` [..., Hkv, D] ->
    (q int8 [..., Hkv, D], scale bf16 [..., Hkv])."""
    scale = jnp.max(jnp.abs(new.astype(jnp.float32)), axis=-1) / 127.0
    q = jnp.round(new.astype(jnp.float32) / jnp.maximum(scale, 1e-8)[..., None]).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def dequantize_kv(q: Array, scale: Array) -> Array:
    return q.astype(jnp.bfloat16) * scale[..., None]


def entry_scatter_token(entry, page_table: Array, length: Array, new: Array, *, ring: bool):
    op = scatter_token_ring if ring else scatter_token
    if isinstance(entry, dict):
        q, scale = quantize_kv(new)
        return {"q": op(entry["q"], page_table, length, q),
                "scale": op(entry["scale"], page_table, length, scale)}
    return op(entry, page_table, length, new)


def entry_scatter_chunk(entry, page_table: Array, start: Array, new: Array, valid: Array, *, ring: bool):
    op = scatter_chunk_ring if ring else scatter_chunk
    if isinstance(entry, dict):
        q, scale = quantize_kv(new)
        return {"q": op(entry["q"], page_table, start, q, valid),
                "scale": op(entry["scale"], page_table, start, scale, valid)}
    return op(entry, page_table, start, new, valid)


def entry_gather(entry, page_table: Array) -> Array:
    """Contiguous cache view with dequantisation fused into the gather."""
    if isinstance(entry, dict):
        return dequantize_kv(gather_pages(entry["q"], page_table), gather_pages(entry["scale"], page_table))
    return gather_pages(entry, page_table)


def entry_gather_ring(entry, page_table: Array, cur_pos: Array, window: int) -> Array:
    if isinstance(entry, dict):
        return dequantize_kv(
            gather_pages_ring(entry["q"], page_table, cur_pos, window),
            gather_pages_ring(entry["scale"], page_table, cur_pos, window),
        )
    return gather_pages_ring(entry, page_table, cur_pos, window)


def paged_cache_bytes(layers: int, num_pages: int, page_size: int, n_kv: int, head_dim: int, elem_bytes: int = 2) -> int:
    return 2 * layers * num_pages * page_size * n_kv * head_dim * elem_bytes


# ---------------------------------------------------------------------------
# Page-tier ops registry: per state kind, the jittable extract/insert pair
# that moves whole pages between device pools and the host tier.  Registering
# ops is what makes a kind ``spillable`` — slot-dense kinds never register
# (no page granularity to move) and fall back to replay.  All three paged
# kinds share the entry-op pair above: int8-vs-bf16 layout differences are
# absorbed by the dict dispatch inside the entry ops, and ring pages spill
# exactly like full pages (the write CURSOR travels in the scheduler's
# request snapshot, not in the pool).
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PageTierOps:
    """The spill/restore op pair for one paged state kind."""

    extract: Any  # (entry, pages) -> payload        (device -> host via device_get)
    insert: Any  # (entry, dst, payload) -> entry    (host -> device via device_put)


PAGE_TIER_OPS: dict[str, PageTierOps] = {}


def register_tier_ops(kind: str, ops: PageTierOps) -> PageTierOps:
    """Register spill/restore ops for ``kind`` (must be a registered paged
    state kind) — the extension point a new paged kind implements to join
    the host tier."""
    sk = STATE_KINDS.get(kind)
    if sk is None or not sk.paged:
        raise ValueError(f"tier ops need a registered PAGED state kind, got {kind!r}")
    PAGE_TIER_OPS[kind] = ops
    return ops


def tier_ops(kind: str) -> PageTierOps:
    return PAGE_TIER_OPS[kind]


for _kind in ("paged-full", "paged-int8", "paged-ring"):
    register_tier_ops(_kind, PageTierOps(extract=entry_extract_pages, insert=entry_insert_pages))
