"""The one traffic generator.  A mix is a data file under ``traffic/``; this
module turns it and a seed into the requests the program is sent.

Lengths come from a deck: the quantiles ``(i + 0.5) / deck`` of the mix's
distribution, clipped to its range (``deck`` a power of two).  The request
at stream position ``n`` takes the prompt size whose level is the bit
reversal of ``(n + a) * m mod deck``, with offset ``a`` and odd multiplier
``m`` drawn from the seed.  So every deck of requests holds each prompt size
once, any ``2**j`` consecutive requests hold one from each of ``2**j`` equal
strata of the distribution, and every seed sends the same sizes in another
order: the prompts a measured window happens to hold are a fair sample at
any seed.  Answer sizes and the greedy half take a seeded shuffle of each
deck, so they pair with prompt sizes at random.  Token ids and sampling
seeds come from the seed too; the same seed gives the same requests.

One closed-loop client per engine slot; the first answer of client ``j``
keeps the share ``(j + 0.5) / clients`` of its size, as if it had been
running when the clients started, so their first requests end spread out
instead of together.

Mix keys::

    stagger_ticks      engine ticks between two clients' first requests
    lead_in_ticks      further ticks before the measured window opens
    deck               sizes per deck, a power of two
    prompt / output    {"dist": "lognormal", "median", "sigma", "min", "max"}
                       or {"dist": "uniform", "min", "max"}, optionally
                       "round_to" a multiple; with documents,
                       "prompt" is the question that follows the document
    greedy_share       share of each block decoded greedily
    sampled            {"temperature", "top_p"} of the other requests
    documents          optional {"count", "dist"...}: shared documents,
                       cached during set-up; each block of ``count``
                       requests asks about every document once
    fill_pool          optional: fill the page pool during set-up with
                       prompts no request shares, so the window starts
                       with the prefix cache full, as on a server that has
                       run for a while
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class Req:
    """One request as sent: prompt ids, token budget and decode policy."""

    prompt: tuple[int, ...]
    max_new: int
    greedy: bool
    temperature: float = 0.0
    top_p: float = 1.0
    seed: int = 0
    document: int = -1  # index into Traffic.documents, -1 for none


def deck(dist: dict, k: int) -> list[int]:
    """The ``k`` quantiles of ``dist``, clipped to [min, max]."""
    lo, hi = int(dist["min"]), int(dist["max"])
    out = []
    for i in range(k):
        u = (i + 0.5) / k
        if dist["dist"] == "lognormal":
            x = dist["median"] * math.exp(dist["sigma"] * NormalDist().inv_cdf(u))
        elif dist["dist"] == "uniform":
            x = lo + (hi - lo) * u
        else:
            raise ValueError(f"unknown length distribution {dist['dist']!r}")
        step = int(dist.get("round_to", 1))
        out.append(min(hi, max(lo, step * int(round(x / step)))))
    return out


class Traffic:
    """The request stream of one mix at one seed, and its closed-loop shape."""

    def __init__(self, mix: dict, vocab: int, slots: int, seed: int):
        self.mix = mix
        self.vocab = vocab
        self.rng = np.random.default_rng(int(seed))
        self.clients = int(slots)
        self.stagger_ticks = int(mix["stagger_ticks"])
        self.lead_in_ticks = int(mix["lead_in_ticks"])
        self.k = int(mix["deck"])
        self.bits = self.k.bit_length() - 1
        if self.k != 1 << self.bits:
            raise ValueError(f"deck {self.k} is not a power of two")
        # offset and odd multiplier of the prompt order
        self.order = (int(self.rng.integers(0, self.k)), 2 * int(self.rng.integers(0, self.k // 2 or 1)) + 1)
        self.n = 0  # stream position
        self.prompts = deck(mix["prompt"], self.k)
        self.outputs = deck(mix["output"], self.k)
        n_greedy = int(round(mix["greedy_share"] * self.k))
        self.greedy = [True] * n_greedy + [False] * (self.k - n_greedy)
        docs = mix.get("documents")
        self.documents: list[tuple[int, ...]] = []
        if docs:
            lengths = self.rng.permutation(deck(docs, int(docs["count"])))
            self.documents = [self._tokens(int(n)) for n in lengths]
        self._block: list[Req] = []
        self._doc_order: list[int] = []

    def _tokens(self, n: int) -> tuple[int, ...]:
        return tuple(int(t) for t in self.rng.integers(1, self.vocab, size=n))

    def _next_doc(self) -> int:
        if not self._doc_order:
            self._doc_order = list(self.rng.permutation(len(self.documents)))
        return int(self._doc_order.pop())

    def level(self, n: int) -> int:
        """Deck level of the prompt at stream position ``n``."""
        a, m = self.order
        x = (n + a) * m % self.k
        return int(format(x, f"0{self.bits}b")[::-1], 2) if self.bits else 0

    def _new_block(self) -> list[Req]:
        o_order = self.rng.permutation(self.k)
        g_order = self.rng.permutation(self.k)
        sampled = self.mix["sampled"]
        block = []
        for i in range(self.k):
            n, self.n = self.n, self.n + 1
            greedy = self.greedy[g_order[i]]
            doc = self._next_doc() if self.documents else -1
            prompt = self._tokens(self.prompts[self.level(n)])
            if doc >= 0:
                prompt = self.documents[doc] + prompt
            block.append(Req(
                prompt=prompt,
                max_new=self.outputs[o_order[i]],
                greedy=greedy,
                temperature=0.0 if greedy else float(sampled["temperature"]),
                top_p=1.0 if greedy else float(sampled["top_p"]),
                seed=int(self.rng.integers(0, 2**31 - 1)),
                document=doc,
            ))
        return block[::-1]

    def next(self, first_of: int | None = None) -> Req:
        """The next request of the stream (endless); ``first_of`` names the
        client whose first request this is, which gets its cut answer."""
        if not self._block:
            self._block = self._new_block()
        req = self._block.pop()
        if first_of is not None:
            req = dataclasses.replace(req, max_new=max(1, math.ceil(req.max_new * (first_of + 0.5) / self.clients)))
        return req

    def fillers(self, slots: int, num_pages: int, page_size: int, max_len: int) -> list[tuple[int, ...]]:
        """One unshared prompt per slot that together fill the pool (each
        admission also reserves the page of its first output token)."""
        if not self.mix.get("fill_pool"):
            return []
        pages = min((num_pages - 1) // slots - 1, (max_len - 1) // page_size)
        return [self._tokens(pages * page_size) for _ in range(slots)]

    def max_tokens(self) -> int:
        """Longest request the mix can send: prompt (with document) plus output."""
        doc = max((len(d) for d in self.documents), default=0)
        return doc + max(self.prompts) + max(self.outputs)

    def check_fits(self, max_len: int, num_pages: int, page_size: int) -> None:
        """Every request fits ``max_len``, and the documents plus every
        client's private pages fit the pool, so no request waits for pages
        that the cached documents hold."""
        if self.max_tokens() > max_len:
            raise ValueError(f"mix sends {self.max_tokens()} tokens, over max_len {max_len}")
        if not self.documents:
            return
        doc_pages = sum(len(d) // page_size for d in self.documents)
        # a request's own pages: the document's partial last page, the
        # question and the answer
        own = -(-(max(self.prompts) + max(self.outputs)) // page_size) + 1
        if doc_pages + self.clients * own > num_pages - 1:
            raise ValueError(f"documents ({doc_pages} pages) and {self.clients} clients' own pages "
                             f"({self.clients * own}) exceed the pool of {num_pages - 1}")
