"""The one traffic generator: a mix file of parameters in, a cycle of rounds out.

A mix (``traffic/<name>.json``) is data only.  Its sizes -- document
lengths, which document each request reads, the length of each request's
own part and of its output -- are drawn once from the mix's own
``size_seed``, so every ``--seed`` runs the same set of sizes and so the
same compiled shapes.  ``--seed`` chooses the order of the rounds (the
cycle is rotated) and every token: the documents' content, kept for the
whole run, and each request's own tokens, drawn afresh on every replay.

Token ids are drawn from [1, vocab): id 0 never appears in a prompt, so a
zero in a padded launch operand marks padding.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List, Optional, Tuple

import numpy as np


@dataclass(frozen=True)
class RequestSpec:
    doc: Optional[int]  # index of the shared document it starts with, if any
    own_tokens: int  # tokens of its own after the document
    output_tokens: int


@dataclass
class Cycle:
    mix: dict
    seed: int
    vocab: int
    doc_lengths: List[int]
    docs: List[Tuple[int, ...]]
    rounds: List[List[RequestSpec]]  # in the order this seed serves them

    @property
    def block(self) -> int:
        return int(self.mix["block"])

    @property
    def clients(self) -> int:
        return int(self.mix["clients"])

    def request_tokens(self, replay: int, round_idx: int, client: int) -> Tuple[int, ...]:
        """Prompt of one request: its document (if any) and tokens of its
        own, fresh for every replay of the cycle."""
        spec = self.rounds[round_idx][client]
        rng = np.random.default_rng([self.seed, replay, round_idx, client, 1])
        own = tuple(int(t) for t in rng.integers(1, self.vocab, spec.own_tokens))
        head = self.docs[spec.doc] if spec.doc is not None else ()
        return head + own

    def max_sequence(self) -> int:
        """Longest prompt plus output any request of the mix can have."""
        return max(
            (self.doc_lengths[r.doc] if r.doc is not None else 0) + r.own_tokens + r.output_tokens
            for rnd in self.rounds
            for r in rnd
        )

    def doc_pages(self) -> int:
        return sum(n // self.block for n in self.doc_lengths)

    def pool_pages(self) -> int:
        """Pages the engine gets: every document resident at once, each
        client's own prompt and output folded into pages, and the mix's
        stated headroom.  Sized to the working set, no larger: the pool is
        a host array that the program uploads whole after each page write."""
        own = max(r.own_tokens + r.output_tokens for rnd in self.rounds for r in rnd)
        per_client = math.ceil(own / self.block) + 1  # +1: a trailing partial page
        return self.doc_pages() + self.clients * per_client + int(self.mix["pool"]["headroom_pages"])

    def host_pages(self) -> Optional[int]:
        """Host-tier pages: every claimed document at once; unbounded (never
        used) where the mix holds no claims."""
        if (self.mix.get("documents") or {}).get("claim") is None:
            return None
        return self.doc_pages() + int(self.mix["pool"]["headroom_pages"])


def _draw(rng: np.random.Generator, dist: dict, n: int) -> List[int]:
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "uniform":
        xs = rng.integers(lo, hi + 1, n)
    elif dist["dist"] == "lognormal":
        xs = np.exp(rng.normal(math.log(float(dist["median"])), float(dist["sigma"]), n))
        xs = np.clip(np.rint(xs), lo, hi)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return [int(x) for x in xs]


def _choose_docs(rng: np.random.Generator, docs: dict, n_rounds: int, clients: int) -> List[List[int]]:
    count = int(docs["count"])
    if docs["choice"] == "zipf":
        w = 1.0 / np.arange(1, count + 1) ** float(docs["zipf_s"])
        return [list(rng.choice(count, clients, p=w / w.sum())) for _ in range(n_rounds)]
    raise ValueError(f"unknown document choice {docs['choice']!r}")


def build_cycle(mix: dict, seed: int, vocab: int) -> Cycle:
    size_rng = np.random.default_rng(int(mix["size_seed"]))
    block = int(mix["block"])
    clients, n_rounds = int(mix["clients"]), int(mix["rounds_per_cycle"])
    docs_cfg = mix.get("documents")
    doc_lengths: List[int] = []
    choice: List[List[Optional[int]]] = [[None] * clients for _ in range(n_rounds)]
    if docs_cfg:
        lo, hi = int(docs_cfg["tokens_min"]) // block, int(docs_cfg["tokens_max"]) // block
        doc_lengths = [int(b) * block for b in size_rng.integers(lo, hi + 1, int(docs_cfg["count"]))]
        choice = [[int(d) for d in rnd] for rnd in _choose_docs(size_rng, docs_cfg, n_rounds, clients)]
    rounds = []
    for r in range(n_rounds):
        own = _draw(size_rng, mix["own_tokens"], clients)
        out = _draw(size_rng, mix["output_tokens"], clients)
        rounds.append([RequestSpec(choice[r][c], own[c], out[c]) for c in range(clients)])
    rng = np.random.default_rng([seed, 0])
    docs = [tuple(int(t) for t in rng.integers(1, vocab, n)) for n in doc_lengths]
    start = seed % n_rounds
    rounds = rounds[start:] + rounds[:start]
    return Cycle(mix, seed, vocab, doc_lengths, docs, rounds)
