"""Client batches of every federated round, generated from a seed.

A traffic mix is a JSON file ``bench/traffic/<name>.json``. Its
``federation`` block sets the shape of the traffic, which this one
generator reads:

    population          clients that exist (ids 0 .. population-1)
    clients_per_round   clients drawn per round, without replacement
                        (all of them when equal to the population)
    batch, seq          rows and tokens per row of each client's batch
    domains, dirichlet  each client mixes ``domains`` text domains with
                        weights ~ Dirichlet(dirichlet), fixed per client
    zipf                exponent of each domain's unigram law (rank^-zipf
                        over a domain-specific permutation of the vocab)
    follow              share of tokens that follow a bigram rule of
                        their domain from the previous token

Every seed gives the same sizes and the same amount of work; only which
clients and which tokens change. Round t's batch depends on (seed, t)
alone, so any round can be drawn again without the ones before it.
"""

from __future__ import annotations

import numpy as np


class Traffic:
    def __init__(self, federation: dict, vocab: int, seed: int):
        f = federation
        self.population = int(f["population"])
        self.n = int(f["clients_per_round"])
        self.batch, self.seq = int(f["batch"]), int(f["seq"])
        self.domains = int(f["domains"])
        self.dirichlet = float(f["dirichlet"])
        self.follow = float(f.get("follow", 0.5))
        if not 0 < self.n <= self.population:
            raise ValueError(f"clients_per_round must be in [1, population], "
                             f"got {self.n} of {self.population}")
        self.vocab, self.seed = int(vocab), int(seed)
        rng = np.random.default_rng([self.seed, 0])
        self.perm = np.stack([rng.permutation(self.vocab)
                              for _ in range(self.domains)]).astype(np.int32)
        p = np.arange(1, self.vocab + 1, dtype=np.float64) ** -float(f["zipf"])
        self.cdf = np.cumsum(p / p.sum())

    def clients(self, t: int) -> np.ndarray:
        """Ids of the clients taking part in round t."""
        if self.n == self.population:
            return np.arange(self.n)
        rng = np.random.default_rng([self.seed, 1, t])
        return np.sort(rng.choice(self.population, self.n, replace=False))

    def mixture(self, client: int) -> np.ndarray:
        """A client's fixed weights over the domains."""
        rng = np.random.default_rng([self.seed, 2, int(client)])
        return rng.dirichlet(np.full(self.domains, self.dirichlet))

    def batch_of(self, t: int) -> np.ndarray:
        """Round t's tokens, (clients_per_round, batch, seq) int32."""
        rng = np.random.default_rng([self.seed, 3, t])
        shape = (self.n, self.batch, self.seq)
        mix = np.stack([self.mixture(c) for c in self.clients(t)])
        u = rng.random(shape[:2])
        dom = (u[..., None] > np.cumsum(mix, -1)[:, None, :]).sum(-1)
        dom = np.minimum(dom, self.domains - 1)
        ranks = np.minimum(np.searchsorted(self.cdf, rng.random(shape)),
                           self.vocab - 1)
        toks = self.perm[dom[..., None], ranks]
        nxt = (toks[..., :-1].astype(np.int64) * 7 + 3 + dom[..., None]) \
            % self.vocab
        follow = rng.random(nxt.shape) < self.follow
        toks[..., 1:] = np.where(follow, nxt, toks[..., 1:])
        return toks.astype(np.int32)
