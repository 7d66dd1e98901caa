"""Seeded client traffic for one cell: the load, the window's batches and
the read-back sample, from a traffic mix's data file.

The key arithmetic is a copy of the repository's generator
(``repro.workloads.sampler`` / ``reference``): a bounded inverse-CDF
zipfian over ranks in float32, then a multiplicative scramble of ranks
into keys with uint32 wraparound.  The load inserts every key once in
the scramble's order of ``0 .. N-1`` (YCSB ``insertorder=hashed``, by a
multiplicative permutation rather than YCSB's FNV hash).

Batches are homogeneous in kind.  Kinds come in blocks of ``kind_block``
batches holding exactly ``share * kind_block`` batches of each kind, in
a seeded order.  Writes draw their keys from one stream that is the same
for every seed: the keys written decide the store's compaction work, so
every seed gives the same work in another order, with its own read keys
and its own versions in every value.
"""
from __future__ import annotations

import numpy as np

SCRAMBLE_MUL = 2654435761       # Knuth's multiplicative constant
KINDS = ("get", "put")
WRITE_SEED = 0                  # the write keys' stream, the same for all


def seed_sequence(seed: int) -> np.random.SeedSequence:
    """Any whole number, negative or past 64 bits included."""
    return np.random.SeedSequence(int(seed) % 2**64)


def zipf_ranks(u: np.ndarray, n: int, theta: float) -> np.ndarray:
    """Ranks in ``[0, n)`` from uniforms: P(r) = ((r+2)^(1-t) - (r+1)^(1-t))
    / (n^(1-t) - 1), in float32, theta kept off the singularity at 1."""
    t = max(theta, 1e-3)
    if abs(t - 1.0) < 1e-4:
        t += 2e-4
    c = np.float32(n) ** np.float32(1 - t)
    ranks = ((c - 1) * np.asarray(u, np.float32) + 1) \
        ** np.float32(1 / (1 - t)) - 1
    return np.clip(ranks, 0, n - 1).astype(np.int32)


def scramble(ranks, offset: int, n: int) -> np.ndarray:
    """Rank to key: ``(rank + offset) * SCRAMBLE_MUL mod 2^32 mod n``."""
    x = (np.asarray(ranks).astype(np.int64) + offset).astype(np.uint32)
    x = (x * np.uint32(SCRAMBLE_MUL)).astype(np.uint32)
    return (x % np.uint32(n)).astype(np.int32)


class Traffic:
    """The op stream of one run, drawn from ``--seed``.

    ``mix`` is the traffic file's object: ``shares`` ({kind: share}),
    ``keys`` ({kind: {"dist": "zipf"|"uniform", "theta": t}}), ``batch``,
    ``inflight``, ``kind_block`` and ``readback_batches``; the harness
    also reads ``warmup_batches``, the batches run before the window."""

    def __init__(self, mix: dict, key_space: int, seed: int):
        self.mix = mix
        self.n = key_space
        self.batch = int(mix["batch"])
        # one stream each, so that the read-back sample and the versions
        # do not depend on how many batches a window ran
        kinds, keys, readback, version = (
            np.random.default_rng(s) for s in seed_sequence(seed).spawn(4))
        self.kind_rng, self.readback_rng = kinds, readback
        self.key_rng = {"get": keys, "put": np.random.default_rng(
            seed_sequence(WRITE_SEED))}
        self.block = self._kind_block(mix)
        self._kinds: list[str] = []
        # a per-write version base; versions stay below 2^24 so that a
        # float32 lane holds them exactly
        self.version_base = int(version.integers(0, 1 << 23))

    @staticmethod
    def _kind_block(mix: dict) -> list[str]:
        block = int(mix["kind_block"])
        out = []
        for kind in KINDS:
            count = float(mix["shares"].get(kind, 0)) * block
            if abs(count - round(count)) > 1e-9:
                raise ValueError(f"share of {kind} times kind_block "
                                 f"{block} is not a whole number")
            out += [kind] * round(count)
        unknown = set(mix["shares"]) - set(KINDS)
        if unknown or len(out) != block:
            raise ValueError(f"shares {mix['shares']} do not fill a block "
                             f"of {block} with kinds {KINDS}")
        return out

    def version(self, t: int) -> int:
        """The version every write of global batch ``t`` carries."""
        return (self.version_base + t) % (1 << 24)

    def load_batches(self):
        """Every key once, in scrambled order, in batches."""
        keys = scramble(np.arange(self.n), 0, self.n)
        for i in range(0, self.n, self.batch):
            yield keys[i:i + self.batch]

    def keys(self, kind: str) -> np.ndarray:
        spec, rng = self.mix["keys"][kind], self.key_rng[kind]
        if spec["dist"] == "uniform":
            return rng.integers(0, self.n, self.batch, dtype=np.int32)
        if spec["dist"] == "zipf":
            u = rng.random(self.batch, dtype=np.float32)
            return scramble(zipf_ranks(u, self.n, float(spec["theta"])),
                            0, self.n)
        raise ValueError(f"unknown key distribution {spec['dist']!r}")

    def next_batch(self) -> tuple[str, np.ndarray]:
        if not self._kinds:
            self._kinds = [str(k) for k in
                           self.kind_rng.permutation(self.block)]
        kind = self._kinds.pop()
        return kind, self.keys(kind)

    def readback_keys(self) -> np.ndarray:
        """A seeded uniform sample of distinct keys, read after the window
        closes: cold records, moved between tiers by compaction, and the
        load's writes."""
        n = min(int(self.mix["readback_batches"]) * self.batch, self.n)
        n -= n % self.batch
        return self.readback_rng.choice(self.n, n, replace=False) \
            .astype(np.int32)
