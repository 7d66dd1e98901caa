"""The plain reference the benchmark's ``correct`` is decided against.

A store's semantics, written out with nothing of the program under test:
every get returns the row of the latest put of its key that was issued
before it.  A row encodes its key and the version of the write that
produced it, in every one of its lanes, so a lost, stale or altered row
reads differently from the reference's.

Lane 0 holds the key, lane 1 the version, and lane ``j >= 2`` a 16-bit
mix of the two, times 256, plus ``j``; every lane is an integer below
2^24, which a float32 holds exactly, so a row survives any exact copy
bit for bit.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

_KEY_MUL = np.uint32(0x9E3779B1)
_VER_MUL = np.uint32(0x85EBCA77)


def rows(keys: np.ndarray, versions, width: int) -> np.ndarray:
    """float32[len(keys), width]: the row that a write of ``keys`` at
    ``versions`` (one per key, or one for all) stores."""
    k = np.asarray(keys, np.uint32)
    v = np.broadcast_to(np.asarray(versions, np.uint32), k.shape)
    mix = ((k * _KEY_MUL) ^ (v * _VER_MUL)) >> np.uint32(16)
    out = (mix * np.uint32(256)).astype(np.float32)[:, None] \
        + np.arange(width, dtype=np.float32)[None, :]
    out[:, 0] = k
    if width > 1:
        out[:, 1] = v
    return out


class Reference:
    """The latest written version of every key (-1: never written)."""

    def __init__(self, key_space: int):
        self.version = np.full(key_space, -1, np.int64)

    def put(self, keys: np.ndarray, version: int) -> None:
        self.version[keys] = version

    def expected(self, keys: np.ndarray) -> np.ndarray:
        return self.version[keys].copy()


@dataclass
class Check:
    """What the window's get answers and the read-back are compared on.

    ``stale_or_lost``: get answers (every lane of every get batch) that
    were not found, or whose key or version differs from the reference.
    ``bad_rows``: rows of a seeded sample of each get batch, and every
    read-back row, with any lane unlike the reference's row.
    ``dropped``: keys that a store which routes its batches could not
    place, over set-up, window and read-back (None: the store does not
    route, and the number is not compared)."""
    answers: int = 0
    stale_or_lost: int = 0
    rows_compared: int = 0
    bad_rows: int = 0
    dropped: int | None = None
    examples: list = field(default_factory=list)

    def compare(self, keys, want_version, found, head, sample_idx,
                sample_rows, width: int) -> None:
        """``head`` is the answers' lanes 0 and 1; ``sample_rows`` the full
        rows at ``sample_idx``."""
        keys = np.asarray(keys)
        want_version = np.asarray(want_version)
        ok = (np.asarray(found, bool) & (want_version >= 0)
              & (head[:, 0] == keys.astype(np.float32))
              & (head[:, 1] == want_version.astype(np.float32)))
        self.answers += len(keys)
        bad = np.flatnonzero(~ok)
        self.stale_or_lost += len(bad)
        for i in bad[:max(3 - len(self.examples), 0)]:
            self.examples.append(
                f"key {int(keys[i])}: found {bool(found[i])}, lanes "
                f"{head[i].tolist()}, want version {int(want_version[i])}")
        want = rows(keys[sample_idx], want_version[sample_idx], width)
        self.rows_compared += len(sample_idx)
        self.bad_rows += int((sample_rows != want).any(axis=1).sum())

    def numbers(self) -> dict:
        """Each number compared, with its limit: an exact comparison."""
        out = {"stale_or_lost": {"value": self.stale_or_lost, "limit": 0},
               "bad_rows": {"value": self.bad_rows, "limit": 0}}
        if self.dropped is not None:
            out["dropped"] = {"value": self.dropped, "limit": 0}
        return out

    @property
    def failed(self) -> int:
        """Answers and rows that came out wrong, and keys dropped."""
        return self.stale_or_lost + self.bad_rows + (self.dropped or 0)

    @property
    def correct(self) -> bool:
        return (self.answers > 0 and self.rows_compared > 0
                and self.failed == 0)
