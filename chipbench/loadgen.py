"""Traffic generators, one per loop kind, driven by a traffic file and the seed.

The same seed gives the same tape. Every seed gives the same amount of work:
sizes and counts are fixed by the traffic file, and the seed only chooses
addresses, data and which results are checked. So runs with different seeds
spread no wider than runs of one seed.
"""
from __future__ import annotations

import numpy as np

# independent random streams of one seed
_VERBS, _SOURCE, _SAMPLE, _SHARDS = range(4)


def rng(seed: int, stream: int) -> np.random.Generator:
    """The generator of one stream of ``seed`` (any integer, however large)."""
    return np.random.default_rng([int(seed) % (1 << 64), stream])


# ---------------------------------------------------------------------------
# verbs: closed-loop doorbell batches
# ---------------------------------------------------------------------------

def verbs_tape(seed: int, pool_words: int, message_words: int, batch: int,
               n_batches: int) -> tuple[np.ndarray, np.ndarray]:
    """(n_batches, batch) slot numbers of the remote and the local side of
    each WQE; a slot is ``message_words`` words, so offsets are aligned to
    the message size. Within a batch no slot repeats on either side, and no
    entry continues the one before it on the remote side, so no two WQEs of
    a batch can be coalesced into one transfer."""
    slots = pool_words // message_words
    if slots < 2 * batch:
        raise ValueError(f"{slots} slots of {message_words} words cannot "
                         f"hold a batch of {batch} distinct, apart slots")
    g = rng(seed, _VERBS)

    def draw(n):
        if slots <= 4096:
            return np.argsort(g.random((n, slots)), axis=1)[:, :batch]
        return g.integers(0, slots, (n, batch))

    def bad(a, adjacent):
        s = np.sort(a, axis=1)
        dup = (np.diff(s, axis=1) == 0).any(axis=1)
        if adjacent:
            dup |= (np.diff(a, axis=1) == 1).any(axis=1)
        return dup

    remote, local = draw(n_batches), draw(n_batches)
    for arr, adjacent in ((remote, True), (local, False)):
        redo = bad(arr, adjacent)
        while redo.any():
            arr[redo] = draw(int(redo.sum()))
            redo = bad(arr, adjacent)
    return remote.astype(np.int64), local.astype(np.int64)


def source_words(seed: int, n: int) -> np.ndarray:
    """The source row of a verbs cell: ``n`` f32 words in [1, 2)."""
    return rng(seed, _SOURCE).random(n, dtype=np.float32) + np.float32(1)


def sample(seed: int, n: int, k: int) -> set:
    """``k`` of ``range(n)`` drawn from the seed."""
    picked = rng(seed, _SAMPLE).choice(n, size=min(k, n), replace=False)
    return {int(i) for i in picked}


# ---------------------------------------------------------------------------
# gradient all-reduce: closed loop
# ---------------------------------------------------------------------------

def gradient_buckets(seed: int, n_buckets: int, n_peers: int,
                     words: int) -> np.ndarray:
    """(n_buckets, n_peers, words) standard normal f32 gradient shards."""
    return rng(seed, _SHARDS).standard_normal(
        (n_buckets, n_peers, words), dtype=np.float32)
