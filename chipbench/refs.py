"""Plain references the timed path is compared with.

They import nothing of the program under test and take nothing it made:
they work from the seed's data, the traffic tape and numpy alone.
"""
from __future__ import annotations

import numpy as np


def bad_words(got: np.ndarray, want: np.ndarray) -> int:
    """Words of ``got`` whose bits differ from ``want``'s."""
    got, want = np.ascontiguousarray(got), np.ascontiguousarray(want)
    if got.shape != want.shape:
        return max(got.size, want.size)
    return int(np.count_nonzero(got.view(np.uint32) != want.view(np.uint32)))


def copy_replay(src: np.ndarray, dst: np.ndarray, src_slots: np.ndarray,
                dst_slots: np.ndarray, slot_words: int) -> np.ndarray:
    """The destination row after copying, in order, slot ``src_slots[i]``
    of ``src`` onto slot ``dst_slots[i]`` of ``dst`` (``src`` is never
    written). The last copy onto a slot decides its content."""
    src_slots = np.asarray(src_slots).ravel()
    dst_slots = np.asarray(dst_slots).ravel()
    out = dst.copy().reshape(-1, slot_words)
    if dst_slots.size:
        _, first_rev = np.unique(dst_slots[::-1], return_index=True)
        last = dst_slots.size - 1 - first_rev
        out[dst_slots[last]] = src.reshape(-1, slot_words)[src_slots[last]]
    return out.reshape(-1)


def sum_error(shards: np.ndarray, got) -> float:
    """Largest error of any rank's all-reduced copy against the float64 sum
    of ``shards`` (n_peers, words), per element over the sum of the
    magnitudes of its terms: the error of a float32 sum of n terms in any
    order stays under about n float32 ulps of that scale."""
    shards = np.asarray(shards)
    want = shards.sum(axis=0, dtype=np.float64)
    scale = np.abs(shards).sum(axis=0, dtype=np.float64)
    scale[scale == 0] = 1.0
    worst = 0.0
    for copy in got:
        copy = np.asarray(copy, np.float64)
        if copy.shape != want.shape:
            return float("inf")
        err = np.abs(copy - want) / scale
        worst = max(worst, float(np.nan_to_num(err, nan=np.inf).max()))
    return worst


def sum_bf16(shards: np.ndarray) -> np.ndarray:
    """The sum of ``shards`` (n_peers, words) with every term and every
    partial sum rounded to bfloat16: the reference one precision below the
    configuration's float32, the control of the all-reduce cell."""
    import ml_dtypes
    bf16 = ml_dtypes.bfloat16
    acc = np.asarray(shards[0]).astype(bf16)
    for s in shards[1:]:
        acc = (acc.astype(np.float32)
               + np.asarray(s).astype(bf16).astype(np.float32)).astype(bf16)
    return acc.astype(np.float32)
