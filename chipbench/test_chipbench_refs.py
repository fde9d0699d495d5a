import numpy as np

from chipbench import loadgen, refs


def test_copy_replay_equals_copying_one_by_one():
    rng = np.random.default_rng(0)
    words, slot = 256, 8
    src = rng.random(words, dtype=np.float32)
    dst0 = rng.random(words, dtype=np.float32)
    s = rng.integers(0, words // slot, 200)
    d = rng.integers(0, words // slot, 200)      # many repeats: last wins
    want = dst0.copy()
    for a, b in zip(s, d):
        want[b * slot:(b + 1) * slot] = src[a * slot:(a + 1) * slot]
    got = refs.copy_replay(src, dst0, s, d, slot)
    assert refs.bad_words(got, want) == 0
    assert refs.bad_words(dst0, dst0) == 0


def test_bad_words_counts_bits_not_values():
    a = np.array([0.0, 1.0, np.nan], np.float32)
    b = np.array([-0.0, 1.0, np.nan], np.float32)
    assert refs.bad_words(a, b) == 1             # +0 and -0 differ
    assert refs.bad_words(a, a[:2]) == 3


def test_sum_error_separates_float32_from_bfloat16():
    shards = loadgen.gradient_buckets(11, 1, 4, 1 << 16)[0]
    ring = shards[1] + shards[2] + shards[3] + shards[0]   # another order
    f32 = refs.sum_error(shards, [ring, shards.sum(axis=0)])
    bf16 = refs.sum_error(shards, [refs.sum_bf16(shards)])
    assert f32 < 1e-6
    assert bf16 > 1e-3
    assert refs.sum_error(shards, [ring[:-1]]) == float("inf")
