import numpy as np
import pytest

from chipbench import loadgen

BIG_SEED = 2 ** 31 + 12345


@pytest.mark.parametrize("words,msg,batch", [(1 << 20, 16, 50),
                                             (1 << 20, 4096, 50)])
def test_verbs_tape_is_seeded_distinct_and_never_coalescable(words, msg,
                                                             batch):
    a = loadgen.verbs_tape(BIG_SEED, words, msg, batch, 64)
    b = loadgen.verbs_tape(BIG_SEED, words, msg, batch, 64)
    c = loadgen.verbs_tape(BIG_SEED + 1, words, msg, batch, 64)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    remote, local = a
    for side in (remote, local):
        assert side.shape == (64, batch)
        assert side.min() >= 0 and side.max() < words // msg
        assert all(len(set(row)) == batch for row in side)
    assert not (np.diff(remote, axis=1) == 1).any()


def test_allreduce_shards_are_seeded_normal_f32():
    a = loadgen.gradient_buckets(BIG_SEED, 2, 4, 1 << 12)
    assert a.shape == (2, 4, 1 << 12) and a.dtype == np.float32
    assert np.array_equal(a, loadgen.gradient_buckets(BIG_SEED, 2, 4, 1 << 12))
    assert not np.array_equal(a, loadgen.gradient_buckets(7, 2, 4, 1 << 12))
    assert abs(a.mean()) < 0.05 and 0.95 < a.std() < 1.05


def test_source_row_is_seeded_and_in_one_binade():
    a = loadgen.source_words(BIG_SEED, 1 << 12)
    assert np.array_equal(a, loadgen.source_words(BIG_SEED, 1 << 12))
    assert a.dtype == np.float32 and a.min() >= 1 and a.max() < 2


@pytest.mark.parametrize("seed", [0, 7, BIG_SEED, 2 ** 62 + 3])
def test_sample_is_seeded_and_the_same_size_for_every_seed(seed):
    s = loadgen.sample(seed, 4, 2)
    assert len(s) == 2 and s <= set(range(4))
    assert s == loadgen.sample(seed, 4, 2)
    assert loadgen.sample(seed, 3, 5) == {0, 1, 2}
