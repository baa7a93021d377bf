"""Seeded inputs: one seed gives the same keys twice, another seed others,
and each stream draws apart from the rest."""
import pytest

from bench import generator

BIG = 2 ** 31 + 12345


@pytest.mark.parametrize("stream", sorted(generator.STREAMS))
def test_same_seed_same_keys_twice(stream):
    a = generator.key_words(BIG, stream)
    assert a.dtype.name == "uint32" and a.shape == (2,)
    assert a.tolist() == generator.key_words(BIG, stream).tolist()


@pytest.mark.parametrize("stream", sorted(generator.STREAMS))
def test_other_seed_other_keys(stream):
    assert generator.key_words(BIG, stream).tolist() != \
        generator.key_words(BIG + 1, stream).tolist()


def test_streams_are_independent():
    for seed in (0, 3, BIG, 2 ** 63 + 1):
        assert generator.key_words(seed, "weights").tolist() != \
            generator.key_words(seed, "train").tolist()
