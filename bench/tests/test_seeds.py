"""Every input of a run comes from ``--seed``: the same seed gives the
same sub-seeds, whatever its size, and different seeds or tags differ."""
from harness import seeds


def test_same_seed_same_inputs():
    big = 2 ** 31 + 12345
    assert seeds.sub_seed(big, 3, 0) == seeds.sub_seed(big, 3, 0)
    assert (seeds.rng(big, 4).permutation(100)
            == seeds.rng(big, 4).permutation(100)).all()


def test_seeds_and_tags_differ():
    got = {seeds.sub_seed(s, t, c) for s in (0, 1, 2 ** 31 + 1, 2 ** 33 + 1,
                                             2 ** 64 + 1)
           for t in (1, 2, 3) for c in range(4)}
    assert len(got) == 5 * 3 * 4


def test_sub_seeds_fit_a_32_bit_prng_key_after_a_bracket_is_added():
    for s in (0, 2 ** 31 - 1, 2 ** 31 + 7, 2 ** 40, 10 ** 30):
        v = seeds.sub_seed(s, 3, 1)
        assert 0 <= v < 2 ** 30 and v + 800 < 2 ** 31
