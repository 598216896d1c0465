import numpy as np
import pytest

from harness import traffic as tr

MIX = {"batch": 4, "wave": 8,
       "prompt": {"min": 512, "max": 2048, "dist": "log-uniform"},
       "output": {"min": 16, "max": 64, "dist": "log-uniform"}}


def test_same_seed_same_wave():
    spec = tr.spec_of(MIX)
    a = tr.make_wave(spec, 64000, 2**31 + 7, 3)
    b = tr.make_wave(spec, 64000, 2**31 + 7, 3)
    assert all((x[0] == y[0]).all() and x[1] == y[1] for x, y in zip(a, b))


def test_seeds_share_the_lengths_and_their_order_and_not_the_tokens():
    spec = tr.spec_of(MIX)
    a = tr.make_wave(spec, 64000, 1, 0)
    b = tr.make_wave(spec, 64000, 2, 0)
    assert [(len(t), m) for t, m in a] == [(len(t), m) for t, m in b]
    assert not np.array_equal(a[0][0][:16], b[0][0][:16])


def test_waves_hold_the_same_lengths_in_another_order():
    spec = tr.spec_of(MIX)
    a = tr.make_wave(spec, 64000, 1, 0)
    b = tr.make_wave(spec, 64000, 1, 1)
    assert sorted(len(t) for t, _ in a) == sorted(len(t) for t, _ in b)
    assert sorted(m for _, m in a) == sorted(m for _, m in b)
    assert [len(t) for t, _ in a] != [len(t) for t, _ in b]


def test_lengths_follow_the_distribution():
    p, o = tr.wave_lengths(tr.spec_of(MIX))
    assert p.min() >= 512 and p.max() <= 2048 and o.min() >= 16
    # log-uniform mid-quantiles: equal ratios between neighbours
    r = p[1:] / p[:-1]
    assert np.allclose(r, r.mean(), rtol=0.01)
    assert tr.s_max(tr.spec_of(MIX)) == 2048 + 64


def test_tokens_stay_in_the_vocabulary():
    spec = tr.spec_of(MIX)
    for toks, _ in tr.make_wave(spec, 500, 9, 1):
        assert toks.dtype == np.int32 and toks.min() >= 1 and toks.max() < 500


@pytest.mark.parametrize("extra", [{"prefix_sharing": 0.5}, {"greedy": False},
                                   {"eos": 2}, {"arrival_rate": 4.0}])
def test_a_mix_the_generator_does_not_make_is_refused(extra):
    with pytest.raises(ValueError):
        tr.spec_of(dict(MIX, **extra))


def test_the_committed_mix_is_read_whole():
    from harness.cell import BENCH
    import json

    mix = json.loads((BENCH / "traffic" / "reasoning.json").read_text())
    assert tr.spec_of(mix).batch == 16
