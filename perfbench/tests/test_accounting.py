"""The benchmark's copied byte and flop formulas against the program's mix
registry as it stands: any drift between the two shows here."""
import pytest

from perfbench import accounting
from repro.bench import mixes

NAMES = mixes.mix_names() + ["fma_3", "fma_128", "rw_5to2", "rw_8to8"]


@pytest.mark.parametrize("name", NAMES)
def test_formulas_match_registry(name):
    mix = mixes.get_mix(name)
    for nbytes in (4096, 2**30):
        assert accounting.bytes_per_pass(name, nbytes) == \
            mix.bytes_per_pass(nbytes)
        n = nbytes // 4
        assert accounting.flops_per_pass(name, n) == mix.flops_per_pass(n)


def test_generator_sweeps_match_registry():
    assert accounting.GEN_SWEEPS_PER_PASS == mixes.GEN_SWEEPS_PER_PASS


@pytest.mark.parametrize("name", ["fma_0", "rw_0to1", "rw_9", "stream"])
def test_unknown_mix_is_an_error(name):
    with pytest.raises(KeyError):
        accounting.per_element(name)
