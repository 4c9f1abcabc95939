import math

import pytest

from bench.lib import work


@pytest.mark.parametrize("p, per_elem", [(8, 894_300), (4, 106_740)])
def test_flops_per_element(p, per_elem):
    assert work.flops_per_elem(p) == per_elem


def test_flops_match_the_program_count():
    from repro.core.flops import paop_flops_per_elem

    for p in range(1, 9):
        assert work.flops_per_elem(p) == paop_flops_per_elem(p)


@pytest.mark.parametrize("p, refine, itemsize, mb", [
    (8, 3, 4, 52.050968), (8, 3, 8, 104.101936), (4, 4, 4, 52.280344)])
def test_apply_bytes(p, refine, itemsize, mb):
    nelem = 8 * 8**refine
    ndof = 3 * (8 * 2**refine * p + 1) * (2**refine * p + 1) ** 2
    assert ndof == 6_502_275
    assert math.isclose(work.apply_bytes(itemsize, ndof, nelem) / 1e6, mb)


def test_roofline_names_the_binding_term():
    peak = work.peaks("TPU v5 lite")
    flops = work.apply_flops(8, 4096)
    nbytes = work.apply_bytes(4, 6_502_275, 4096)
    pct, bound = work.roofline(flops, nbytes, 0.27, peak)
    assert bound == "memory"
    assert math.isclose(pct, 100 * nbytes / 819e9 / 0.27)
    assert work.roofline(1e15, 1.0, 10.0, peak)[1] == "compute"


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("cpu")
