"""The admission insert copies each mapped page once, exactly, and
touches no other pool row (``_kv_insert.check``): on one device here, and
on four-device meshes (tp=4, and dp=2 x tp=2) in one subprocess whose
output each case's test reads."""
import pytest

from _kv_insert import CASES, check
from test_distributed import run


@pytest.mark.parametrize("case", sorted(CASES))
def test_insert_copies_each_mapped_page_once(case):
    from repro.launch.mesh import make_mesh
    check(make_mesh((1, 1), ("data", "model")), case)


@pytest.fixture(scope="module")
def four_devices():
    """Output of the ``kv_insert_contract`` scenario: one line per case
    and mesh that passed."""
    return run("kv_insert_contract")


@pytest.mark.parametrize("mesh", ["1x4", "2x2"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_insert_copies_each_mapped_page_once_on_four_devices(
        four_devices, case, mesh):
    assert f"insert contract OK {case} {mesh}" in four_devices, (
        four_devices)
