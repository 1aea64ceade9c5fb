"""The algorithmic counts, the roofline arithmetic and the peak table."""

import json

import pytest

import counts


def test_deposition_work_uniform_o3():
    # 64^3 cells, 8 particles per cell, order 3: the paper's 419 FLOPs a particle
    flops, nbytes = counts.deposition_work(3, 2_097_152, 262_144)
    assert flops == 2_097_152 * 419
    assert nbytes == 2_097_152 * 28 + 3 * 262_144 * 4


@pytest.mark.parametrize("order,per_particle", [(1, 61), (2, 190), (3, 419)])
def test_canonical_flops_are_the_papers(order, per_particle):
    assert counts.deposition_work(order, 1, 0)[0] == per_particle


def test_gather_work():
    flops, nbytes = counts.gather_work(2, 1000, 64)
    assert flops == 6 * 27 * 2 * 1000
    assert nbytes == 6 * 64 * 4 + 1000 * 12 + 1000 * 24


def test_roofline_share_takes_the_larger_bound():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    # 50 FLOPs need 0.5 s, 20 bytes need 2 s: bytes bound; measured 4 s
    share, bound = counts.roofline_share(50.0, 20.0, 4.0, peak)
    assert bound == "bytes" and share == pytest.approx(50.0)
    share, bound = counts.roofline_share(500.0, 20.0, 10.0, peak)
    assert bound == "flops" and share == pytest.approx(50.0)


def test_uniform_o3_deposition_is_bytes_bound_on_v5e():
    peak = counts.peaks("TPU v5 lite")
    flops, nbytes = counts.deposition_work(3, 2_097_152, 262_144)
    assert flops / peak["flops_per_s"] == pytest.approx(4.46e-6, rel=1e-2)
    assert nbytes / peak["hbm_bytes_per_s"] == pytest.approx(7.55e-5, rel=1e-2)
    share, bound = counts.roofline_share(flops, nbytes, 7.55e-3, peak)
    assert bound == "bytes" and share == pytest.approx(1.0, rel=1e-2)


def test_unknown_device_kind_is_refused(tmp_path):
    with pytest.raises(KeyError, match="no peaks"):
        counts.peaks("TPU v9 imaginary")
    table = tmp_path / "peaks.json"
    table.write_text(json.dumps({"source": "x", "devices": {"A": {"flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}}}))
    assert counts.peaks("A", table)["flops_per_s"] == 1.0
    with pytest.raises(KeyError):
        counts.peaks("TPU v5 lite", table)
