import pytest

from benchmark import bytes_model


def test_d2q9_fuse2():
    assert bytes_model.round_trip_bytes(11, 4) == 90
    assert bytes_model.bytes_per_update(11, 4, 2) == 45.0
    # PR 24's check: 0.10273 ns per update read 53.485 % of 819 GB/s
    least = bytes_model.least_hbm_seconds(1.0, 11, 4, 2, "TPU v5 lite")
    assert 100 * least / 0.10273e-9 == pytest.approx(53.485, abs=0.01)


def test_d3q27_cumulant_fuse3():
    assert bytes_model.round_trip_bytes(34, 4) == 274
    assert bytes_model.bytes_per_update(34, 4, 3) == pytest.approx(91.333,
                                                                   abs=1e-3)


def test_fuse_of_tag():
    assert bytes_model.fuse_of("pallas_2d[d2q9,fuse=2]") == 2
    assert bytes_model.fuse_of("pallas_d3q[d3q27_cumulant,fuse=3]") == 3
    assert bytes_model.fuse_of("pallas_sharded[{'y': 4, 'x': 1}]") == 0


def test_unknown_kind_is_an_error():
    with pytest.raises(KeyError):
        bytes_model.peak("cpu")
    with pytest.raises(ValueError):
        bytes_model.bytes_per_update(11, 4, 0)
