"""The trace reduction on one small recorded chip trace, and the table of
peaks."""

import io
import zipfile

import pytest

from chipbench_helpers import FIXTURES
from lib import peaks, profile_reduce


def test_busy_union_of_the_recorded_sumsq_turn():
    reduced = profile_reduce.reduce_xspace((FIXTURES / "sumsq_turn.xplane.pb").read_bytes())
    assert reduced["devices"] == 1
    assert reduced["busy_s"] == pytest.approx(0.116716220234, rel=1e-9)
    # eight passes: the generator fused with the first, then seven alike
    by_time = sorted(reduced["ops"].values(), reverse=True)
    assert len(by_time) == 2
    assert sum(by_time) == pytest.approx(reduced["busy_s"], rel=1e-9)
    assert all(name.startswith("%fusion") for name in reduced["ops"])


def test_a_trace_with_no_device_plane_is_an_error():
    with pytest.raises(profile_reduce.NoDevicePlane):
        profile_reduce.reduce_xspace((FIXTURES / "hello_turn.xplane.pb").read_bytes())


def test_profile_zip_is_read_through_its_xplane_member():
    blob = io.BytesIO()
    with zipfile.ZipFile(blob, "w") as archive:
        archive.writestr("plugins/profile/2026_09_30/host.xplane.pb",
                         (FIXTURES / "sumsq_turn.xplane.pb").read_bytes())
    assert profile_reduce.reduce_profile_zip(blob.getvalue())["busy_s"] > 0.1
    empty = io.BytesIO()
    with zipfile.ZipFile(empty, "w") as archive:
        archive.writestr("readme.txt", "no trace")
    with pytest.raises(profile_reduce.NoDevicePlane):
        profile_reduce.reduce_profile_zip(empty.getvalue())


@pytest.mark.parametrize(
    "intervals, want",
    [
        ([(0, 10), (20, 30)], 20e-12),
        ([(0, 10), (5, 15)], 15e-12),
        ([(5, 15), (0, 10), (0, 3)], 15e-12),
        ([(0, 10), (2, 4)], 10e-12),
        ([], 0.0),
    ],
)
def test_union_of_intervals(intervals, want):
    assert profile_reduce.union_seconds(intervals) == pytest.approx(want)


def test_peaks_of_an_unknown_device_kind_is_an_error():
    assert peaks.peaks_of("TPU v5 lite")["bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_of("TPU v9 imaginary")
