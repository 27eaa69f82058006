"""The trace reduction on a small hand-checked trace."""

import os

import pytest

from chipbench.harness import reduce_trace as rt

from .conftest import DATA

WALL = 1_000_000_000_000  # wall clock minus trace clock in the fixture, ns


@pytest.fixture(scope="module")
def profile():
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "small_trace.pbtxt")) as f:
        text = "".join(line for line in f if not line.startswith("#"))
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text)
    )


def test_union_counts_overlap_once():
    assert rt.union([(1, 2), (1.5, 3), (3.5, 4), (0, 0.5)]) == [
        (0, 0.5), (1, 3), (3.5, 4)
    ]


def test_busy_launches_and_gaps_with_anchor(profile):
    window = (WALL + 900_000, WALL + 22_000_000)
    flushes = [(WALL + 980_000, WALL + 8_500_000), (WALL + 20_000_000, WALL + 22_000_000)]
    got = rt.reduce_profile(profile, window, flushes)
    assert got["device_planes"] == 1
    assert got["launches"] == 3
    assert got["anchored"] is True
    assert got["busy_s"] == pytest.approx(5.5e-3, rel=1e-9)
    assert got["window_s"] == pytest.approx(21.1e-3, rel=1e-9)
    # gaps: [8,21] between the flushes, [4,6] and [3,3.5] inside the first,
    # [0.9,1] before it starts
    assert [label for label, _ in got["idle_gaps"]] == [
        "between_flushes", "inside_flush", "inside_flush", "between_flushes"
    ]
    assert [s for _, s in got["idle_gaps"]] == pytest.approx(
        [13e-3, 2e-3, 0.5e-3, 0.1e-3], rel=1e-9
    )
    assert sum(got["idle_by_label_s"].values()) + got["busy_s"] == pytest.approx(
        got["window_s"], rel=1e-9
    )
    assert got["device_ops"][0] == ["module:jit_run(1)", pytest.approx(4e-3)]
    assert ["fusion.2", pytest.approx(3.5e-3)] in got["device_ops"]
    # the host's launch events inside the window are as many as the modules
    assert got["host_launch_events"] == 3
    # jit_run(1), jit_run(2), jit_run(1) repeats nothing twice: all three
    assert got["launches_in_whole_periods"] == 3
    assert got["module_s_per_launch"] == pytest.approx(2e-3, rel=1e-9)


def test_host_launches_with_and_without_a_window(profile):
    assert rt.host_launches(profile) == 4
    assert rt.host_launches(profile, (WALL + 900_000, WALL + 22_000_000)) == 3
    assert rt.host_launches(profile, (WALL + 2_000_000, WALL + 7_000_000)) == 1


def test_whole_periods_drops_the_check_that_the_window_cut():
    check = ["slice", "scan", "slice", "pair"]
    assert rt.whole_periods(check * 4 + check[:3]) == 16
    assert rt.whole_periods(check * 2) == 8
    assert rt.whole_periods(check + check[:1]) == 5  # nothing repeats twice
    assert rt.whole_periods(["a"] * 7) == 7
    assert rt.whole_periods([]) == 0


def test_without_a_window_the_span_of_device_events(profile):
    got = rt.reduce_profile(profile)
    assert got["anchored"] is False
    assert got["window_s"] == pytest.approx(21e-3, rel=1e-9)
    assert got["busy_s"] == pytest.approx(5.5e-3, rel=1e-9)
    assert {label for label, _ in got["idle_gaps"]} == {"unlabelled"}


def test_no_device_plane_reads_nothing():
    from jax.profiler import ProfileData

    text = 'planes { id: 1 name: "/host:CPU" }'
    data = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text)
    )
    assert rt.reduce_profile(data) is None
