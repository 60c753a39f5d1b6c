"""Each stage's roofline need from known shapes, and the peak table."""
import numpy as np
import pytest
from types import SimpleNamespace

from chipbench import roofline
from chipbench.roofline import agg, refine

BOXES = {"A": {"lat0": 37.70, "lng0": -122.52, "dlat": 0.11, "dlng": 0.12}}
CFG = {"cities": BOXES}
IN_A = (37.75, -122.45)
OUT = (33.0, -118.0)


def test_agg_need_counts_selected_rows_and_result_groups():
    lat = np.array([IN_A[0], IN_A[0], IN_A[0], OUT[0]])
    lng = np.array([IN_A[1], IN_A[1], IN_A[1], OUT[1]])
    tables = {"loc.lat": lat, "loc.lng": lng,
              "hour": np.array([8, 9, 12, 8]), "dow": np.zeros(4, int),
              "month": np.ones(4, int), "road_id": np.array([1, 2, 1, 3]),
              "speed": np.ones(4)}
    spec = {"cities": ["A"], "hour": [8, 9], "dow": [0, 4], "month": [1, 1]}
    # rows 0 and 1 reach the stage (key + value, 8 B each); 2 groups of
    # count, sum and sum of squares (12 B each)
    assert agg.need_bytes(tables, spec, CFG) == 2 * 8 + 2 * 12


def test_refine_need_counts_candidate_points_once():
    day2 = 2 * 86400.0
    # trip 0: 3 points, one in A at 7 h; trip 1: 2 points outside A
    lat = np.array([IN_A[0], OUT[0], OUT[0], OUT[0], OUT[0]])
    lng = np.array([IN_A[1], OUT[1], OUT[1], OUT[1], OUT[1]])
    t = np.full(5, day2 + 7 * 3600.0)
    tables = {"track.lat": lat, "track.lng": lng, "track.t": t,
              "track.lat/splits": np.array([0, 3, 5]),
              "id": np.array([10, 11]), "day": np.array([2, 2]),
              "duration_s": np.zeros(2)}
    spec = {"day": 2, "legs": [{"cities": ["A"], "hours": [6, 12]}]}
    assert refine.need_bytes(tables, spec, CFG) == 3 * 16 + 1


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v99")
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_share_is_none_without_stage_time():
    ctx = SimpleNamespace(trace={"ops": [("copy.3", 0, 1, {})], "lo": 0,
                                 "hi": 1}, traced=[{}], tables={}, cfg=CFG,
                          device_kind="TPU v5 lite")
    assert roofline.share("agg", ctx) is None


def test_share_divides_least_time_by_stage_time():
    need = agg.need_bytes  # 8 B a selected row, 12 B a group
    lat = np.full(2, IN_A[0])
    lng = np.full(2, IN_A[1])
    tables = {"loc.lat": lat, "loc.lng": lng, "hour": np.array([8, 8]),
              "dow": np.zeros(2, int), "month": np.ones(2, int),
              "road_id": np.array([1, 2]), "speed": np.ones(2)}
    spec = {"cities": ["A"], "hour": [8, 9], "dow": [0, 4], "month": [1, 1]}
    secs = need(tables, spec, CFG) / 819e9 * 4     # the kernel took 4x
    ctx = SimpleNamespace(trace={"ops": [("segment_agg.1", 0, secs, {})],
                                 "lo": 0, "hi": 1}, traced=[spec],
                          tables=tables, cfg=CFG,
                          device_kind="TPU v5 lite")
    assert roofline.share("agg", ctx) == pytest.approx(25.0)
