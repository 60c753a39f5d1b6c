"""The control of each configuration fails its limits at a small size:
the aggregate reference one precision step down (bfloat16) fails
``cov_gap_max``; the trip answer without the exact refine fails
``rows_mismatched``."""
import pytest

from chipbench.control import readings
from chipbench.gen import ensure
from chipbench.reference import tables as ref_tables

from .conftest import small_config, traffic


@pytest.mark.parametrize("cfg_name,mix,number", [
    ("sec6_speedobs", "cov_analyst", "cov_gap_max"),
    ("sec6_trips", "tess_analyst", "rows_mismatched"),
    ("sec6_trips", "tess_served8", "rows_mismatched")])
def test_control_fails_the_limit(tmp_path, cfg_name, mix, number):
    cfg = small_config(cfg_name)
    directory, _, _ = ensure(cfg, 2**32 + 9, str(tmp_path))
    worst = readings(cfg, traffic(mix), 2**32 + 9,
                     ref_tables.load(directory))
    assert worst[number] > cfg["limits"][number], worst
