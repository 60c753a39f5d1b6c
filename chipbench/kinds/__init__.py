"""Query kinds on the program's side: one module per kind, found by the
``kind`` a traffic file gives each query.  Each module turns a query's
data (``spec``) into the program's ``Flow`` (``flow(spec, cfg)``) and
reads the program's result back as plain arrays (``answer(result)``),
in the form the kind's reference module compares."""
from __future__ import annotations

from repro.geo import AreaTree, mercator

__all__ = ["region"]


def region(cities, boxes, level: int = 6) -> AreaTree:
    """Union of the cities' boxes, covered by area-tree cells down to
    ``level``: the region a query names."""
    area = AreaTree.empty()
    for c in cities:
        b = boxes[c]
        ix, iy = mercator.latlng_to_xy([b["lat0"], b["lat0"] + b["dlat"]],
                                       [b["lng0"], b["lng0"] + b["dlng"]])
        area = area | AreaTree.from_box(int(ix[0]), int(iy[1]), int(ix[1]),
                                        int(iy[0]), max_level=level)
    return area
