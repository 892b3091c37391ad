"""Core: the paper's contribution — PKT truss decomposition and its relatives."""

from repro.core.pkt import pkt, truss_pkt, PKTResult, peel_live_subset
from repro.core.truss_inc import IncrementalTruss, UpdateStats
from repro.core.hierarchy import (TrussHierarchy, HIER_MODES,
                                  hierarchy_from_graph)
from repro.core.support import (
    compute_support,
    compute_support_ros,
    triangle_count,
    build_support_table,
    build_peel_table,
    resolve_peel_table,
    support_table_size,
    peel_table_size,
    TABLE_MODES,
)
from repro.core.wc import truss_wc
from repro.core.ros import truss_ros
from repro.core.ref import truss_numpy
from repro.core.triangle_list import truss_trilist, enumerate_triangles
from repro.core.kcore import kcore_numpy, kcore_park
from repro.core.pkt_dist import pkt_dist, make_pkt_dist, make_support_dist

__all__ = [
    "pkt", "truss_pkt", "PKTResult", "peel_live_subset",
    "IncrementalTruss", "UpdateStats",
    "TrussHierarchy", "HIER_MODES", "hierarchy_from_graph",
    "compute_support", "compute_support_ros", "triangle_count",
    "build_support_table", "build_peel_table", "resolve_peel_table",
    "support_table_size", "peel_table_size", "TABLE_MODES",
    "truss_wc", "truss_ros", "truss_numpy",
    "truss_trilist", "enumerate_triangles",
    "kcore_numpy", "kcore_park",
    "pkt_dist", "make_pkt_dist", "make_support_dist",
]
