from .kernel import lower_bound, scan_window
from .ops import (QUERY_BLOCK, SCAN_LANES, prepare_sorted, snapshot_lookup,
                  snapshot_scan, sorted_lookup, sorted_scan)
from .ref import lookup_ref, scan_ref

__all__ = ["QUERY_BLOCK", "SCAN_LANES", "lower_bound", "scan_window",
           "prepare_sorted", "snapshot_lookup", "snapshot_scan",
           "sorted_lookup", "sorted_scan", "lookup_ref", "scan_ref"]
