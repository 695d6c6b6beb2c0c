from .ops import (DeviceExport, batched_lookup, mix64, patch_prepared,
                  snapshot_lookup)

__all__ = ["DeviceExport", "batched_lookup", "mix64", "patch_prepared",
           "snapshot_lookup"]
