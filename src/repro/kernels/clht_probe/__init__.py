from .kernel import clht_probe
from .ops import (DeviceExport, batched_lookup, mix64, patch_prepared,
                  snapshot_lookup, tag_lookup)
from .ref import probe_ref

__all__ = ["clht_probe", "DeviceExport", "batched_lookup", "mix64",
           "patch_prepared", "snapshot_lookup", "tag_lookup", "probe_ref"]
