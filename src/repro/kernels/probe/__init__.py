from .fingerprint import FP_EMPTY, account, fp64, fp_partial
from .kernel import probe64, probe64_fp
from .ops import (book_upload, combine64, fetch_packed, gather_chain_windows,
                  pack_outputs, pad_queries, split64, probe64_lookup,
                  probe64_windows, upload_packed)
from .ref import probe64_fp_ref, probe64_ref

__all__ = ["probe64", "probe64_fp", "probe64_lookup", "probe64_windows",
           "split64", "combine64", "book_upload", "upload_packed",
           "pack_outputs", "fetch_packed", "gather_chain_windows",
           "pad_queries",
           "fp64", "fp_partial", "FP_EMPTY", "account",
           "probe64_ref", "probe64_fp_ref"]
