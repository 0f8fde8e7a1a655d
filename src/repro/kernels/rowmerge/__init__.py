from repro.kernels.rowmerge.ops import merge_rows
from repro.kernels.rowmerge.rowmerge import GROUP as ROW_GROUP
from repro.kernels.rowmerge import ref

__all__ = ["merge_rows", "ROW_GROUP", "ref"]
