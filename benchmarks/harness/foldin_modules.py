"""What the readers of the fold-in look for in a device trace: the XLA
modules of every route of ``cfk_tpu/streaming/foldin.py``, by the names
their jitted entries carry (``_padded_fold``: the rectangle; ``_cells_fold_
gram`` and ``_cells_fold_solve``: the cells route, PR 41).  Renamed, they are
no longer found and the metrics fall silent, which is the point."""

FOLD_MODULES = ("_padded_fold", "_cells_fold")
