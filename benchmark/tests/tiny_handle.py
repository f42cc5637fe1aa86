"""The tiny cell of ``test_rehearsal_handle.py``: one kind more for
``tiny.cell``, the rehearsal's tiny row-adagrad configuration under a tiny
twin of ``traffic/zipf-rows-handle.json``, named by no entry of
``workloads``."""

import tiny

tiny.KINDS["handle"] = ("tiny-sparse-row-adagrad.json",
                        "tiny-zipf-handle.json")
cell = tiny.cell
