"""Pallas TPU kernels for the paper's compute hot-spots.

The paper IS a kernel-engineering paper: its contribution is the naive and
tiled (shared-memory) AIDW kernels in two data layouts.  Each kernel here has
its pure-jnp oracle in ``ref.py`` and a jit'd public wrapper in ``ops.py``.
On a TPU the kernels are compiled by Mosaic; on the CPU they run in Pallas
interpret mode, which the correctness tests use.  The main-path kernels are
also compiled for a described TPU v5e at real widths by
``tests/kernels/test_tpu_compile.py``.
"""

from repro.kernels.ops import aidw, idw

__all__ = ["aidw", "idw"]
