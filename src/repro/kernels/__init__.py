"""Pallas TPU kernels for the compute hot-spots the paper's resources model.

Each kernel: <name>.py (pl.pallas_call + explicit BlockSpec VMEM tiling),
jit'd wrapper in ops.py, pure-jnp oracle in ref.py.  Kernels run compiled;
CPU tests ask for interpret mode.  ``tests/test_tpu_compile.py`` compiles
each for a described TPU v5e, and ``chip_smoke.py`` runs each on the chip
against its oracle.  None is on a model or simulator path yet.
"""
from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
