"""Pallas TPU kernels for the compute hot-spots the paper's resources model.

Each kernel: <name>.py (pl.pallas_call + explicit BlockSpec VMEM tiling),
jit'd wrapper in ops.py, pure-jnp oracle in ref.py.  Kernels run compiled;
CPU tests ask for interpret mode.  ``tests/test_tpu_compile.py`` compiles
each for a described TPU v5e, and ``chip_smoke.py`` runs each on the chip
against its oracle.  On no model or simulator path, but for
``attention.flash_mha``, the attention of long self-attention on the TPU
(``models/layers._sdpa``).
"""
from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
