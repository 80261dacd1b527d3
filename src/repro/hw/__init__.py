"""Hardware model: the simulated SSD (paper Table 2)."""
from repro.hw.ssd_spec import SSDSpec, DEFAULT_SSD

__all__ = ["SSDSpec", "DEFAULT_SSD"]
