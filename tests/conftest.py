import os

# Smoke tests and benches run on the CPU; a test that needs several devices
# or a described TPU makes them itself.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_enable_x64", False)
