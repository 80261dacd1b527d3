"""Conduit reproduction: programmer-transparent NDP offloading (the paper's
framework, §4), and a JAX model stack that trains and serves on TPU v5e with
fixed name-rule sharding plans.

Public API:
    repro.core.vectorize      compile-time pass: JAX fn -> vector IR
    repro.sim.simulate        event-driven execution under any policy
    repro.configs.get         the 10 assigned architecture configs
    repro.launch.*            mesh, sharding plans, train / serve drivers
"""
__version__ = "1.0.0"
