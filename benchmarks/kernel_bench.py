"""Kernel micro-benchmarks: wall time per call on the default device —
compiled on a TPU, interpret mode anywhere else (a correctness-path
timing, not TPU performance); every row names the platform — plus the
analytic per-op latency table the simulator's resources implement
(Table 2 constants)."""
from __future__ import annotations

import functools
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_row
from repro.core.isa import Resource, VectorInstr, compute_latency_ns
from repro.hw.ssd_spec import DEFAULT_SSD
from repro.kernels import ops, ref


def _time(fn, *args, reps=3):
    fn(*args)  # compile
    t0 = time.time()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / reps * 1e6


def kernel_microbench() -> List[str]:
    rng = np.random.default_rng(0)
    rows = []
    dev = jax.devices()[0]
    interpret = dev.platform != "tpu"
    mode = "interpret" if interpret else "compiled"
    ops_ = {name: functools.partial(getattr(ops, name), interpret=interpret)
            for name in ("mws_bitwise", "bitserial_add", "bitserial_mul",
                         "shift_add_mul", "int8_matmul", "flash_attention")}
    print(f"\n== kernel microbench ({mode} on {dev.platform} "
          f"{dev.device_kind}, wall time per call)")
    stack = jnp.asarray(rng.integers(-2**31, 2**31, (8, 64, 512),
                                     dtype=np.int32))
    a = jnp.asarray(rng.integers(-2**20, 2**20, (64, 512), dtype=np.int32))
    b = jnp.asarray(rng.integers(-2**20, 2**20, (64, 512), dtype=np.int32))
    a8 = jnp.asarray(rng.integers(-128, 128, (128, 256), dtype=np.int8))
    b8 = jnp.asarray(rng.integers(-128, 128, (256, 128), dtype=np.int8))
    q = jnp.asarray(rng.normal(size=(4, 128, 64)).astype(np.float32))
    cases = [
        ("mws_and", lambda: ops_["mws_bitwise"](stack, "and")),
        ("bitserial_add", lambda: ops_["bitserial_add"](a, b)),
        ("bitserial_mul", lambda: ops_["bitserial_mul"](a, b)),
        ("shift_add_mul", lambda: ops_["shift_add_mul"](a, b)),
        ("int8_matmul", lambda: ops_["int8_matmul"](a8, b8)),
        ("flash_attention", lambda: ops_["flash_attention"](q, q, q)),
    ]
    for name, fn in cases:
        us = _time(fn)
        print(f"  {name:16s} {us:10.1f} us/call")
        rows.append(csv_row(f"kernel/{name}", f"{us:.1f}",
                            f"us_per_call {mode} {dev.platform} "
                            f"{dev.device_kind}"))
    return rows


def resource_latency_table() -> List[str]:
    """Analytic per-page-op latency of each SSD compute resource (the
    simulator's Table 2-derived model)."""
    rows = []
    spec = DEFAULT_SSD
    page = spec.page_size
    print("\n== per-page-op latency model (us), 16KiB INT8 vectors")
    print(f"  {'op':10s} {'ISP':>9s} {'PuD':>9s} {'IFP':>9s} "
          f"{'IFP(latched)':>13s} {'CPU':>9s} {'GPU':>9s}")
    for op in ("and", "xor", "add", "mul", "cmp"):
        ins = VectorInstr(iid=0, op=op, vlen=page, elem_bytes=1,
                          srcs=(0, 1), dst=2)
        vals = []
        for r in (Resource.ISP, Resource.PUD, Resource.IFP):
            vals.append(compute_latency_ns(ins, r, spec) / 1e3)
        latched = compute_latency_ns(ins, Resource.IFP, spec,
                                     operands_latched=True) / 1e3
        cpu = compute_latency_ns(ins, Resource.HOST_CPU, spec) / 1e3
        gpu = compute_latency_ns(ins, Resource.HOST_GPU, spec) / 1e3
        print(f"  {op:10s} {vals[0]:9.2f} {vals[1]:9.2f} {vals[2]:9.2f} "
              f"{latched:13.2f} {cpu:9.2f} {gpu:9.2f}")
        rows.append(csv_row(f"latmodel/{op}",
                            "/".join(f"{v:.2f}" for v in vals),
                            "isp/pud/ifp_us"))
    return rows
