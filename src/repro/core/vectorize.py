"""Compile-time preprocessing (§4.3.1): programmer-transparent vectorization.

The paper runs a custom LLVM pass (``-force-vector-width=4096
-force-vector-interleave=1``) that turns loops into page-aligned SIMD
operations and embeds metadata in the IR.  Our IR is the **jaxpr**: the user
writes ordinary JAX code; :func:`vectorize` traces it, walks the equations,
and strip-mines every primitive into 16 KiB page-aligned
:class:`~repro.core.isa.VectorInstr` ops — 4096 lanes of 32-bit, or 16384
lanes after the paper's INT8 quantization (§5.4) — with SSA dependency
edges, operand logical pages, and operation-type metadata (Table 1).

Partial vectorization (strip-mining, §4.3.1): array tails that do not fill
a page become shorter-``vlen`` instructions.  Non-vectorizable equations
(data-dependent control flow, sorts, unknown-trip-count loops — the §7
limitations) are emitted as ``CONTROL`` instructions pinned to ISP,
mirroring the paper's treatment of control-intensive regions.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.extend.core import Literal

from repro.core.isa import (OP_TO_CLASS, Location, OpClass, VectorInstr,
                            latency_band)
from repro.core.mapping import PageTable
from repro.hw.ssd_spec import DEFAULT_SSD, SSDSpec

# -- primitive -> mnemonic table (the auto-vectorizer's pattern match) -------

_ELEMENTWISE = {
    "add": "add", "add_any": "add", "sub": "sub", "mul": "mul",
    "div": "div", "rem": "div", "pow": "mul", "integer_pow": "mul",
    "neg": "sub", "sign": "cmp", "abs": "max",
    "exp": "exp", "exp2": "exp", "log": "exp", "log1p": "exp",
    "expm1": "exp", "tanh": "tanh", "logistic": "logistic",
    "sqrt": "rsqrt", "rsqrt": "rsqrt", "cbrt": "rsqrt",
    "sin": "exp", "cos": "exp", "erf": "exp", "erf_inv": "exp",
    "max": "max", "min": "min",
    "and": "and", "or": "or", "xor": "xor", "not": "not",
    "shift_left": "shl", "shift_right_logical": "shr",
    "shift_right_arithmetic": "shr",
    "lt": "cmp", "le": "cmp", "gt": "cmp", "ge": "cmp",
    "eq": "cmp", "ne": "cmp",
    "floor": "cmp", "ceil": "cmp", "round": "cmp",
    "is_finite": "cmp", "square": "mul",
    "clamp": "select", "select_n": "select", "nextafter": "add",
}

_REDUCTIONS = {
    "reduce_sum": "reduce_sum", "reduce_max": "reduce_max",
    "reduce_min": "reduce_max", "reduce_prod": "reduce_sum",
    "reduce_and": "reduce_max", "reduce_or": "reduce_max",
    "argmax": "reduce_max", "argmin": "reduce_max",
    "reduce_precision": "copy",
}

_COPYLIKE = {
    "broadcast_in_dim": "broadcast", "convert_element_type": "copy",
    "concatenate": "copy", "pad": "copy",
    "dynamic_update_slice": "copy",
    "iota": "iota", "copy": "copy", "device_put": "copy",
}

_SHUFFLE = {"transpose": "shuffle", "rev": "shuffle"}
_GATHERLIKE = {"gather": "gather", "scatter": "scatter",
               "scatter-add": "scatter", "scatter_add": "scatter"}
_FREE = {"reshape", "squeeze", "expand_dims", "stop_gradient",
         "bitcast_convert_type", "copy_p", "sharding_constraint",
         "split", "optimization_barrier"}
_CONTROL = {"sort", "while", "cond", "top_k", "cumsum", "cumlogsumexp",
            "cummax", "approx_top_k"}
_RECURSE = {"pjit", "closed_call", "custom_jvp_call", "custom_vjp_call",
            "custom_vjp_call_jaxpr", "remat", "checkpoint", "custom_jvp_call_jaxpr",
            "remat_call", "named_call", "core_call", "jvp_call"}


@dataclasses.dataclass
class TraceStats:
    """Table 3 workload characterization."""

    total_instrs: int
    vectorizable_pct: float          # fraction of vectorizable instructions
    avg_reuse: float                 # reads per distinct page before overwrite
    band_mix: Dict[str, float]       # {low, medium, high} fractions
    op_mix: Dict[str, int]
    footprint_bytes: int

    def as_row(self) -> Dict[str, Any]:
        return {
            "vectorizable_pct": round(100 * self.vectorizable_pct, 1),
            "avg_reuse": round(self.avg_reuse, 1),
            "low_pct": round(100 * self.band_mix.get("low", 0.0)),
            "medium_pct": round(100 * self.band_mix.get("medium", 0.0)),
            "high_pct": round(100 * self.band_mix.get("high", 0.0)),
            "instrs": self.total_instrs,
        }


@dataclasses.dataclass
class Trace:
    """Output of compile-time preprocessing: the Conduit binary."""

    instrs: List[VectorInstr]
    pages: PageTable
    input_pages: Dict[str, List[int]]
    output_pages: List[List[int]]
    name: str = ""

    def characterize(self) -> TraceStats:
        """Workload characterization (Table 3).

        ``avg_reuse``: operations consuming the same data *version* before
        it is replaced — reads of each page between consecutive writes,
        averaged over versions.
        """
        cur_reads: Dict[int, int] = {}
        version_reads: List[int] = []
        bands: Dict[str, int] = {"low": 0, "medium": 0, "high": 0}
        ops: Dict[str, int] = {}
        nvec = 0
        for ins in self.instrs:
            for s in ins.srcs:
                cur_reads[s] = cur_reads.get(s, 0) + 1
            if ins.dst in cur_reads:
                version_reads.append(cur_reads.pop(ins.dst))
            if ins.vectorizable:
                nvec += 1
                # Band mix counts computation ops only — COPY instructions
                # are data staging, not computation (Table 3 counts ops).
                if ins.op_class is not OpClass.COPY:
                    bands[latency_band(ins.op_class)] += 1
            ops[ins.op] = ops.get(ins.op, 0) + 1
        version_reads.extend(cur_reads.values())   # final live versions
        total = len(self.instrs)
        nbv = max(1, sum(bands.values()))
        avg_reuse = (sum(version_reads) / max(1, len(version_reads)))
        return TraceStats(
            total_instrs=total,
            vectorizable_pct=nvec / max(1, total),
            avg_reuse=avg_reuse,
            band_mix={k: v / nbv for k, v in bands.items()},
            op_mix=ops,
            footprint_bytes=len(self.pages) * self.pages.spec.page_size,
        )


class _Vectorizer:
    def __init__(self, spec: SSDSpec, elem_bytes: int, quantize: bool,
                 max_instrs: int, scan_unroll_limit: int,
                 matmul_k_steps: int = 16):
        self.spec = spec
        self.page_bytes = spec.page_size
        self.elem_bytes = elem_bytes
        self.quantize = quantize
        self.max_instrs = max_instrs
        self.scan_unroll_limit = scan_unroll_limit
        self.matmul_k_steps = matmul_k_steps
        self.pages = PageTable(spec)
        self.instrs: List[VectorInstr] = []
        self.producer: Dict[int, int] = {}      # page id -> producing iid
        self._iid = 0

    # -- helpers --------------------------------------------------------------

    def _ebytes(self, aval) -> int:
        if self.quantize:
            return self.elem_bytes           # INT8 quantization (§5.4)
        return aval.dtype.itemsize

    def _lanes(self, ebytes: int) -> int:
        return self.page_bytes // ebytes

    def _npages(self, aval) -> int:
        return max(1, math.ceil(aval.size * self._ebytes(aval) / self.page_bytes))

    def pages_for(self, env: Dict, atom) -> Optional[List[int]]:
        """Logical pages for a jaxpr atom (None = scalar literal)."""
        if isinstance(atom, Literal):
            if np.ndim(atom.val) == 0 or np.size(atom.val) <= 8:
                return None
            pids = self.pages.alloc_array(
                int(np.size(atom.val)) * self._ebytes(atom.aval), name="lit")
            return pids
        return env[atom]

    def emit(self, op: str, srcs: Sequence[Optional[int]], dst: int,
             vlen: int, ebytes: int, tag: str = "",
             vectorizable: bool = True) -> int:
        if len(self.instrs) >= self.max_instrs:
            raise TraceBudgetExceeded(
                f"trace exceeded max_instrs={self.max_instrs}; "
                f"reduce the workload scale (tag={tag})")
        real_srcs = tuple(s for s in srcs if s is not None)
        deps = tuple(sorted({self.producer[s] for s in real_srcs
                             if s in self.producer}
                            | ({self.producer[dst]} if dst in self.producer
                               else set())))
        iid = self._iid
        self._iid += 1
        self.instrs.append(VectorInstr(
            iid=iid, op=op, vlen=vlen, elem_bytes=ebytes,
            srcs=real_srcs, dst=dst, deps=deps, tag=tag,
            vectorizable=vectorizable))
        self.producer[dst] = iid
        return iid

    def emit_map(self, op: str, in_pages: Sequence[Optional[List[int]]],
                 out_pages: List[int], aval, tag: str,
                 vectorizable: bool = True) -> None:
        """Strip-mine an elementwise op over the output pages."""
        ebytes = self._ebytes(aval)
        lanes = self._lanes(ebytes)
        total = aval.size
        for i, dst in enumerate(out_pages):
            vlen = min(lanes, total - i * lanes) if total > 0 else lanes
            srcs = []
            for pl in in_pages:
                if pl is None:
                    srcs.append(None)
                elif len(pl) == 0:
                    srcs.append(None)
                else:
                    srcs.append(pl[min(i, len(pl) - 1)])  # broadcast reuse
            self.emit(op, srcs, dst, max(1, vlen), ebytes, tag,
                      vectorizable=vectorizable)

    # -- equation dispatch ----------------------------------------------------

    def run(self, jaxpr, env: Dict) -> None:
        for eqn in jaxpr.eqns:
            self.eqn(eqn, env)

    def _bind_outputs(self, eqn, env, pages_list):
        for var, pl in zip(eqn.outvars, pages_list):
            env[var] = pl

    def _out_pages(self, eqn, idx=0, name=""):
        aval = eqn.outvars[idx].aval
        return self.pages.alloc_array(
            aval.size * self._ebytes(aval), name=name or str(eqn.primitive))

    def eqn(self, eqn, env: Dict) -> None:
        prim = eqn.primitive.name
        tag = prim

        if prim in _RECURSE or prim == "pjit":
            inner = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
            if inner is None:
                self._fallback_control(eqn, env)
                return
            closed = inner if hasattr(inner, "jaxpr") else None
            inner_jaxpr = closed.jaxpr if closed is not None else inner
            sub_env: Dict = {}
            for iv, atom in zip(inner_jaxpr.invars, eqn.invars):
                sub_env[iv] = self.pages_for(env, atom)
            if closed is not None:
                for cv, val in zip(inner_jaxpr.constvars, closed.consts):
                    sub_env[cv] = self.pages.alloc_array(
                        int(np.size(val)) * self.elem_bytes, name="const")
            self.run(inner_jaxpr, sub_env)
            for ov, innerv in zip(eqn.outvars, inner_jaxpr.outvars):
                if isinstance(innerv, Literal):
                    env[ov] = self.pages.alloc_array(
                        innerv.aval.size * self._ebytes(innerv.aval), "lit")
                else:
                    env[ov] = sub_env[innerv]
            return

        if prim == "scan":
            self._scan(eqn, env)
            return

        if prim == "dot_general":
            self._dot_general(eqn, env)
            return

        if prim in _FREE:
            src = self.pages_for(env, eqn.invars[0])
            out_aval = eqn.outvars[0].aval
            need = self._npages(out_aval)
            if src is None or len(src) < need:
                out = self._out_pages(eqn)
                self.emit_map("copy", [src], out, out_aval, tag)
                env[eqn.outvars[0]] = out
            else:
                env[eqn.outvars[0]] = src[:need]   # aliasing, no data movement
            for extra in eqn.outvars[1:]:
                env[extra] = self.pages.alloc_array(
                    extra.aval.size * self._ebytes(extra.aval), prim)
            return

        if prim in ("slice", "dynamic_slice"):
            # A vectorized load at an offset reads the source pages in place:
            # alias the page sub-range covering the sliced bytes (no copy).
            src = self.pages_for(env, eqn.invars[0])
            in_aval = eqn.invars[0].aval
            out_aval = eqn.outvars[0].aval
            if src is None:
                env[eqn.outvars[0]] = None
                return
            eb = self._ebytes(in_aval)
            if prim == "slice":
                starts = eqn.params["start_indices"]
                limits = eqn.params["limit_indices"]
                acc, flat_start, flat_last = 1, 0, 0
                for dim in range(len(in_aval.shape) - 1, -1, -1):
                    flat_start += starts[dim] * acc
                    flat_last += (limits[dim] - 1) * acc
                    acc *= in_aval.shape[dim]
            else:
                flat_start, flat_last = 0, in_aval.size - 1   # dynamic start
            first = (flat_start * eb) // self.page_bytes
            last = (flat_last * eb) // self.page_bytes
            sub = src[first:last + 1] or src[-1:]
            env[eqn.outvars[0]] = sub
            return

        if prim in _ELEMENTWISE:
            op = _ELEMENTWISE[prim]
            ins = [self.pages_for(env, a) for a in eqn.invars]
            out = self._out_pages(eqn)
            self.emit_map(op, ins, out, eqn.outvars[0].aval, tag)
            self._bind_outputs(eqn, env, [out])
            return

        if prim in _REDUCTIONS:
            self._reduction(eqn, env, _REDUCTIONS[prim])
            return

        if prim in _COPYLIKE:
            op = _COPYLIKE[prim]
            ins = [self.pages_for(env, a) for a in eqn.invars]
            outs = []
            for idx, ov in enumerate(eqn.outvars):
                out = self.pages.alloc_array(
                    ov.aval.size * self._ebytes(ov.aval), prim)
                self.emit_map(op, ins, out, ov.aval, tag)
                outs.append(out)
            self._bind_outputs(eqn, env, outs)
            return

        if prim in _SHUFFLE or prim in _GATHERLIKE:
            op = _SHUFFLE.get(prim) or _GATHERLIKE[prim]
            ins = [self.pages_for(env, a) for a in eqn.invars]
            out = self._out_pages(eqn)
            self.emit_map(op, ins, out, eqn.outvars[0].aval, tag)
            self._bind_outputs(eqn, env, [out])
            return

        if prim == "threefry2x32":
            ins = [self.pages_for(env, a) for a in eqn.invars]
            out = self._out_pages(eqn)
            aval = eqn.outvars[0].aval
            for op in ("xor", "shl", "add", "xor"):   # fused PRNG rounds
                self.emit_map(op, ins, out, aval, tag)
                ins = [out]
            self._bind_outputs(eqn, env, [out])
            return

        if prim in _CONTROL:
            self._fallback_control(eqn, env)
            return

        # Unknown primitive: conservatively non-vectorizable (paper §7).
        self._fallback_control(eqn, env)

    def _fallback_control(self, eqn, env: Dict) -> None:
        ins = [self.pages_for(env, a) for a in eqn.invars]
        outs = []
        for ov in eqn.outvars:
            aval = ov.aval
            out = self.pages.alloc_array(
                aval.size * self._ebytes(aval), str(eqn.primitive))
            # CONTROL region: per-page scalar execution on ISP.
            self.emit_map("scalar", ins, out, aval,
                          tag=str(eqn.primitive), vectorizable=False)
            outs.append(out)
        self._bind_outputs(eqn, env, outs)

    def _scan(self, eqn, env: Dict) -> None:
        """Counted loop: unroll (LLVM vectorizes counted loops, §4.3.1)."""
        length = eqn.params["length"]
        ncarry = eqn.params["num_carry"]
        nconsts = eqn.params["num_consts"]
        closed = eqn.params["jaxpr"]
        body = closed.jaxpr
        if length > self.scan_unroll_limit:
            # unknown/large trip count -> §7 limitation: control fallback
            self._fallback_control(eqn, env)
            return
        consts = [self.pages_for(env, a) for a in eqn.invars[:nconsts]]
        carry = [self.pages_for(env, a)
                 for a in eqn.invars[nconsts:nconsts + ncarry]]
        xs = [self.pages_for(env, a) for a in eqn.invars[nconsts + ncarry:]]
        ys_accum: List[List[int]] = [[] for _ in range(len(eqn.outvars) - ncarry)]
        for t in range(length):
            sub_env: Dict = {}
            bvars = body.invars
            for cv, val in zip(body.constvars, closed.consts):
                sub_env[cv] = self.pages.alloc_array(
                    int(np.size(val)) * self.elem_bytes, "const")
            for v, pl in zip(bvars[:nconsts], consts):
                sub_env[v] = pl
            for v, pl in zip(bvars[nconsts:nconsts + ncarry], carry):
                sub_env[v] = pl
            for v, pl in zip(bvars[nconsts + ncarry:], xs):
                if pl is None:
                    sub_env[v] = None
                else:
                    per = max(1, len(pl) // max(1, length))
                    sub_env[v] = pl[t * per:(t + 1) * per] or pl[-per:]
            self.run(body, sub_env)
            outs = []
            for ov in body.outvars:
                if isinstance(ov, Literal):
                    outs.append(self.pages.alloc_array(
                        max(1, ov.aval.size) * self.elem_bytes, "lit"))
                else:
                    outs.append(sub_env[ov])
            carry = outs[:ncarry]
            for k, ypl in enumerate(outs[ncarry:]):
                ys_accum[k].extend(ypl or [])
        for var, pl in zip(eqn.outvars[:ncarry], carry):
            env[var] = pl
        for var, pl in zip(eqn.outvars[ncarry:], ys_accum):
            env[var] = pl or self.pages.alloc_array(
                var.aval.size * self._ebytes(var.aval), "scan_y")

    def _reduction(self, eqn, env: Dict, op: str) -> None:
        src = self.pages_for(env, eqn.invars[0])
        out_aval = eqn.outvars[0].aval
        out = self.pages.alloc_array(
            max(1, out_aval.size) * self._ebytes(out_aval), op)
        ebytes = self._ebytes(eqn.invars[0].aval)
        lanes = self._lanes(ebytes)
        if src is None:
            self.emit(op, [], out[0], 1, ebytes, op)
        else:
            # accumulate page partials into the (smaller) output; successive
            # accumulations into one page serialize via the producer dep.
            for i, s in enumerate(src):
                dst = out[i % len(out)]
                self.emit(op, [s, dst], dst,
                          min(lanes, eqn.invars[0].aval.size), ebytes, op)
        self._bind_outputs(eqn, env, [out])

    def _dot_general(self, eqn, env: Dict) -> None:
        """Decompose a matmul into page-wide multiply + accumulate chains.

        C[b, m, n] += A[b, m, k] * B[b, k, n]: vectorize over n (lanes);
        each (m, k, n-page) triple becomes a ``mul`` into a scratch page
        followed by an ``add`` into the accumulator page — the two native
        SIMD ops every resource's ISA actually exposes (bbop_mul/bbop_add,
        ifp.shift_and_add / ifp.shift_add, mve.vmul / mve.vadd).

        Contraction steps are grouped into at most ``matmul_k_steps``
        macro-iterations per output page (the vectorizer's interleave
        granularity): each macro-iteration is one page-wide mul+add pair.
        """
        a_aval = eqn.invars[0].aval
        b_aval = eqn.invars[1].aval
        out_aval = eqn.outvars[0].aval
        dnums = eqn.params["dimension_numbers"]
        ((a_contract, b_contract), (a_batch, b_batch)) = dnums
        k = int(np.prod([a_aval.shape[d] for d in a_contract])) or 1
        batch = int(np.prod([a_aval.shape[d] for d in a_batch])) or 1
        m = max(1, a_aval.size // max(1, k * batch))
        n = max(1, b_aval.size // max(1, k * batch))
        ebytes = self._ebytes(out_aval)
        lanes = self._lanes(ebytes)
        n_pages = max(1, math.ceil(n / lanes))

        a_pages = self.pages_for(env, eqn.invars[0]) or []
        b_pages = self.pages_for(env, eqn.invars[1]) or []
        out = self.pages.alloc_array(out_aval.size * ebytes, "dot")

        bp = max(1, len(b_pages))
        ap = max(1, len(a_pages))
        scratch = self.pages.alloc_array(
            min(len(out), 8) * self.page_bytes, "dot_tmp", Location.DRAM)
        k_steps = min(k, self.matmul_k_steps)
        # Vectorize over the flattened OUTPUT (interleaved rows fill a full
        # page-wide vector); the contraction is the serial loop, grouped
        # into k_steps macro-iterations of one page-wide mul + add each.
        total_out = out_aval.size
        for opg, dst in enumerate(out):
            tmp = scratch[opg % len(scratch)]
            vlen = max(1, min(lanes, total_out - opg * lanes))
            for ki in range(k_steps):
                a_pid = a_pages[(opg * k_steps + ki) % ap] if a_pages else None
                b_pid = b_pages[(ki * len(out) + opg) % bp] if b_pages else None
                self.emit("mul", [a_pid, b_pid], tmp, vlen, ebytes,
                          "dot_general")
                self.emit("add", [tmp, dst], dst, vlen, ebytes, "dot_general")
        self._bind_outputs(eqn, env, [out])


class TraceBudgetExceeded(RuntimeError):
    pass


def _compact(instrs: List[VectorInstr], pages: PageTable,
             input_pages: Dict[str, List[int]],
             output_pages: List[List[int]], spec: SSDSpec):
    """Liveness-based page recycling (the buffer-reuse pass every real
    compiler performs: LLVM's vectorized loops update arrays in place, they
    do not allocate fresh SSA storage per operation).

    Input/const pages (live-in data) and trace outputs are pinned; every
    intermediate page is remapped onto a recycled physical pool once its
    last reader has issued.  SSA dependency edges (iids) are untouched —
    only page identities change — so execution ordering is preserved.
    """
    pinned = set()
    for pl in input_pages.values():
        pinned.update(pl)
    for pl in output_pages:
        pinned.update(pl)
    written: set = set()
    for ins in instrs:
        for s in ins.srcs:
            if s not in written:
                pinned.add(s)        # read-before-write: live-in constant
        written.add(ins.dst)

    last_use: Dict[int, int] = {}
    for ins in instrs:
        for p in ins.srcs + (ins.dst,):
            last_use[p] = ins.iid

    new_pages = PageTable(spec)
    mapping: Dict[int, int] = {}
    for vp in sorted(pinned):
        ent = pages[vp]
        npid = new_pages.alloc_array(spec.page_size, name=ent.name,
                                     location=ent.location)[0]
        mapping[vp] = npid

    free: List[int] = []
    release_at: Dict[int, List[int]] = {}
    for vp, iid in last_use.items():
        if vp not in pinned:
            release_at.setdefault(iid, []).append(vp)

    def lookup(vp: int) -> int:
        if vp in mapping:
            return mapping[vp]
        if free:
            npid = free.pop()
        else:
            npid = new_pages.alloc_array(
                spec.page_size, name="tmp", location=Location.DRAM)[0]
        mapping[vp] = npid
        return npid

    for ins in instrs:
        ins.srcs = tuple(lookup(s) for s in ins.srcs)
        ins.dst = lookup(ins.dst)
        for vp in release_at.get(ins.iid, ()):
            if vp in mapping:
                free.append(mapping.pop(vp))

    # pinned pages stay in `mapping` (never released)
    new_inputs = {k: [mapping[p] for p in pl] for k, pl in input_pages.items()}
    new_outputs = [[mapping[p] for p in pl if p in mapping]
                   for pl in output_pages]
    return new_pages, new_inputs, new_outputs


def vectorize(fn: Callable, *example_args,
              spec: SSDSpec = DEFAULT_SSD,
              elem_bytes: int = 1,                 # INT8 quantization (§5.4)
              quantize: bool = True,
              max_instrs: int = 400_000,
              scan_unroll_limit: int = 128,
              matmul_k_steps: int = 16,
              name: str = "") -> Trace:
    """Trace ``fn`` and emit the Conduit vector-instruction binary.

    This is the full compile-time phase: loop auto-vectorization (jaxpr
    equations are already loop-free SSA over arrays — each equation is the
    vectorized loop body), strip-mining into page-aligned instructions, and
    metadata embedding.  Inputs are assumed resident in flash at t=0 (§4.4
    "we assume all application data resides in the SSD").
    """
    closed = jax.make_jaxpr(fn)(*example_args)
    v = _Vectorizer(spec, elem_bytes, quantize, max_instrs, scan_unroll_limit,
                    matmul_k_steps)
    env: Dict = {}
    input_pages: Dict[str, List[int]] = {}
    flat, _ = jax.tree_util.tree_flatten(example_args)
    for i, (var, val) in enumerate(zip(closed.jaxpr.invars, flat)):
        ebytes = v._ebytes(var.aval)
        pids = v.pages.alloc_array(max(1, var.aval.size) * ebytes,
                                   name=f"in{i}")
        env[var] = pids
        input_pages[f"in{i}"] = pids
    for cv, val in zip(closed.jaxpr.constvars, closed.consts):
        env[cv] = v.pages.alloc_array(
            max(1, int(np.size(val))) * v.elem_bytes, name="const")
    v.run(closed.jaxpr, env)
    out_pages = []
    for ov in closed.jaxpr.outvars:
        if isinstance(ov, Literal):
            out_pages.append([])
        else:
            out_pages.append(env[ov] or [])
    new_pages, new_in, new_out = _compact(v.instrs, v.pages, input_pages,
                                          out_pages, spec)
    return Trace(instrs=v.instrs, pages=new_pages, input_pages=new_in,
                 output_pages=new_out,
                 name=name or getattr(fn, "__name__", "fn"))
