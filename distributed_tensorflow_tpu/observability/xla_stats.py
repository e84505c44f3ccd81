"""Per-compiled-program XLA memory/compile ledger.

XLA reports HBM footprint and compile cost for free on every backend: ``compiled.memory_analysis()``
carries argument/output/temp/generated-code bytes per executable (the
tests already read it on CPU), and compile wall-time is one
``perf_counter`` pair around ``lower().compile()``.  :class:`ProgramLedger`
captures both without changing what runs:

* ``ledger.jit(fn, name=...)`` replaces a ``jax.jit(fn)`` call site.
  With no ledger (flag off) the call site uses ``jax.jit`` literally, so
  the compiled-program set is byte-identical — the PR 11 parity
  discipline.  With a ledger, the wrapper AOT-compiles on first call per
  abstract argument signature (``jax.jit(fn).lower(*args).compile()``),
  times the compile, records the executable's memory analysis, then
  dispatches the cached executable — same program, one extra host-side
  bookkeeping pass at compile time, zero per-call device syncs.
* ``capture(name, lowered_or_compiled)`` records programs compiled
  elsewhere (a caller that already AOT-lowers a step for
  ``cost_analysis`` gets its memory analysis from the same executable at
  no extra compile).

``peak_bytes_est`` per program is ``argument + output + temp − alias``
bytes — XLA's own live-footprint decomposition; ``manifest()`` sums it
per run (every program's buffers are resident in a serving process) and
totals compile seconds.  ``analyze programs --against BASELINE`` diffs
two manifests: a new program or temp-bytes growth past a threshold exits
nonzero — the reusable form of today's hand-written program-set pins.

If AOT lowering fails for a call site (exotic shardings, backend quirks),
the wrapper falls back to plain ``jax.jit`` dispatch and records the
program name with ``compile_s`` only — observability must never take the
serving path down.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable

# jax is imported lazily inside the jit path: the manifest/diff half of
# this module is what the stdlib-only `analyze programs` CLI imports,
# and it must not pay (or require) a jax import

_MEM_FIELDS = (
    ("argument_size_in_bytes", "argument_bytes"),
    ("output_size_in_bytes", "output_bytes"),
    ("temp_size_in_bytes", "temp_bytes"),
    ("generated_code_size_in_bytes", "generated_code_bytes"),
    ("alias_size_in_bytes", "alias_bytes"),
)


def cost_fields(compiled) -> dict[str, Any]:
    """Extract XLA ``cost_analysis`` flops/bytes from a compiled
    executable — the same executable whose memory_analysis the ledger
    already reads, at zero extra compiles.  None-tolerant: CPU backends
    may report nothing, and rounds 19's roofline attribution treats a
    None column as "no data", never as zero work."""
    out: dict[str, Any] = {"flops": None, "bytes_accessed": None}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else None
        if ca is not None:
            flops = float(ca.get("flops", 0.0) or 0.0)
            nbytes = float(ca.get("bytes accessed", 0.0) or 0.0)
            out["flops"] = flops if flops > 0 else None
            out["bytes_accessed"] = nbytes if nbytes > 0 else None
    except Exception:
        pass
    return out


def memory_fields(compiled) -> dict[str, int]:
    """Extract the memory-analysis byte fields from a compiled executable,
    zeros when the backend reports nothing (memory_analysis may be None
    or partial off-TPU)."""
    out = {dst: 0 for _, dst in _MEM_FIELDS}
    try:
        mem = compiled.memory_analysis()
    except Exception:
        mem = None
    if mem is not None:
        for src, dst in _MEM_FIELDS:
            try:
                out[dst] = int(getattr(mem, src, 0) or 0)
            except Exception:
                pass
    # XLA's live-footprint decomposition: arguments + outputs + temps
    # minus donated/aliased bytes counted twice
    out["peak_bytes_est"] = max(
        out["argument_bytes"] + out["output_bytes"] + out["temp_bytes"]
        - out["alias_bytes"], 0)
    return out


def _abstract_signature(args: tuple) -> tuple:
    """Hashable (treedef, per-leaf shape/dtype) key — one compile per
    distinct abstract signature, mirroring jax.jit's own cache key."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(args)
    return treedef, tuple(
        (tuple(getattr(x, "shape", ())), str(getattr(x, "dtype", type(x))),
         bool(getattr(x, "weak_type", False)))
        for x in leaves)


class _ObservedJit:
    """Callable standing in for one ``jax.jit(fn)``: AOT-compiles per
    abstract signature with timing + memory capture, dispatches the
    cached executable thereafter."""

    def __init__(self, ledger: "ProgramLedger", fn: Callable, name: str,
                 **jit_kwargs: Any):
        import jax

        self._ledger = ledger
        self._jitted = jax.jit(fn, **jit_kwargs)
        self._name = name
        self._compiled: dict[tuple, Callable] = {}

    def __call__(self, *args):
        sig = _abstract_signature(args)
        compiled = self._compiled.get(sig)
        if compiled is None:
            t0 = time.perf_counter()
            try:
                compiled = self._jitted.lower(*args).compile()
            except Exception:
                # fall back to the plain jitted callable: its first call
                # still compiles (timed below), but no memory analysis
                compiled = self._jitted
                self._compiled[sig] = compiled
                out = compiled(*args)
                self._ledger._record(self._name, None,
                                     time.perf_counter() - t0)
                return out
            self._compiled[sig] = compiled
            self._ledger.capture(self._name, compiled,
                                 compile_s=time.perf_counter() - t0)
        return compiled(*args)


class ProgramLedger:
    """Named per-program memory/compile records (module docstring).
    Thread-safe: the serving fleet's replica workers compile through one
    shared ledger."""

    def __init__(self):
        self._lock = threading.Lock()
        # name -> {compiles, compile_s, <memory fields>}
        self._programs: dict[str, dict[str, Any]] = {}

    # ------------------------------------------------------------- capture
    def jit(self, fn: Callable, name: str, **jit_kwargs: Any) -> _ObservedJit:
        """Observed replacement for ``jax.jit(fn, **jit_kwargs)``.  Call
        sites select it with ``jax.jit if ledger is None else ledger.jit``
        so the flag-off path is the literal builtin."""
        return _ObservedJit(self, fn, name, **jit_kwargs)

    def capture(self, name: str, compiled, compile_s: float = 0.0) -> None:
        """Record a compiled executable's memory analysis under ``name``
        (programs compiled elsewhere enter
        here at zero extra compile cost).  Cost-analysis flops/bytes ride
        the same executable (round 19's roofline columns)."""
        self._record(name, {**memory_fields(compiled),
                            **cost_fields(compiled)}, compile_s)

    def _record(self, name: str, mem: dict[str, int] | None,
                compile_s: float) -> None:
        with self._lock:
            rec = self._programs.get(name)
            if rec is None:
                rec = self._programs[name] = {
                    "compiles": 0, "compile_s": 0.0,
                    **{dst: 0 for _, dst in _MEM_FIELDS},
                    "peak_bytes_est": 0,
                    # cost_analysis columns (round 19): None until a
                    # backend reports them — None is "no data", never 0
                    "flops": None, "bytes_accessed": None}
            rec["compiles"] += 1
            rec["compile_s"] += float(compile_s)
            if mem is not None:
                # identical recompiles (fleet replicas) report identical
                # bytes — keep the max so a heterogeneous same-name
                # program surfaces its worst case
                for k, v in mem.items():
                    if v is None:
                        continue
                    if rec.get(k) is None:
                        rec[k] = v if k in ("flops", "bytes_accessed") \
                            else int(v)
                    else:
                        rec[k] = max(rec[k], v if k in
                                     ("flops", "bytes_accessed") else int(v))

    # ------------------------------------------------------------- reading
    def programs(self) -> dict[str, dict[str, Any]]:
        with self._lock:
            return {name: dict(rec)
                    for name, rec in sorted(self._programs.items())}

    def compile_total_s(self) -> float:
        with self._lock:
            return sum(rec["compile_s"] for rec in self._programs.values())

    def peak_hbm_bytes_est(self) -> int:
        """Per-run peak estimate: per-program peaks SUMMED — every
        program's buffers stay resident in a long-lived serving process
        (BASELINE.md "Memory/compile accounting" states the semantics
        and its bias vs measured HBM)."""
        with self._lock:
            return sum(rec["peak_bytes_est"]
                       for rec in self._programs.values())

    def manifest(self) -> dict[str, Any]:
        """JSON-ready ledger: the ``analyze programs`` input."""
        return {
            "schema_version": 1,
            "programs": self.programs(),
            "program_count": len(self._programs),
            "peak_hbm_bytes_est": self.peak_hbm_bytes_est(),
            "compile_total_s": self.compile_total_s(),
        }


def diff_manifests(current: dict[str, Any], baseline: dict[str, Any],
                   temp_threshold: float = 0.10,
                   flops_threshold: float = 0.10) -> list[dict[str, Any]]:
    """Program-set drift between two manifests (stdlib-only — analyze
    imports this logic's twin; kept here so library users gate in-process).

    Returns a list of findings; empty means no drift.  A finding is a
    program ADDED vs baseline, or one whose ``temp_bytes`` grew more than
    ``temp_threshold`` (relative; absolute growth when baseline is 0).
    FLOPs growth past ``flops_threshold`` WARNS (``severity: warn``) the
    way temp-bytes growth fails — more model work per call is worth a
    look but legitimate config changes move it, so it never exits the
    gate nonzero on its own; None columns (CPU backends) compare as "no
    data" and are skipped.  Removed programs are reported as
    informational (``severity: info``) — shrinking the program set never
    fails the gate."""
    cur = current.get("programs", {})
    base = baseline.get("programs", {})
    findings: list[dict[str, Any]] = []
    for name in sorted(cur):
        if name not in base:
            findings.append({
                "severity": "fail", "kind": "program_added", "name": name,
                "detail": f"program {name!r} not in baseline"})
            continue
        t_cur = int(cur[name].get("temp_bytes", 0))
        t_base = int(base[name].get("temp_bytes", 0))
        if t_base <= 0:
            grew = t_cur > 0
            rel = None
        else:
            rel = (t_cur - t_base) / t_base
            grew = rel > temp_threshold
        if grew:
            findings.append({
                "severity": "fail", "kind": "temp_bytes_grew", "name": name,
                "baseline": t_base, "current": t_cur, "relative": rel,
                "threshold": temp_threshold,
                "detail": (f"temp bytes {t_base} -> {t_cur} "
                           f"(threshold {temp_threshold:.0%})")})
        f_cur = cur[name].get("flops")
        f_base = base[name].get("flops")
        if f_cur is not None and f_base is not None and f_base > 0:
            f_rel = (float(f_cur) - float(f_base)) / float(f_base)
            if f_rel > flops_threshold:
                findings.append({
                    "severity": "warn", "kind": "flops_grew", "name": name,
                    "baseline": f_base, "current": f_cur,
                    "relative": f_rel, "threshold": flops_threshold,
                    "detail": (f"flops {f_base:.3g} -> {f_cur:.3g} "
                               f"(threshold {flops_threshold:.0%})")})
    for name in sorted(set(base) - set(cur)):
        findings.append({
            "severity": "info", "kind": "program_removed", "name": name,
            "detail": f"program {name!r} gone vs baseline"})
    return findings
