"""Reduction of a JAX profiler trace (``.xplane.pb``) to device time.

What a TPU trace holds (``jax.profiler.ProfileData``): a plane
``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one event per
program execution, named ``jit_<fn>(<fingerprint>)``) and ``XLA Ops`` (one
event per HLO instruction, named by its text ``%<name>.<k> = ...``; a
``while`` loop's event spans the ops of its body, which are listed too), and
a plane ``/host:CPU`` with one line per host thread. Times are nanoseconds on
one clock for host and device.

Both step programs of the serving engine are jitted functions named ``fn``,
so a program is classified by the kernels inside it: one that runs the paged
decode attention kernel is a decode step, one that runs the chunk attention
kernel a prefill step. Kernels are found by the names of their Pallas calls.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

# Pallas kernels by the HLO name of their call (the wrapper's name).
KERNELS = {
    "attn_decode": re.compile(r"paged_decode_attention"),
    "attn_chunk": re.compile(r"paged_chunk_attention"),
    "ffn": re.compile(r"twell_gate_matmul|twell_fused_ffn|twell_down_proj"
                      r"|twell_pack|tile_skip_ffn"),
}
PROGRAM_KINDS = (("decode", "attn_decode"), ("prefill", "attn_chunk"))
CONTAINERS = ("while", "conditional", "call")


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] ...`` -> ``fusion``."""
    head = event_name.split("=", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", head)


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: int        # ns
    dur: int          # ns

    @property
    def end(self) -> int:
        return self.start + self.dur


@dataclasses.dataclass
class Program:
    name: str
    start: int
    dur: int
    kind: str = "other"


def union_ns(intervals: Iterable[Tuple[int, int]]) -> Tuple[int, List]:
    """(covered nanoseconds, merged intervals) of (start, end) pairs."""
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def classify(programs: List[Program], ops: Sequence[Event]) -> None:
    """Set each program's kind from the kernels that ran inside it."""
    marks = {kind: sorted(o.start for o in ops if KERNELS[k].search(o.name))
             for kind, k in PROGRAM_KINDS}
    for p in programs:
        for kind, starts in marks.items():
            i = bisect.bisect_left(starts, p.start)
            if i < len(starts) and starts[i] < p.start + p.dur:
                p.kind = kind
                break


@dataclasses.dataclass
class Trace:
    """Device activity of one chip inside a window ``[lo, hi)`` (ns)."""
    lo: int
    hi: int
    ops: List[Event]
    programs: List[Program]
    host_spans: List[Event] = dataclasses.field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def busy_intervals(self):
        return union_ns((max(o.start, self.lo), min(o.end, self.hi))
                        for o in self.ops if o.end > self.lo
                        and o.start < self.hi)

    @property
    def busy_s(self) -> float:
        return self.busy_intervals()[0] / 1e9

    def kernel_s(self, kernel: str, program_kind: Optional[str] = None
                 ) -> float:
        """Summed device time of a kernel's events (optionally only those
        inside programs of one kind)."""
        pat = KERNELS[kernel]
        spans = [(p.start, p.start + p.dur) for p in self.programs
                 if program_kind is None or p.kind == program_kind]
        spans.sort()
        starts = [s for s, _ in spans]
        total = 0
        for o in self.ops:
            if not pat.search(o.name):
                continue
            i = bisect.bisect_right(starts, o.start) - 1
            if i >= 0 and o.start < spans[i][1]:
                total += o.dur
        return total / 1e9

    def program_s(self, kind: str) -> float:
        """Device seconds of the programs of one kind, clipped to the
        window."""
        return sum(min(p.start + p.dur, self.hi) - max(p.start, self.lo)
                   for p in self.programs if p.kind == kind) / 1e9

    def program_count(self, kind: str) -> int:
        return sum(1 for p in self.programs if p.kind == kind)

    def top_ops(self, n: int = 10) -> List[List]:
        """The device operations that took most time, by HLO name (loops
        that only contain other ops left out)."""
        acc: Dict[str, int] = {}
        for o in self.ops:
            name = op_name(o.name)
            if name in CONTAINERS:
                continue
            acc[name] = acc.get(name, 0) + o.dur
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9] for k, v in top]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """The longest gaps in device activity, each named by the host span
        that overlaps it most (``host:none`` when no span does)."""
        _, merged = self.busy_intervals()
        edges = [self.lo] + [x for iv in merged for x in iv] + [self.hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for a, b in gaps[:n]:
            best, best_ov = "host:none", 0
            for h in self.host_spans:
                ov = min(b, h.end) - max(a, h.start)
                if ov > best_ov:
                    best, best_ov = h.name, ov
            out.append([best, (b - a) / 1e9])
        return out

    def breakdown(self) -> Dict[str, List[List]]:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def read_xplane(path: str, window_span: str, device: int = 0):
    """(device ops, device programs, (lo, hi)) from an ``.xplane.pb``; the
    window is the host span named ``window_span``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    lo = hi = None
    ops: List[Event] = []
    progs: List[Program] = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == window_span:
                        lo, hi = int(e.start_ns), int(e.start_ns + e.duration_ns)
        elif plane.name == f"/device:TPU:{device}":
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [Event(e.name, int(e.start_ns), int(e.duration_ns))
                           for e in line.events]
                elif line.name == "XLA Modules":
                    progs = [Program(e.name, int(e.start_ns),
                                     int(e.duration_ns))
                             for e in line.events]
    if lo is None:
        raise ValueError(f"no host span {window_span!r} in {path}")
    return ops, progs, (lo, hi)


def load(trace_dir: str, window_span: str) -> Trace:
    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    ops, progs, (lo, hi) = read_xplane(sorted(paths)[-1], window_span)
    keep = lambda e: e.start < hi and e.start + e.dur > lo
    ops = [o for o in ops if keep(o)]
    progs = [p for p in progs if keep(p)]
    classify(progs, ops)
    return Trace(lo=lo, hi=hi, ops=ops, programs=progs)
