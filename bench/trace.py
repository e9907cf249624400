"""Profiler trace of a window, reduced to what the per-layer metrics read.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  Each TPU device plane (``/device:TPU:<k>``) has an "XLA Ops"
line (one event per executed HLO instruction, control-flow containers
such as ``while`` enclosing their bodies' ops) and an "XLA Modules" line
(one event per program execution).  The host plane carries the
benchmark's own ``TraceAnnotation`` spans.  Host and device events share
one clock (nanoseconds from the start of the capture).

An op's layer comes from the ``op_name`` metadata of its instruction in
the compiled program's HLO text: the ``jax.named_scope`` path the program
put around it (``fused_kernel``, ``local_step``, ``gossip``).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
import tempfile

import numpy as np

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*.*?\s([\w\-]+)\(')
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_EVENT = re.compile(r'^%?([\w.\-]+)\s*=')


def scope_map(hlo_text: str) -> dict[str, str]:
    """instruction name -> op_name metadata (the named-scope path)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        meta = _OP_NAME.search(line)
        out[m.group(1)] = meta.group(1) if meta else ""
    return out


def instruction(event_name: str) -> str:
    """'%fusion.12 = f32[..] fusion(..)' -> 'fusion.12'."""
    m = _EVENT.match(event_name)
    return m.group(1) if m else event_name


def opcode(instr: str) -> str:
    """'collective-permute-start.3' -> 'collective-permute-start'."""
    return re.sub(r'\.\d+$', '', instr)


def in_scope(op_name: str, scope: str) -> bool:
    return f"/{scope}/" in f"/{op_name}/"


@dataclasses.dataclass
class DeviceTrace:
    # ops: (start_ns, end_ns) arrays and instruction names, sorted by start
    start: np.ndarray
    end: np.ndarray
    names: list
    leaf: np.ndarray      # False for control-flow containers of other ops
    modules: list         # (name, start_ns, end_ns) program executions


@dataclasses.dataclass
class Trace:
    devices: list         # DeviceTrace per device used
    spans: list           # (name, start_ns, end_ns) host spans "bench.*"
    scopes: dict          # instruction -> op_name

    def window(self) -> tuple[float, float]:
        w = [s for s in self.spans if s[0] == "bench.window"]
        if not w:
            raise ValueError("no bench.window span in the trace")
        return w[0][1], w[0][2]


def union_length(start, end, lo=-np.inf, hi=np.inf) -> float:
    """Length of the union of [start, end) intervals clipped to [lo, hi)."""
    s = np.clip(start, lo, hi)
    e = np.clip(end, lo, hi)
    keep = e > s
    s, e = s[keep], e[keep]
    if s.size == 0:
        return 0.0
    order = np.argsort(s, kind="stable")
    s, e = s[order], e[order]
    run_end = np.maximum.accumulate(e)
    # an interval opens a new run where it starts after every earlier end
    new = np.empty(s.size, bool)
    new[0] = True
    new[1:] = s[1:] > run_end[:-1]
    idx = np.flatnonzero(new)
    ends = np.append(run_end[idx[1:] - 1], run_end[-1])
    return float(np.sum(ends - s[idx]))


def idle_gaps(start, end, lo, hi):
    """Idle intervals [(a, b)] of a device inside [lo, hi)."""
    order = np.argsort(start, kind="stable")
    s, e = start[order], end[order]
    gaps, t = [], lo
    for a, b in zip(s, e):
        if b <= t:
            continue
        if a > t:
            gaps.append((t, min(a, hi)))
        t = max(t, b)
        if t >= hi:
            break
    if t < hi:
        gaps.append((t, hi))
    return [(a, b) for a, b in gaps if b > a]


def _leaf_mask(start, end) -> np.ndarray:
    """False for an event that encloses the event after it (a container)."""
    leaf = np.ones(start.size, bool)
    if start.size > 1:
        leaf[:-1] = ~((start[1:] >= start[:-1]) & (start[1:] < end[:-1]))
    return leaf


def read_xplane(path: str, n_devices: int, scopes: dict) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m and int(m.group(1)) < n_devices:
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                           for e in line.events]
                elif line.name == "XLA Modules":
                    mods = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
            ops.sort(key=lambda o: o[0])
            start = np.array([o[0] for o in ops], np.float64)
            end = np.array([o[1] for o in ops], np.float64)
            names = [instruction(o[2]) for o in ops]
            devices[int(m.group(1))] = DeviceTrace(
                start, end, names, _leaf_mask(start, end), sorted(
                    mods, key=lambda x: x[1]))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    if sorted(devices) != list(range(n_devices)):
        raise ValueError(f"trace has device planes {sorted(devices)}, "
                         f"expected {n_devices}")
    return Trace([devices[k] for k in range(n_devices)], spans, scopes)


class Capture:
    """``with Capture() as cap:`` profiles the block into a temporary
    directory outside the checkout; ``cap.reduce(...)`` reads it and
    deletes the files."""

    def __enter__(self):
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        import jax

        jax.profiler.stop_trace()
        return False

    def reduce(self, n_devices: int, scopes: dict) -> Trace:
        try:
            files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if len(files) != 1:
                raise ValueError(f"expected one xplane file, found {files}")
            return read_xplane(files[0], n_devices, scopes)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
