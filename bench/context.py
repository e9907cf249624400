"""What a per-layer metric reader gets: the reduced trace of the window
and the counts of the work the window did."""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.trace import Trace, in_scope, opcode

#: named scopes of the DEPOSITUM update (Pallas kernels and jnp path)
UPDATE_SCOPES = ("fused_kernel", "local_step")
GOSSIP_SCOPE = "gossip"


@dataclasses.dataclass
class Context:
    trace: Trace
    rounds: int            # round-program executions in the traced window
    comm_period: int       # local steps per round
    tokens: int            # training tokens the traced window completed
    chips: int
    peaks: dict            # bench/peaks.json entry of the device kind
    model: dict            # the configuration's sizes (Cell.model)
    seq_len: int
    client_leaf_bytes: list   # bytes of each leaf of one client's weights
    clients_per_device: int

    @property
    def window_s(self) -> float:
        lo, hi = self.trace.window()
        return (hi - lo) * 1e-9

    def scoped_seconds(self, scopes) -> list[float]:
        """Per device: summed duration of leaf ops under any of ``scopes``
        inside the window."""
        lo, hi = self.trace.window()
        out = []
        for dev in self.trace.devices:
            total = 0.0
            for s, e, name, leaf in zip(dev.start, dev.end, dev.names,
                                        dev.leaf):
                if leaf and lo <= s < hi:
                    op = self.trace.scopes.get(name, "")
                    if any(in_scope(op, sc) for sc in scopes):
                        total += e - s
            out.append(total * 1e-9)
        return out

    def round_module(self, dev) -> str:
        """The program that took most device time in the window."""
        lo, hi = self.trace.window()
        total: dict = {}
        for name, s, e in dev.modules:
            if lo <= s < hi:
                total[name] = total.get(name, 0.0) + e - s
        return max(total, key=total.get) if total else ""

    def device_ops(self, top: int = 10) -> list:
        """[(label, seconds)] of the leaf ops that took most device time,
        averaged over devices; the label is the op's scope path and opcode."""
        lo, hi = self.trace.window()
        total: dict = {}
        for dev in self.trace.devices:
            for s, e, name, leaf in zip(dev.start, dev.end, dev.names,
                                        dev.leaf):
                if leaf and lo <= s < hi:
                    op = self.trace.scopes.get(name, "")
                    path = "/".join(p for p in op.split("/")[-3:] if p)
                    label = f"{opcode(name)} {path}".strip()
                    total[label] = total.get(label, 0.0) + (e - s) * 1e-9
        n = len(self.trace.devices)
        return sorted(((k, float(v / n)) for k, v in total.items()),
                      key=lambda kv: -kv[1])[:top]


def busy_seconds(ctx: Context) -> list[float]:
    from bench.trace import union_length

    lo, hi = ctx.trace.window()
    return [union_length(d.start, d.end, lo, hi) * 1e-9
            for d in ctx.trace.devices]


def idle_gaps_by_host_span(ctx: Context, top: int = 10) -> list:
    """[(label, seconds)] of device 0's longest idle gaps in the window,
    each named by the innermost benchmark host span over its middle."""
    from bench.trace import idle_gaps

    lo, hi = ctx.trace.window()
    dev = ctx.trace.devices[0]
    gaps = sorted(idle_gaps(dev.start, dev.end, lo, hi),
                  key=lambda g: g[0] - g[1])[:top]
    out = []
    for a, b in gaps:
        mid = 0.5 * (a + b)
        over = [s for s in ctx.trace.spans if s[1] <= mid < s[2]]
        name = min(over, key=lambda s: s[2] - s[1])[0] if over else "none"
        out.append((name, (b - a) * 1e-9))
    return out


def per_device_mean(values) -> float:
    return float(np.mean(values))
