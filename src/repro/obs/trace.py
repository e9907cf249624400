"""Tracing and timing hooks: scopes name device ops, spans name host time.

* :func:`annotate` — a ``jax.named_scope`` around a DEPOSITUM phase
  (forward/backward pass, local step, gossip collective, compression
  pack/unpack, fused-kernel launch, telemetry).  The scope lands in the
  ``op_name`` metadata of every HLO instruction the phase lowers to, which
  is how a device profile names its ops.  Trace-time metadata only: it
  emits no ops and cannot change numerics or trigger retraces.
* :func:`span` — a ``jax.profiler.TraceAnnotation`` around host work (the
  trainer loop's ``trainer.*`` spans).  A span is recorded only while a
  profiler capture is active, on the capture's clock, so it lines up with
  the device ops it dispatched or waited for.
* :func:`time_fn` — wall-clock timing that separates **blocked** time
  (``block_until_ready`` per call — the honest number) from **dispatch**
  time (enqueue only — async queue cost), returned as a :class:`Timing`.
* :func:`profile_capture` — opt-in ``jax.profiler.trace`` capture around a
  block, written to a TensorBoard-readable directory.  Gated by an
  explicit flag (or ``REPRO_PROFILE_DIR``) because captures are large.
"""
from __future__ import annotations

import contextlib
import os
import time
from typing import Callable, NamedTuple, Optional

import jax

#: DEPOSITUM phase names, the only scopes :func:`annotate` puts on device
#: ops: one vocabulary so profiles from different backends line up.  An op
#: belongs to at most one phase, except compression pack/unpack, which the
#: packed wire path runs inside ``gossip``.
PHASES = ("fwd_bwd", "local_step", "gossip", "compress_pack",
          "compress_unpack", "fused_kernel", "telemetry")


def annotate(name: str):
    """Name the device ops of one phase: ``jax.named_scope(name)``.

    Safe inside jit/vmap/scan tracing (metadata only).  ``name`` must be
    one of :data:`PHASES`.
    """
    if name not in PHASES:
        raise ValueError(f"unknown phase {name!r}; phases are {PHASES}")
    return jax.named_scope(name)


def span(name: str, **args):
    """Name a stretch of host time: ``jax.profiler.TraceAnnotation``.

    ``args`` ride along as the event's arguments (e.g. ``round=3``).
    Outside a profiler capture it records nothing.
    """
    return jax.profiler.TraceAnnotation(name, **args)


class Timing(NamedTuple):
    """Per-iteration wall times in microseconds."""

    blocked_us: float   # block_until_ready every iteration — the honest one
    dispatch_us: float  # issue-only loop, one final block (async queue cost)


def time_fn(fn: Callable, *args, iters: int = 20, warmup: int = 3
            ) -> Timing:
    """Time ``fn(*args)``: blocked per-iteration, then dispatch-only.

    The measurement previously private to ``benchmarks/kernel_bench._time``
    — warm up, block every iteration for the honest wall time, then an
    issue-only loop with a single trailing block for the async queue cost.
    """
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    blocked = (time.perf_counter() - t0) / iters * 1e6
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = fn(*args)
    dispatch = (time.perf_counter() - t0) / iters * 1e6
    jax.block_until_ready(out)  # drain before the next measurement starts
    return Timing(blocked, dispatch)


@contextlib.contextmanager
def profile_capture(log_dir: Optional[str] = None, *,
                    enabled: Optional[bool] = None):
    """Opt-in ``jax.profiler.trace`` capture around a block.

    Enabled when ``enabled=True``, or when ``enabled`` is None and the
    ``REPRO_PROFILE_DIR`` env var is set (its value is the default
    ``log_dir``).  Disabled, it is a no-op context — callers wrap their
    run loop unconditionally and flip the flag.
    """
    env_dir = os.environ.get("REPRO_PROFILE_DIR")
    if enabled is None:
        enabled = env_dir is not None
    if not enabled:
        yield None
        return
    target = log_dir or env_dir or "profile"
    os.makedirs(target, exist_ok=True)
    with jax.profiler.trace(target):
        yield target
