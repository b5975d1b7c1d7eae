"""Transport calibration: measured cost of keeping a device replica in sync.

Some deployments put the accelerator behind a *taxed* transport — a
proxied device where executing a jitted update step costs the HOST tens of
CPU-ms per uploaded MB (protocol serialization on the dispatch path),
stealing the very core the operator's native kernels run on.  On such
links, per-record device syncs cost more host CPU than the entire rest of
the pipeline.  What a dispatch costs on a directly attached chip is
unmeasured — ROADMAP A1; the CPU backend, where the "device" is the host
itself, calibrates too.

Operators that can run host-authoritative (the window operator's host emit
tier, ``operators/window_agg.py``) consult this module to pick a device
sync cadence: per-record ``scatter`` on healthy links, ``deferred``
(replica refreshed at sync points — barriers, idle, end of input) on taxed
ones.  This is the ingress-side twin of the host emit tier, which exists
to avoid fire-time downloads (PARITY.md "emit tier").

Calibration is *self-measured*, not synthetic: a plain blocking
``device_put`` does not expose the tax (the cost is in executing
dispatched computations, not in moving raw buffers), so the operator
records the until-ready wall time of its own first few real update steps
via :func:`record_dispatch_cost` and this module aggregates the verdict
process-wide (the link does not change under a running process — later
operators skip the probe entirely).
"""

from __future__ import annotations

from typing import List, Optional, Tuple

#: dispatch cost above this marks the link taxed (unmeasured on a directly
#: attached chip — ROADMAP A1).  The CPU
#: backend calibrates too: there the "transport" is the XLA dispatch
#: compute itself (a CPU scatter costs ~0.5µs/update regardless of state
#: size), which on slow hosts measures far past this threshold — exactly
#: the boxes where per-batch replica sync loses to the deferred refresh.
DISPATCH_TAXED_ABOVE_MS_PER_MB = 6.0

#: samples needed before a verdict; the MIN per-MB cost is used, so the
#: first sample's compile time and queue-drain noise cannot tip the scale
MIN_SAMPLES = 3

#: samples below this upload size are discarded: a healthy link's FIXED
#: dispatch latency (~0.2-1 ms) divided by a sub-MB payload reads as a
#: huge per-MB cost and would freeze a false "taxed" verdict process-wide.
#: Tiny-batch workloads therefore never calibrate and keep the safe
#: default (per-batch scatter).
MIN_SAMPLE_MB = 0.5

_samples: List[Tuple[float, float]] = []  # (mb, seconds)
_verdict: Optional[bool] = None


def record_dispatch_cost(mb: float, seconds: float) -> None:
    """Feed one measured (uploaded MB, until-ready seconds) sample from a
    real dispatched update step.  Sub-``MIN_SAMPLE_MB`` samples are ignored
    (fixed dispatch latency would masquerade as per-MB cost)."""
    global _verdict
    if mb < MIN_SAMPLE_MB:
        return
    _samples.append((mb, seconds))
    if _verdict is None and len(_samples) >= MIN_SAMPLES:
        best = min(s / m for m, s in _samples)
        _verdict = best * 1e3 > DISPATCH_TAXED_ABOVE_MS_PER_MB


def dispatch_taxed() -> Optional[bool]:
    """True/False once calibrated; None while samples are still needed."""
    return _verdict


def dispatch_ms_per_mb() -> Optional[float]:
    """Best measured dispatch cost in ms per uploaded MB (None = unmeasured)."""
    if not _samples:
        return None
    return min(s / m for m, s in _samples) * 1e3


def reset(verdict: Optional[bool] = None) -> None:
    """Clear calibration state (tests), optionally pinning a verdict."""
    global _samples, _verdict
    _samples = []
    _verdict = verdict
