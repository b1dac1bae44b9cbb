"""Per-program performance attribution: the ``ProgramProfile`` registry.

Every jitted hot program — the train step, the exchange RS/AG halves, the
prefill chunk, the decode step — gets one :class:`ProgramProfile` that
joins two sources:

- **compile-time cost**: ``jitted.lower(*args).cost_analysis()`` — the
  per-device flops / HBM-bytes estimate XLA computes *without* building an
  executable (cheap: it reuses the jit trace cache and never
  compiles). Collective bytes come from the caller's analytic
  wire accounting (``exchanger.wire_summary`` / ``Engine.wire``) because
  the pre-optimization StableHLO text has no compiled-HLO collectives to
  parse — same modeling discipline as ``exchange/bytes_wire``.
- **measured durations**: the instrument sites (train loop, serve engine,
  exchange-half micro-timer) feed per-call wall times via
  :func:`observe` — the join contract is *name equality* with the span
  that times the program (``train/step``, ``serve/decode_step``, ...).

The join emits achieved-FLOPs / achieved-bandwidth / MFU gauges against
:func:`repro.roofline.analysis.peaks` (keyed by ``device_kind``; a device
with no known peak gets no ratio gauge), so
"decode runs at 9% of the memory roofline" is a metric in every
``--metrics-out`` dump, not a bench-day observation.

Host-side only: nothing here adds an op to a jitted program — ``lower()``
reuses the trace the first dispatch created (or primes the cache for it),
and :func:`instrument` wraps *dispatch*, never the program. Gated by the
telemetry switch plus ``REPRO_TELEMETRY_PROFILE=0`` (profile-only off);
capture failures increment ``profile/capture_errors`` and never break the
caller.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

from repro.telemetry import _runtime, metrics, trace

_profiles: dict = {}


def enabled() -> bool:
    return _runtime._state.enabled and _runtime._state.config.profile


def _slug(name: str) -> str:
    return name.replace("/", "_")


@dataclass
class ProgramProfile:
    """Cost + measured-duration attribution for one jitted program."""
    name: str
    flops: float = 0.0           # per-device, from cost_analysis
    hbm_bytes: float = 0.0       # per-device, pre-optimization estimate
    coll_bytes: float = 0.0      # per-rank analytic wire bytes (caller)
    calls: int = 0
    total_time_s: float = 0.0
    compile_time_s: float = 0.0
    capture_time_s: float = 0.0
    captured: bool = False
    meta: dict = field(default_factory=dict)

    @property
    def mean_time_s(self) -> float:
        return self.total_time_s / self.calls if self.calls else 0.0

    @property
    def achieved_flops_s(self) -> float:
        m = self.mean_time_s
        return self.flops / m if m > 0 else 0.0

    @property
    def achieved_hbm_bw(self) -> float:
        m = self.mean_time_s
        return self.hbm_bytes / m if m > 0 else 0.0

    @property
    def achieved_coll_bw(self) -> float:
        m = self.mean_time_s
        return self.coll_bytes / m if m > 0 else 0.0

    def roofline(self) -> dict:
        """Ratios vs the device's peaks (``roofline.analysis.peaks``), and
        the roofline bound time over the terms whose peak is known. Empty
        on a device with no known peak."""
        from repro.roofline.analysis import peaks
        pk = peaks()
        terms, out = {}, {}
        for term, key, amount, ratio, achieved in (
                ("compute", "flops", self.flops, "mfu",
                 self.achieved_flops_s),
                ("memory", "hbm_bw", self.hbm_bytes, "hbm_frac",
                 self.achieved_hbm_bw),
                ("collective", "ici_bw", self.coll_bytes, "coll_frac",
                 self.achieved_coll_bw)):
            if key in pk:
                terms[term] = amount / pk[key]
                out[ratio] = achieved / pk[key]
        if terms:
            out["t_roofline_s"] = max(terms.values())
            out["bound"] = max(terms, key=terms.get)
        return out

    def gauges(self) -> dict:
        """The metric names/values this profile exports (flat
        ``profile/<program>/<quantity>`` namespace)."""
        s = _slug(self.name)
        out = {}
        if self.captured:
            out[f"profile/{s}/flops"] = self.flops
            out[f"profile/{s}/hbm_bytes"] = self.hbm_bytes
            out[f"profile/{s}/coll_bytes"] = self.coll_bytes
        if self.calls:
            out[f"profile/{s}/calls"] = float(self.calls)
            out[f"profile/{s}/mean_time_s"] = self.mean_time_s
        if self.captured and self.calls:
            rl = self.roofline()
            out[f"profile/{s}/achieved_flops_s"] = self.achieved_flops_s
            out[f"profile/{s}/achieved_hbm_bw"] = self.achieved_hbm_bw
            for ratio in ("mfu", "hbm_frac"):
                if ratio in rl:
                    out[f"profile/{s}/{ratio}"] = rl[ratio]
            if self.coll_bytes:
                out[f"profile/{s}/achieved_coll_bw"] = self.achieved_coll_bw
                if "coll_frac" in rl:
                    out[f"profile/{s}/coll_frac"] = rl["coll_frac"]
        return out


def _get(name: str) -> ProgramProfile:
    p = _profiles.get(name)
    if p is None:
        p = _profiles[name] = ProgramProfile(name)
    return p


def get(name: str) -> ProgramProfile | None:
    return _profiles.get(name)


def programs() -> dict:
    return dict(_profiles)


def reset() -> None:
    _profiles.clear()


def capture(name: str, jfn, *args, coll_bytes: float = 0.0,
            **kwargs) -> ProgramProfile | None:
    """Record compile-time cost analysis for ``jfn`` called with ``args``.

    Uses the AOT ``lower()`` path *without* ``compile()`` — the lowered
    cost analysis shares the jit trace cache (no retrace when
    the program already dispatched, and the trace is reused when it
    dispatches later) while an AOT ``compile()`` would pay a full second
    XLA compile. Never raises: failures count in
    ``profile/capture_errors``."""
    if not enabled():
        return None
    prof = _get(name)
    t0 = time.perf_counter()
    try:
        lowered = jfn.lower(*args, **kwargs)
        ca = lowered.cost_analysis() or {}
        prof.flops = float(ca.get("flops", 0.0))
        prof.hbm_bytes = float(ca.get("bytes accessed", 0.0))
    except Exception as e:  # noqa: BLE001 — attribution must never break a run
        metrics.counter("profile/capture_errors").inc()
        prof.meta["capture_error"] = f"{type(e).__name__}: {e}"
        return None
    prof.coll_bytes = float(coll_bytes or 0.0)
    prof.capture_time_s = time.perf_counter() - t0
    prof.captured = True
    return prof


def observe(name: str, seconds: float) -> None:
    """Join one measured call duration into the program's profile."""
    if not enabled():
        return
    prof = _get(name)
    prof.calls += 1
    prof.total_time_s += float(seconds)


def compile_time(name: str, seconds: float) -> None:
    """Record a program's first-call (compile + first execution) wall time
    as a ``compile/*`` gauge — the per-program view TrainReport's single
    ``compile_time`` scalar can't give."""
    if not enabled():
        return
    _get(name).compile_time_s = float(seconds)
    metrics.gauge(f"compile/{_slug(name)}_s").set(float(seconds))


def instrument(name: str, jfn, *, coll_bytes: float = 0.0):
    """Wrap a jitted callable with first-call attribution: cost capture
    (before the call — donated buffers are still alive), then a blocked
    timing of the compile + first execution. Later calls pass through
    untouched; disabled telemetry passes through from call zero. The
    wrapped program itself is never altered (byte-identical on/off)."""
    state = {"first": True}

    def wrapped(*args, **kwargs):
        if state["first"] and enabled():
            state["first"] = False
            import jax
            with trace.span("profile/capture", program=name):
                capture(name, jfn, *args, coll_bytes=coll_bytes, **kwargs)
            t0 = time.perf_counter()
            out = jfn(*args, **kwargs)
            jax.block_until_ready(out)
            compile_time(name, time.perf_counter() - t0)
            return out
        return jfn(*args, **kwargs)

    wrapped.jitted = jfn    # introspection: the unwrapped program
    wrapped.program_name = name
    return wrapped


def emit(registry=None) -> None:
    """Write every profile's gauges into ``registry`` (default: the
    process-wide registry) so flush/dump picks them up."""
    if not enabled():
        return
    if registry is None:
        registry = _runtime.default_registry()
    for prof in _profiles.values():
        for gname, v in prof.gauges().items():
            registry.gauge(gname).set(v)
