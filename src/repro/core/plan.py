"""Execution plans: where a batched query runs.

An :class:`ExecutionPlan` names the whole pipeline:

* ``"cpu"``    — the NumPy reference path (exact host sketching, one host
  ``searchsorted`` over the fused arena, vectorized grouped sweep).  This
  is the bit-parity oracle every other plan is gated against.
* ``"device"`` — the device-resident path (:mod:`repro.core.device_plan`):
  the arena stays resident on the accelerator across batches, the probe
  binary search (jitted XLA) and the small-group sweep (a Pallas kernel)
  run on the device, and only final block extents return to host.
  Sketching stays on the exact host path by default so the plan is
  bit-identical to ``"cpu"`` by construction; pin
  ``sketch_backend="pallas"`` to move the (f32) ICWS sketch onto the
  device too.
* ``"auto"``   — resolve once per batch: ``"device"`` when a real
  accelerator backs jax, else silently ``"cpu"``.

How each store level is probed and grouped under a plan is decided in
one place, ``repro.core.query._probe_level``.  A plan is resolved from
:class:`repro.core.results.QueryOptions` via :func:`resolve_plan` — once
per batch, never per query.  ``sketch_backend`` and ``fanout`` left
``None`` take the defaults; a value outside their choices is a
``TypeError`` rather than a silent fallback.
"""

from __future__ import annotations

from dataclasses import dataclass

__all__ = ["ExecutionPlan", "resolve_plan", "plan_names",
           "device_preferred"]

_PLANS = ("cpu", "device")

#: the QueryOptions fields a plan resolves: default first, then the rest
#: of the values every plan can run
_CHOICES = {
    # exact host sketching keeps plan="device" bit-identical to plan="cpu";
    # sketch_backend="pallas" pins the f32 on-device ICWS sketch instead
    "sketch_backend": ("exact", "pallas"),
    "fanout": ("threaded", "serial"),
}


@dataclass(frozen=True)
class ExecutionPlan:
    """A fully resolved pipeline: ``name`` is the resolved plan ("auto"
    never survives resolution), the other fields the concrete values the
    query engine dispatches on."""

    name: str
    sketch_backend: str
    fanout: str

    @property
    def device(self) -> bool:
        return self.name == "device"


def plan_names() -> list[str]:
    return sorted(_PLANS) + ["auto"]


def device_preferred() -> bool:
    """Capability check for ``plan="auto"``: is a real accelerator backing
    jax?  Interpret-mode Pallas on CPU is correct but slower than NumPy,
    so auto only picks the device plan when the hardware pays for it.  A
    backend that fails to initialise raises here: it is an error to fix,
    not a reason to serve on the cpu."""
    import jax
    return jax.default_backend() in ("tpu", "gpu")


def resolve_plan(options=None, *, capabilities: dict | None = None
                 ) -> ExecutionPlan:
    """Resolve options (or a bare plan name) into an :class:`ExecutionPlan`.

    Called once per batch by the query pipeline.  ``capabilities``
    overrides the device check (``{"device": False}`` forces the auto
    downgrade; tests use it).  ``"auto"`` silently resolves to
    ``"device"`` only when that check passes, else to ``"cpu"``; an
    *explicitly* requested plan is honored regardless (on CPU it runs the
    kernels in interpret mode — the parity-gating configuration CI
    exercises).
    """
    if options is None or isinstance(options, str):
        name, pins = options or "cpu", {}
    else:
        name = getattr(options, "plan", "cpu") or "cpu"
        pins = {f: getattr(options, f) for f in _CHOICES
                if getattr(options, f, None) is not None}
    if name == "auto":
        capable = (capabilities or {}).get("device")
        if capable is None:
            capable = device_preferred()
        name = "device" if capable else "cpu"
    if name not in _PLANS:
        raise ValueError(f"unknown execution plan {name!r}; "
                         f"known plans: {plan_names()}")
    stages = {f: choices[0] for f, choices in _CHOICES.items()}
    for f, v in pins.items():
        if v not in _CHOICES[f]:
            raise TypeError(
                f"plan {name!r} cannot execute {f}={v!r} (valid values: "
                f"{sorted(_CHOICES[f])}); an unknown stage value is an "
                "error, not a fallback")
        stages[f] = v
    return ExecutionPlan(name=name, **stages)
