"""Execution plans: where each stage of a batched query runs.

PR 3-9 grew the query path one knob at a time — ``sketch_backend=``,
``probe_backend=``, ``sweep=``, ``fanout=`` — until picking "run on the
accelerator" meant knowing four stage-level spellings.  An
:class:`ExecutionPlan` names the whole pipeline instead:

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

A plan is resolved from :class:`repro.core.results.QueryOptions` via
:func:`resolve_plan` — once per batch, never per query.  Stage fields left
``None`` take the plan's defaults; a non-``None`` stage field *pins* that
stage (the debugging escape hatch), and pinning a stage to a value the
plan cannot execute is a ``TypeError`` rather than a silent fallback.
"""

from __future__ import annotations

from dataclasses import dataclass

__all__ = ["ExecutionPlan", "resolve_plan", "register_plan",
           "plan_names", "device_preferred"]

#: the QueryOptions stage fields a plan resolves (in pin order)
STAGE_FIELDS = ("sketch_backend", "probe_backend", "sweep", "fanout")


@dataclass(frozen=True)
class ExecutionPlan:
    """A fully resolved pipeline: concrete backend per stage.

    ``name`` is the resolved plan ("auto" never survives resolution), the
    stage fields are the concrete values the query engine dispatches on.
    """

    name: str
    sketch_backend: str
    probe_backend: str
    sweep: str
    fanout: str

    @property
    def fused(self) -> bool:
        """True when probe and sweep both run device-side, enabling the
        fused pipeline (device gather, no per-stage host round-trip)."""
        return self.probe_backend == "device" and self.sweep == "device"


@dataclass(frozen=True)
class _PlanSpec:
    defaults: dict           # stage field -> default backend
    choices: dict            # stage field -> the values this plan can run
    requires_device: bool    # "auto" only picks it on a real accelerator


_PLANS: dict[str, _PlanSpec] = {}


def register_plan(name: str, *, defaults: dict, choices: dict,
                  requires_device: bool = False) -> None:
    """Register an execution plan.  ``defaults`` must name every stage
    field; ``choices`` lists the stage values the plan can execute."""
    missing = [f for f in STAGE_FIELDS if f not in defaults]
    if missing:
        raise ValueError(f"plan {name!r} defaults missing stages {missing}")
    _PLANS[name] = _PlanSpec(defaults=dict(defaults),
                             choices={f: frozenset(choices.get(f, ()))
                                      for f in STAGE_FIELDS},
                             requires_device=requires_device)


def plan_names() -> list[str]:
    return sorted(_PLANS) + ["auto"]


register_plan("cpu", defaults={
    "sketch_backend": "exact", "probe_backend": "numpy",
    "sweep": "grouped", "fanout": "threaded",
}, choices={
    "sketch_backend": ("exact", "pallas"),
    "probe_backend": ("numpy", "pallas", "percoord"),
    "sweep": ("grouped", "loop"),
    "fanout": ("threaded", "serial"),
})

register_plan("device", defaults={
    # exact host sketching keeps plan="device" bit-identical to plan="cpu";
    # sketch_backend="pallas" pins the f32 on-device ICWS sketch instead
    "sketch_backend": "exact", "probe_backend": "device",
    "sweep": "device", "fanout": "threaded",
}, choices={
    "sketch_backend": ("exact", "pallas"),
    "probe_backend": ("device", "numpy", "pallas", "percoord"),
    "sweep": ("device", "grouped", "loop"),
    "fanout": ("threaded", "serial"),
}, requires_device=True)


def device_preferred() -> bool:
    """Capability check for ``plan="auto"``: is a real accelerator backing
    jax?  Interpret-mode Pallas on CPU is correct but slower than NumPy,
    so auto only picks the device plan when the hardware pays for it.  A
    backend that fails to initialise raises here: it is an error to fix,
    not a reason to serve on the cpu."""
    import jax
    return jax.default_backend() in ("tpu", "gpu")


def _capable(name: str, capabilities: dict | None) -> bool:
    if capabilities is not None and name in capabilities:
        return bool(capabilities[name])
    spec = _PLANS.get(name)
    if spec is None:
        return False
    return device_preferred() if spec.requires_device else True


def resolve_plan(options=None, *, capabilities: dict | None = None
                 ) -> ExecutionPlan:
    """Resolve options (or a bare plan name) into an :class:`ExecutionPlan`.

    Called once per batch by every query entry point.  ``capabilities``
    overrides the availability checks per plan name (``{"device": False}``
    forces the auto downgrade; tests and the batcher's capability cache
    use it).  ``"auto"`` silently resolves to ``"device"`` only when that
    plan's capability check passes, else to ``"cpu"``; an *explicitly*
    requested plan is honored regardless (on CPU it runs the kernels in
    interpret mode — the parity-gating configuration CI exercises).
    """
    if options is None:
        name, pins = "cpu", {}
    elif isinstance(options, str):
        name, pins = options, {}
    else:
        name = getattr(options, "plan", "cpu") or "cpu"
        pins = {f: getattr(options, f) for f in STAGE_FIELDS
                if getattr(options, f, None) is not None}
    if name == "auto":
        name = "device" if _capable("device", capabilities) else "cpu"
    spec = _PLANS.get(name)
    if spec is None:
        raise ValueError(f"unknown execution plan {name!r}; "
                         f"registered plans: {plan_names()}")
    stages = dict(spec.defaults)
    for f, v in pins.items():
        if v not in spec.choices[f]:
            raise TypeError(
                f"plan {name!r} cannot execute {f}={v!r} (valid pins: "
                f"{sorted(spec.choices[f])}); pinning a stage beyond what "
                "the plan supports is an error, not a fallback")
        stages[f] = v
    return ExecutionPlan(name=name, **stages)
