"""Where the Pallas kernels run: compiled for the TPU, or interpreted.

Every kernel wrapper in this package takes ``interpret: bool | None`` and
resolves it here, so the decision has one owner.  ``None`` (what every
caller on the query path passes) means compiled Mosaic on a TPU backend
and the Pallas interpreter everywhere else (the CPU test suite).  Asking
for the interpreter on a TPU backend is refused: there the kernels always
run compiled.  ``interpret=False`` off the TPU is allowed, because that is
how a kernel is compiled ahead of time for a described TPU topology.
"""

from __future__ import annotations


def resolve_interpret(interpret: bool | None = None) -> bool:
    """The ``interpret=`` flag a ``pallas_call`` gets."""
    import jax
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("interpret-mode Pallas on a TPU backend: the "
                         "kernels run compiled there (pass interpret=None)")
    return bool(interpret)
