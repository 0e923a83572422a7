"""Backend compiles during the window (JAX's monitoring hook)."""


def read(rec: dict) -> int:
    return rec["compiles"]
