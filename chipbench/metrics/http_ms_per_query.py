"""Front-end milliseconds per query on the event loop: parsing and
tokenising the request (span ``serve.parse``), and the response's
``to_dict`` and JSON encoding (span ``serve.respond``)."""

from chipbench.window import ms_per_query


def read(rec: dict) -> float | None:
    return ms_per_query(rec, "serve.parse", "serve.respond")
