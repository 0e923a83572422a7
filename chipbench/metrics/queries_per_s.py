"""Queries answered (HTTP 200) over the seconds from the window's opening
to the last answer: all the work the window sent, over all the time it
took.  (Clients stop sending when the window closes; the batches then in
flight count with the time they take, so no batch is cut at the edge.)"""


def read(rec: dict) -> float | None:
    done = [r["done"] for r in rec["requests"] if r["status"] == 200]
    return len(done) / (max(done) - rec["t0"]) if done else None
