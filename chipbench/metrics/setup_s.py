"""Set-up seconds: process start to the window's first request (loading,
upload, warm-up, and a store build when the checkout lacks one)."""


def read(rec: dict) -> float:
    return rec["setup_s"]
