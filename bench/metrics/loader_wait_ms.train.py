"""Milliseconds per step the training loop waited in `next()` on the
`ShardedLoader` (the benchmark's span around the call)."""


def read(record: dict) -> float | None:
    span = record.get("spans", {}).get("bench.loader_next")
    if not span or not record.get("steps"):
        return None
    return span["s"] / record["steps"] * 1e3
