"""Device milliseconds per step in scatter operations (the combiner's
and the owner's scatter-adds of gradients), from the trace."""


def read(record: dict) -> float | None:
    t = record.get("trace")
    s = (t or {}).get("kinds", {}).get("scatter")
    if not s or not record.get("steps"):
        return None
    return s / record["steps"] * 1e3
