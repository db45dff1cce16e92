"""Device milliseconds per step in ops that gather (split_hot's
searchsorted of every slot among the hot ids, the owner lookups of
distribute and restore), from the trace and the step's HLO."""


def read(record: dict) -> float | None:
    t = record.get("trace")
    s = (t or {}).get("kinds", {}).get("gather")
    if not s or not record.get("steps"):
        return None
    return s / record["steps"] * 1e3
