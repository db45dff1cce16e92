"""Device milliseconds per step in sort operations (route_build's
argsort of the batch's slots by feature id), from the trace."""


def read(record: dict) -> float | None:
    t = record.get("trace")
    s = (t or {}).get("kinds", {}).get("sort")
    if not s or not record.get("steps"):
        return None
    return s / record["steps"] * 1e3
