"""Per cent of the training window in which no operation ran on the
device: 1 - (union of op intervals) / window, from the trace."""


def read(record: dict) -> float | None:
    t = record.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
