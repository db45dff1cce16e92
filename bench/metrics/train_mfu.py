"""Per cent of the chips' HBM bandwidth that the bytes the model requires
would use over the window: bench/work.py counts them from each step's
batch, whatever the implementation moves. Sparse LR's FLOPs are
negligible, so bandwidth is the peak that bounds the step."""


def read(record: dict) -> float | None:
    if not record.get("work_bytes") or not record.get("trace"):
        return None
    chips = record["trace"]["devices"]
    peak = record["peaks"]["hbm_bytes_per_s"] * chips
    return 100.0 * record["work_bytes"] / (peak * record["window_s"])
