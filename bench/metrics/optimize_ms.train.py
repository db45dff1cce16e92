"""Device milliseconds per step under the `dpmr.optimize` scope: the
learning-rate schedule and the optimizer's update of the owner block and
the hot table, from the trace and the step's HLO."""
from bench import program_trace


def read(record: dict) -> float | None:
    return program_trace.stage_ms(record, "optimize")
