"""Device milliseconds per step under the `dpmr.map` scope: the map body
(`ops.sigmoid_grad`) and the gradient's scale, from the trace and the
step's HLO."""
from bench import program_trace


def read(record: dict) -> float | None:
    return program_trace.stage_ms(record, "map")
