"""Device milliseconds per step under the `dpmr.reduce` scope: the
strategy's reduce (combine_grads, the exchange, owner_accumulate), the
hot-table scatter-add and its psum, from the trace and the step's HLO."""
from bench import program_trace


def read(record: dict) -> float | None:
    return program_trace.stage_ms(record, "reduce")
