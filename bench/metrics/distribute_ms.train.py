"""Device milliseconds per step under the `dpmr.distribute` scope: the
strategy's distribute (route_build, the exchange, owner_apply,
route_return), the hot-table lookup and assembling theta, from the trace
and the step's HLO."""
from bench import program_trace


def read(record: dict) -> float | None:
    return program_trace.stage_ms(record, "distribute")
