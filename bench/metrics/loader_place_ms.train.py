"""Host milliseconds per step in the program's `loader.place` span, on
`ShardedLoader`'s producer thread: loading one batch and placing it on
the device, from the trace's host plane."""
from bench import program_trace


def read(record: dict) -> float | None:
    return program_trace.span_ms(record, "loader.place")
