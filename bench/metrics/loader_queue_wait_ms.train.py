"""Host milliseconds per step in the program's `loader.wait` span: the
training loop waiting on `ShardedLoader`'s queue of placed batches, from
the trace's host plane."""
from bench import program_trace


def read(record: dict) -> float | None:
    return program_trace.span_ms(record, "loader.wait")
