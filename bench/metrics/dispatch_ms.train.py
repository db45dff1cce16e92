"""Host milliseconds per step in the program's `dpmr.dispatch` span of
`DPMREngine.train_step`: the step-fn lookup, batch placement and the
jitted call up to its return, from the trace's host plane."""
from bench import program_trace


def read(record: dict) -> float | None:
    return program_trace.span_ms(record, "dpmr.dispatch")
