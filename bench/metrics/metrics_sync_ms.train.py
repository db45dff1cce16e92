"""Host milliseconds per step in the program's `dpmr.metrics_sync` span
of `DPMREngine.train_step`: the host reads of the step's loss, accuracy
and overflow, which wait for the device, from the trace's host plane."""
from bench import program_trace


def read(record: dict) -> float | None:
    return program_trace.span_ms(record, "dpmr.metrics_sync")
