"""Device milliseconds per step under the `dpmr.split_hot` scope: the
hot/cold split (`core/hot_sharding.py` `split_hot`, its searchsorted of
every slot among the hot ids), from the trace and the step's HLO."""
from bench import program_trace


def read(record: dict) -> float | None:
    return program_trace.stage_ms(record, "split_hot")
