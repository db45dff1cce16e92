"""Device milliseconds per step under the `exchange` sub-scope: the
all-to-alls of the a2a strategy (requests and responses in distribute,
gradient sums in reduce), averaged over the chips, from the trace and the
step's HLO. None where no op lies under the scope."""
from bench import program_trace


def read(record: dict) -> float | None:
    r = program_trace.read_record(record)
    if not r or not record.get("steps") or "exchange" not in r["substages"]:
        return None
    return r["substages"]["exchange"] / record["steps"] * 1e3
