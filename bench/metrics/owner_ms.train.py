"""Device milliseconds per step of the owner side: the ops under the
`owner_apply` and `owner_accumulate` sub-scopes together, the lookup and
the scatter-add over the (P, cap) buffers each chip receives, averaged
over the chips, from the trace and the step's HLO. None where no op lies
under either scope."""
from bench import program_trace

SCOPES = ("owner_apply", "owner_accumulate")


def read(record: dict) -> float | None:
    r = program_trace.read_record(record)
    if not r or not record.get("steps") or \
            not set(SCOPES) & set(r["substages"]):
        return None
    return sum(r["substages"].get(s, 0.0) for s in SCOPES) \
        / record["steps"] * 1e3
