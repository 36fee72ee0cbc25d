"""Rows of the held experts' matmuls that carried a routed token: the (row,
held expert) pairs the router sent to this chip's experts over the rows
those experts computed (each held expert runs over every row), in percent.
The program's device counters ``moe.routed`` / ``moe.rows``
(models/moe.py), summed over every prefill and decode call of the run,
warm-up included."""
import progtrace


def read(run):
    c = progtrace.counters(run)
    if not c or not c.get("moe.rows"):
        return None
    return 100.0 * c.get("moe.routed", 0) / c["moe.rows"]
