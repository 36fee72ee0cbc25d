"""Prompt tokens the prefill calls computed over the positions they
dispatched (rows x width), in percent: the program's counters
``prefill.tokens`` / ``prefill.rows``, counted by the scheduler over the
run."""
import progtrace


def read(run):
    c = progtrace.counters(run)
    if not c or not c.get("prefill.rows"):
        return None
    return 100.0 * c.get("prefill.tokens", 0) / c["prefill.rows"]
