"""Device self time of the operations under the program's ``kv_gather`` and
``kv_commit`` scopes (the KV pool's gather into per-slot views and the
commit of new rows, serving/kv_cache.py) over the device time of the runner
programs in the traced slice, in percent (progtrace.scope_seconds)."""
import progtrace


def read(run):
    by = progtrace.scope_seconds(run)
    if not by:
        return None
    return 100.0 * sum(by.get(s, 0.0) for s in progtrace.KV_SCOPES) / by["programs"]
