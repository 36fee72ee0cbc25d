"""Bytes the traced decode rounds must read over (their device time x the
chip's HBM bandwidth), in percent.  Per model step: the served weights in
their stored format (the family's ``decode_weight_bytes``) plus the K/V of
each active slot's live positions; device time is that of the decode
program's events."""


def read(run):
    tr = run.trace
    if tr is None:
        return None
    calls = tr.calls("decode")
    if not calls:
        return None
    secs = tr.seconds_in(tr.module_events("jit__decode_fn"), calls)
    per_pos = run.work.kv_bytes_per_position(run.model)
    need = sum(steps * run.work.decode_weight_bytes(run.model, run.forms,
                                                    rows)
               + kv * per_pos for _, _, (rows, steps, kv, _) in calls)
    busy = sum(secs)
    return 100.0 * need / (busy * run.peak["hbm_bytes_per_s"]) if busy else None
