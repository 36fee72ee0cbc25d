"""Model operations of the traced decode rounds over (the device time of the
decode program's events x the chip's bf16 peak), in percent: the whole
decode step's share of the chip's peak, beside the polarized kernel's share
of its roofline.  Per round: each active row's steps through every matmul
(the family's ``matmul_params``) plus attention over the K/V positions it
read."""


def read(run):
    tr = run.trace
    if tr is None:
        return None
    calls = tr.calls("decode")
    busy = sum(tr.seconds_in(tr.module_events("jit__decode_fn"), calls))
    if not busy:
        return None
    ops = sum(run.work.decode_flops(run.model, active * steps, kv)
              for _, _, (_, steps, kv, active) in calls)
    return 100.0 * ops / (busy * run.peak["bf16_flops"])
