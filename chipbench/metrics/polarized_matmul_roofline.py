"""The least time the traced polarized-matmul calls could take over the
device time of their events, in percent.  Per call the least time is the
larger of its operations over the bf16 peak and its bytes (codes, signs,
scales, activations in and out) over HBM bandwidth (work.kernel_call); the
calls of a runner call follow from its rows and width (decode: rows = slots,
one call per matmul per step; prefill: rows = slots x chunk width)."""


def read(run):
    tr = run.trace
    if tr is None:
        return None
    least = 0.0
    calls = tr.calls("decode") + tr.calls("prefill")
    for _, _, nums in tr.calls("decode"):
        rows, steps = nums[0], nums[1]
        least += sum(run.work.kernel_least_seconds(run.model, run.forms,
                                                   rows, steps, run.peak))
    for _, _, (rows, width) in tr.calls("prefill"):
        least += sum(run.work.kernel_least_seconds(run.model, run.forms,
                                                   rows * width, 1, run.peak))
    secs = sum(tr.seconds_in(tr.kernel_events(), sorted(calls)))
    return 100.0 * least / secs if secs else None
