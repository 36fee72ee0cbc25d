"""Model operations of the prompt tokens prefilled and the tokens decoded by
runner calls that ended in the window (the family's ``token_flops``,
attention over the live context) over (the window x the chip's bf16 peak), in percent."""


def read(run):
    mc, end = run.model, run.window_end
    ops = sum(run.work.prefill_flops(mc, start, n)
              for c in run.recorder.prefills if c["t1"] <= end
              for start, n in c["spans"])
    ops += sum(run.work.token_flops(mc, int(p) + j + 1)
               for r in run.recorder.rounds if r["t1"] <= end
               for p, take in zip(r["positions"], r["takes"])
               for j in range(take))
    return 100.0 * ops / (run.window_s * run.peak["bf16_flops"])
