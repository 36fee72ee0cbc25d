"""Device idle time inside the program's ``runner.prepare`` and
``runner.dispatch`` spans (key split, host-to-device copies, the jitted
call until it returns) over the traced slice, in percent
(progtrace.dispatch_idle_ns)."""
import progtrace


def read(run):
    got = progtrace.dispatch_idle_ns(run)
    if got is None:
        return None
    idle, window = got
    return 100.0 * idle / window if window else None
