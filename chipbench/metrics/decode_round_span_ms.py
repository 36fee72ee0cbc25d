"""Mean length of the program's ``runner.decode`` span, the scheduler's
call of a decode round until its tokens are on the host, over the complete
spans of the traced slice (progtrace.decode_span_ms): the slice's mean, not
the window's."""
import progtrace


def read(run):
    return progtrace.decode_span_ms(run)
