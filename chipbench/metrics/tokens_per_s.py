"""Output tokens delivered in the window over the window's length (host
clock).  A ``fixed`` window (a backlog deeper than the window) runs from the
first due time for --seconds; a ``drain`` window from the first due time to
the last completion."""


def read(run):
    return run.window_tokens / run.window_s
