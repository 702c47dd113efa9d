"""Output tokens delivered in the window over the window's seconds; tokens
of requests still in flight at its close count."""


def read(run):
    return run.tokens / run.window_s
