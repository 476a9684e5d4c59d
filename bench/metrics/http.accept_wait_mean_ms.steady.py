"""accept() returning -> first line of the handler (thread start,
request line, headers), mean over the window: `http.accept_wait`."""
from bench import phases


def read(ctx):
    return phases.mean_ms(ctx, "http.accept_wait")
