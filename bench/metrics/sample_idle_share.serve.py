"""Share of the traced window in which the device ran nothing while the
engine's innermost span was ``serve.sample`` (the per-slot sampling
loop, with its wait for the program it samples from), in percent."""

from _serve_spans import idle_share


def read(r):
    if r.kind != "serve" or r.trace is None:
        return None
    return idle_share(r, ("serve.sample",))
