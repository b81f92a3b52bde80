"""Share of the traced window in which the device ran nothing while the
engine's innermost span was one of the scheduler's: ``serve.admit``,
``serve.readmit`` or ``serve.grant``, in percent."""

from _serve_spans import idle_share


def read(r):
    if r.kind != "serve" or r.trace is None:
        return None
    return idle_share(r, ("serve.admit", "serve.readmit", "serve.grant"))
