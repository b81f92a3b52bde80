"""Share of the traced window in which the device ran nothing while the
engine's innermost span was one of the cache manager's block moves:
``serve.spill``, ``serve.promote`` or ``serve.cow``, in percent."""

from _serve_spans import idle_share


def read(r):
    if r.kind != "serve" or r.trace is None:
        return None
    return idle_share(r, ("serve.spill", "serve.promote", "serve.cow"))
