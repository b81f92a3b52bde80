"""Seconds ``core/pipeline.specialize`` took in set-up (host clock)."""


def read(r):
    return r.setup.get("specialize_s")
