"""Milliseconds per admission (``ServeEngine._ingest_request``: prefill and
the per-pool page scatters), from the benchmark's span around each call;
in the traced run the span ends at a device sync, so it holds the device
work.  None in a window with no admission."""


def read(r):
    n = r.spans.count("admit")
    return 1e3 * r.spans.total("admit") / n if n else None
