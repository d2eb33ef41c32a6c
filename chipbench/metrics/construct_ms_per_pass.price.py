"""Host milliseconds per pass spent building the pass's trace (the model
step's page allocation and every block its generator yields), from the
benchmark's spans.  None where the passes build nothing."""


def read(r):
    passes = r.values.get("passes")
    n = r.spans.count("construct")
    return 1e3 * r.spans.total("construct") / passes if n and passes \
        else None
