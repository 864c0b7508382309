"""Host time inside the ``stage``, ``dispatch`` and ``readback`` spans over
steps: the harness's annotations in a resident cell, ``run_pass``'s
``tracer=`` spans in a loader-fed one. Issue time on the host, not device
time; ``readback`` includes waiting for the device."""

LAYER = "async runner (training/pipeline.py)"
UNIT = "ms/step"
MOVES = "samples_per_s_per_chip"
SOURCE = "program_span"


def read(run):
    seconds = run["spans"]["seconds"]
    if not run["window"]["steps"] or "dispatch" not in seconds:
        return None
    host = sum(seconds.get(name, 0.0) for name in ("stage", "dispatch", "readback"))
    return 1e3 * host / run["window"]["steps"]
