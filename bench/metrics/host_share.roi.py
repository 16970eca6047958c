"""Share (%) of the ROI window the volume service's own host work held
the host, from the program's spans: ``engine.tick`` and ``engine.submit``
less ``executor.fetch`` (the wait for the device), over the window."""

from bench.program_spans import host_share


def read(run):
    return host_share(run)
