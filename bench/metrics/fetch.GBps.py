"""Data plane: all bytes fetched over all fetch seconds of the window
(GB/s, 1e9 bytes)."""


def read(run):
    f = run.records.get("fetches")
    if not f:
        return None
    return sum(x["bytes"] for x in f) / sum(x["seconds"] for x in f) / 1e9
