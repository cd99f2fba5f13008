"""Weight residency: bytes woken over the host-clock seconds of each
``WeightManager.wake`` (it blocks on the woken weights), over every switch
of the window (GB/s, 1e9 bytes)."""


def read(run):
    sw = run.records.get("switches")
    if not sw:
        return None
    return sum(s["bytes"] for s in sw) / sum(s["wake_s"] for s in sw) / 1e9
