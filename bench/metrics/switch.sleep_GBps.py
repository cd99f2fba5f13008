"""Weight residency: bytes slept over the host-clock seconds of each
``WeightManager.sleep`` (it returns once the last chunk is on the host),
over every switch of the window (GB/s, 1e9 bytes)."""


def read(run):
    sw = run.records.get("switches")
    if not sw:
        return None
    return sum(s["bytes"] for s in sw) / sum(s["sleep_s"] for s in sw) / 1e9
