"""Transfer engine: chunks sent over a relay chip as a share of all chunks
of the window's fetches (%), from the engine's per-link counters."""


def read(run):
    direct = run.records.get("chunks_direct")
    relay = run.records.get("chunks_relay")
    if direct is None or direct + relay == 0:
        return None
    return 100.0 * relay / (direct + relay)
