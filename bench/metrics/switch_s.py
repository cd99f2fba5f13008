"""Time per model switch (s): from the arrival of a request for the
sleeping weights to its first token (sleep, wake, prefill), the mean over
every switch of the window."""


def read(run):
    sw = run.records.get("switches")
    if not sw:
        return None
    return sum(s["first_token"] - s["arrival"] for s in sw) / len(sw)
