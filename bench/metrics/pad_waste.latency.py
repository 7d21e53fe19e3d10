"""The share of the window's bucket slots that carried padding rather
than a request (the server's flush counters)."""


def read(run):
    flushes = run.obs.get("flushes")
    if not flushes:
        return None
    slots = sum(b * n for b, n in flushes.items())
    return 100.0 * (slots - run.obs["served"]) / slots if slots else None
