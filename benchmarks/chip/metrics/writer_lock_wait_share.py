"""Share of the writers' time in the window spent waiting to take a tablet
group's lock for an append (the acquire wait each ingest_append hold booked,
carried by its lock span), over writers x window."""


def read(art):
    waits = [r["args"]["wait_s"] for r in art.spans
             if r["cat"] == "lock" and r["args"].get("owner") == "ingest_append"
             and "wait_s" in r["args"]]
    if not art.writers or not waits:
        return None
    return sum(waits) / (art.writers * art.window_s)
