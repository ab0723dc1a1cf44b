"""Mean duration of the program's ingest.major spans (a writer-tripped plane-wide
major, ending in block_until_ready) that ran inside the window."""


def read(art):
    durs = [r["dur"] for r in art.spans
            if r["name"] == "ingest.major" and r["t0"] + r["dur"] <= art.window_s]
    return sum(durs) / len(durs) if durs else None
