"""Writer-thread seconds in the program's dictionary encode (its ingest.encode
spans that ran inside the window, summed) per million rows acknowledged."""


def read(art):
    durs = [r["dur"] for r in art.spans
            if r["name"] == "ingest.encode" and r["t0"] + r["dur"] <= art.window_s]
    if not durs or not art.acked_rows:
        return None
    return sum(durs) / (art.acked_rows / 1e6)
