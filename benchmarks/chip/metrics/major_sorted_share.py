"""Share of the base slabs' capacity that the window's majors sorted: the
event rows each ingest.major span reports it sorted (sort_rows, the live
fill bucket), summed, over the capacity it reports (capacity_rows), summed.
None where the spans carry no such arguments."""


def read(art):
    spans = [r for r in art.spans
             if r["name"] == "ingest.major" and r["t0"] + r["dur"] <= art.window_s
             and "sort_rows" in r["args"] and "capacity_rows" in r["args"]]
    if not spans:
        return None
    return (sum(r["args"]["sort_rows"] for r in spans)
            / sum(r["args"]["capacity_rows"] for r in spans))
