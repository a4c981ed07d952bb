"""qps: queries answered in a closed-loop window, over the window from the
first call to the end of the last.  Host clock.  Closed loops only."""


def read(ctx):
    rec = ctx.rec
    if rec["loop"] != "closed":
        return None
    done = sum(len(c["qidx"]) for c in rec["calls"] if c["res"] is not None)
    return done / (rec["end"] - rec["t0"])
