"""Mean program launches per round (`dispatch_count` series, `total`)."""


def read(ctx):
    recs = ctx.series.get("dispatch_count", [])
    if not recs:
        return None
    return sum(r["value"]["total"] for r in recs) / len(recs)
