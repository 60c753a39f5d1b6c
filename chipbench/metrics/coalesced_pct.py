"""Share of the queries the server answered through a coalesced
multi-query dispatch over the window (``QueryServer.stats``)."""


def read(ctx):
    st = ctx.window.get("server")
    if not st or not st["served"]:
        return None
    return 100.0 * st["coalesced_queries"] / st["served"]
