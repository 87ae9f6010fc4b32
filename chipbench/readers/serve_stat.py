"""One number of the served window, as ``open_loop.reduce`` made it from the
token stamps, the benchmark's host spans and the samples taken at each
decode step (``ctx["serve"]``).  Not there, no number."""


def read(ctx, key):
    return (ctx.get("serve") or {}).get(key)
