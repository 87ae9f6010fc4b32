"""The share of a decode step's routed experts that a live token chose:
the program's ``tm_moe_experts_touched_total`` over the experts there are
(the configuration's ``experts`` key, a layer: one series of the counter a
layer) times the decode steps it counted (``tm_moe_decode_steps_total``).
Every decode step of the process.  Under even routing with s live slots and
top-k of n it is ``1 - (1 - k / n) ** s``; a drift of the load shows as a
lower share at the same occupancy.  A program without the counters gives no
number."""


def read(ctx, experts):
    try:
        from torchmpi_tpu import obs
    except ImportError:
        return None
    registry = obs.registry()
    steps = registry.counter_total("tm_moe_decode_steps_total")
    layers = sum(r["name"] == "tm_moe_experts_touched_total"
                 for r in registry.snapshot())
    if not steps or not layers:
        return None
    return (registry.counter_total("tm_moe_experts_touched_total")
            / (ctx["cell"].config[experts] * layers * steps))
