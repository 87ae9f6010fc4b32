"""``readers/serve_scopes_in_module.py`` over ANOTHER count of the traced
stretch: ``per`` names the entry of ``ctx["traced"]`` the device
milliseconds are divided by (``"prefills"``: the admissions the loop made in
the same stretch, for a scope of ``jit__slot_prefill_jit``), where the
reader there divides by the decode steps.  No such count, no execution or no
match (a program without the scopes), no number."""

from chipbench import harness


def read(ctx, per, **args):
    traced = ctx.get("traced") or {}
    if not traced.get(per):
        return None
    inner = harness.load_module(ctx["cell"].manifest, "readers",
                                "serve_scopes_in_module")
    return inner.read({**ctx, "traced": {**traced, "steps": traced[per]}},
                      **args)
