"""Device milliseconds a round that no top-level ``fleet.*`` scope holds.

Busy time is the union of every op on the device; the scopes count only
innermost ops, so this also holds the device time between the ops inside
loop ops (a ``while`` spans its body).  Each top-level scope is read
where a metric of the cell names it; a scope no metric names counts here.
"""

TOP = ("fleet.channel", "fleet.schedule", "fleet.solve", "fleet.gradient",
       "fleet.merge", "fleet.cloud_merge", "fleet.eval")


def read(red, ctx):
    units = ctx["work"]["units"]
    if red.busy_s <= 0.0 or units <= 0:
        return None
    scoped = sum(red.scope_s.get(s, 0.0) for s in TOP)
    return 1e3 * max(red.busy_s - scoped, 0.0) / units
