"""Layer: admit / memory.  The most live groups a flush of the window ran
in (span ``live_groups``): 1 for a program admitted as it stands; more
where admission found it over the watermark and the fused rung ran the
same one program with its live set bounded, each cut costing a few array
passes.  A program without the span key has nothing to read."""


def read(ctx):
    groups = [f["live_groups"] for s in ctx.solves for f in s.flushes
              if "live_groups" in f]
    return max(groups) if groups else None
