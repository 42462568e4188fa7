"""Share of ``runner.run`` span time, in %, inside ``buffers.build`` spans:
the Runner's host-side working-set construction (``repro.obs`` spans of the
traced window)."""


def read(ctx):
    if not ctx.spans:
        return None
    run = sum(e["dur"] for e in ctx.spans
              if e.get("ph") == "X" and e["name"] == "runner.run")
    build = sum(e["dur"] for e in ctx.spans
                if e.get("ph") == "X" and e["name"] == "buffers.build")
    return 100.0 * build / run if run > 0 else None
