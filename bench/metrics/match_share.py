"""Share of the traced part of the window inside ``accel.match`` spans (one
per segment the matcher takes), in percent."""


def read(ctx):
    total = sum(d for name, _, d in ctx["spans"] if name == "accel.match")
    if not total or not ctx["traced_s"]:
        return None
    return 100.0 * total / ctx["traced_s"]
