"""Share of the window spent producing and classifying check-in chunks
(``Simulator._load_next_chunk``, ``sim.stream_wall_s``), in percent."""


def read(ctx):
    if ctx["stream_s"] is None:
        return None
    return 100.0 * ctx["stream_s"] / ctx["window_s"]
