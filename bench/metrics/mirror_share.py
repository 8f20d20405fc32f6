"""Share of the window spent patching and rebuilding the match-state mirror
(``ArrayMatchEngine.patch_s + rebuild_s``), in percent."""


def read(ctx):
    return 100.0 * ctx["mirror_s"] / ctx["window_s"]
