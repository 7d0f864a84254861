"""Device programs: XLA backend compiles that the program's counter
(``repro.obs.spans.compiles``) saw during the traced commit; a measured
window should hold none."""


def read(ctx):
    return getattr(ctx, "compiles", None)
