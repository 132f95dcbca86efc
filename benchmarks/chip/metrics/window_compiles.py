"""Programs compiled or loaded from the compile cache inside the traced
window: the program's ``jax.compile`` spans.  One reader serves every
``window_compiles.<part>`` name."""

from benchmarks.chip import hostlayers as hl


def read(run):
    if hl.compile_counter() is None:
        return None
    return hl.window_compiles(run.spans)
