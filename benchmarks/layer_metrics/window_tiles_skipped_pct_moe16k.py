"""Share of the causally live key tiles that the window layers' windows
skip, over the four layers: 100 x (1 - live tiles run / live tiles four full
layers would run), from the flash kernels' own tile walks
(``ops/pallas/flash_attention.py::_count_tiles``, counted where a kernel is
traced, apart for calls with a window and without; per (batch, head) walk, so
that how often a program was traced cancels). The geometry at 16,384
positions and a window of 4,096 is 56% on a window layer, 0 on the full one:
42% over the period, to a tile's rounding."""

from benchmarks.lib import smallthinker_steps


def read(ctx):
    tiles = smallthinker_steps.window_tiles()
    if tiles is None:
        return None
    window, full, walks_w, walks_f = tiles
    return 100.0 * walks_w * (full - window) / ((walks_w + walks_f) * full)
