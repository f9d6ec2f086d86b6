"""``flash_window_tiles_pct``: of the score tiles a sliding-window
layer's forward grid holds, the share its walk visits — the program's
``attention.flash.tiles_visited`` over ``.tiles_total`` in the series
labelled with the window, counted when the call is traced (one (batch ·
head) slice: 70 of 256 tiles of 512 x 512 at 8,192 tokens under a window
of 2,048: 27.34).  Nothing where no windowed flash call was traced."""


def read(record, name):
    tiles = (record["counters"].get("attention") or {}).get(
        "window_tiles")
    if not tiles or not tiles["total"]:
        return None
    return 100.0 * tiles["visited"] / tiles["total"]
