"""``flash_bd_tiles_pct``: of the score tiles a block-diffusion call's
forward grid holds, the share its walk visits — the program's
``attention.flash.tiles_visited`` over ``.tiles_total`` in the series
labelled ``mask="block_diffusion"``, counted when the call is traced (one
(batch · head) slice: 80 of 256 tiles of 512 x 512 over the 8,192 rows of
two 4,096-id streams in blocks of 4: 31.25).  Nothing where no
block-diffusion flash call was traced."""


def read(record, name):
    tiles = (record["counters"].get("attention") or {}).get("bd_tiles")
    if not tiles or not tiles["total"]:
        return None
    return 100.0 * tiles["visited"] / tiles["total"]
