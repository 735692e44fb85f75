"""Lets the rehearsal suite (``test_cells.py``, as accepted) run with the
configuration PR 30 added, whose sizes are a grid and a number of years
and not an ``n``: ``toy_checkout`` also cuts it to two years of a 9 x 20
grid (the 366 labels kept), so every test that rehearses every cell
rehearses ``doy-clim`` too."""

import json

import test_cells

DOY_CONFIG = "benchmark/configs/xr-doy-clim-era5grid.json"

test_cells.TOY.setdefault("doy_clim", 0)  # written as ``n``, which it ignores
_toy_checkout = test_cells.toy_checkout


def toy_checkout(tmp_path):
    checkout = _toy_checkout(tmp_path)
    with open(checkout / DOY_CONFIG) as f:
        cfg = json.load(f)
    cfg.update(grid=[9, 20], years=2)
    with open(checkout / DOY_CONFIG, "w") as f:
        json.dump(cfg, f)
    return checkout


test_cells.toy_checkout = toy_checkout
