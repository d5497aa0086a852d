"""Plan build: host seconds around the cell's ``prepare`` (cluster,
permute, tile, place) until its tile image is on the device."""


def read(win):
    return win.setup.get("plan_build_s")
