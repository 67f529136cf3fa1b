"""Process start to window start: model build, compilation (or the compile
cache), warm-up of the cell's shapes, the reference check and the ramp."""


def reduce(src):
    return src.get("setup_s")
