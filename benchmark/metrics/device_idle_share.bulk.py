"""Share of the traced window in which no operation ran on the chip, in
the bulk push cell."""
import lib


def read(data):
    return lib.idle_share(data)
