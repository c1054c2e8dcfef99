"""Share of the traced window in which no operation ran on a chip,
averaged over the four chips of the merge cell."""
import lib


def read(data):
    return lib.idle_share(data)
